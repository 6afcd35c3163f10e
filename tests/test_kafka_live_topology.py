"""Orders over TCP in the repo's OWN live topology.

VERDICT r2 "Next #2": the reference runs its async tier continuously —
checkout publishes to a real broker over the network and consumer
groups poll it (/root/reference/src/checkout/kafka/producer.go:11-43,
src/fraud-detection/.../main.kt:54-69, src/accounting/Consumer.cs:77-80).
These tests run THAT topology with this repo's own pieces:

- In-proc tier: a live ``Shop`` on ``KafkaBus`` against a socket
  ``KafkaBroker`` — checkout → Produce v3 (v2 RecordBatch, trace
  headers) → accounting + fraud-detection consumer groups, trace
  context surviving the async boundary.
- Process tier (module fixture): broker + ``serve_shop --kafka`` +
  detector daemon (``KAFKA_ADDR``) as three OS processes; a flag flip
  over the flag-editor HTTP surface floods the topic and the daemon's
  detector flags the orders lane, while ``broker.committed()`` shows
  all three consumer groups advancing.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import time
import urllib.request

import pytest

from opentelemetry_demo_tpu.runtime.kafka_broker import KafkaBroker
from opentelemetry_demo_tpu.services.shop import Shop, ShopConfig
from opentelemetry_demo_tpu.telemetry.tracer import TraceContext

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestLiveShopOverBroker:
    """The in-proc shop with its async tier on a real socket."""

    def _shop(self, broker: KafkaBroker, users: int = 0) -> Shop:
        return Shop(ShopConfig(
            users=users, seed=7,
            kafka_bootstrap=f"127.0.0.1:{broker.port}",
        ))

    def _checkout(self, shop: Shop, user: str) -> None:
        ctx = TraceContext.new()
        shop.cart.add_item(ctx, user, "EYE-PLO-25", 2)
        shop.checkout.place_order(ctx, user, "USD", f"{user}@example.com")

    @staticmethod
    def _pump_until(shop: Shop, cond, timeout_s: float = 10.0) -> None:
        """Delivery is asynchronous (background sender + socket), so
        pump on a loop until the condition holds."""
        deadline = time.monotonic() + timeout_s
        t = 1.0
        while time.monotonic() < deadline:
            shop.pump(t)
            if cond():
                return
            t += 0.25
            time.sleep(0.05)
        raise AssertionError("condition not reached before timeout")

    def test_orders_cross_the_socket_to_both_groups(self):
        broker = KafkaBroker()
        broker.start()
        try:
            shop = self._shop(broker)
            for i in range(3):
                self._checkout(shop, f"u{i}")
            self._pump_until(
                shop,
                lambda: shop.accounting.orders_seen >= 3
                and shop.fraud.orders_checked >= 3,
            )
            assert shop.accounting.orders_seen == 3
            assert shop.fraud.orders_checked == 3
            # Both groups committed their positions ON THE BROKER — the
            # wire-visible proof this was consumption, not an in-proc
            # shortcut (Consumer.cs:77-80 auto-commit semantics).
            assert broker.committed("accounting", "orders") == 3
            assert broker.committed("fraud-detection", "orders") == 3
            shop.bus.close()
        finally:
            broker.stop()

    def test_trace_context_survives_the_async_boundary(self):
        broker = KafkaBroker()
        broker.start()
        try:
            shop = self._shop(broker)
            self._checkout(shop, "u-trace")
            self._pump_until(
                shop, lambda: shop.accounting.orders_seen >= 1
            )
            shop.pump(20.0)  # flush consumer spans to the collector
            # One trace spans the producer AND both consumers: the W3C
            # context rode the v2 record headers (main.go:631-637).
            crossing = [
                t for t in shop.collector.trace_store._traces.values()
                if "checkout" in t.services
                and "fraud-detection" in t.services
                and "accounting" in t.services
            ]
            assert crossing, "no trace crossed checkout → consumers"
            shop.bus.close()
        finally:
            broker.stop()

    def test_broker_bounce_mid_run_buffers_not_crashes(self):
        """A broker restart while the shop holds open sockets: the dead
        connection surfaces as KafkaWireError (half-open) or OSError —
        either way checkout must buffer, not 500 the customer, and
        delivery resumes on the restarted broker."""
        broker = KafkaBroker()
        broker.start()
        port = broker.port
        shop = self._shop(broker)
        self._checkout(shop, "u-pre")
        self._pump_until(shop, lambda: shop.accounting.orders_seen >= 1)
        broker.stop()
        self._checkout(shop, "u-down")  # must not raise
        shop.pump(2.0)
        broker2 = KafkaBroker(port=port)
        broker2.start()
        try:
            deadline = time.monotonic() + 15.0
            t = 3.0
            posted = False
            while time.monotonic() < deadline:
                if not posted:
                    self._checkout(shop, "u-post")
                    posted = True
                t += 0.5
                shop.pump(t)
                if shop.accounting.orders_seen >= 3:
                    break
                time.sleep(0.2)
            # All three orders arrived: pre-bounce, buffered, post.
            assert shop.accounting.orders_seen >= 3
            shop.bus.close()
        finally:
            broker2.stop()

    def test_broker_down_buffers_then_delivers(self):
        """A broker that isn't up yet means retry, not crash: publishes
        buffer producer-side and flow once the broker appears (the
        compose parallel-start reality)."""
        probe = KafkaBroker()
        probe.start()
        addr_port = probe.port
        probe.stop()  # now a dead address
        shop = Shop(ShopConfig(
            users=0, seed=7, kafka_bootstrap=f"127.0.0.1:{addr_port}",
        ))
        self._checkout(shop, "u-early")  # must not raise
        shop.pump(0.5)
        assert shop.accounting.orders_seen == 0
        broker = KafkaBroker(port=addr_port)
        broker.start()
        try:
            deadline = time.monotonic() + 10.0
            t = 1.0
            while time.monotonic() < deadline:
                # Next publish drains the buffer; pumps deliver.
                self._checkout(shop, "u-late")
                t += 0.5
                shop.pump(t)
                if shop.accounting.orders_seen >= 2:
                    break
                time.sleep(0.2)
            assert shop.accounting.orders_seen >= 2, "buffered order lost"
            shop.bus.close()
        finally:
            broker.stop()


# --- three-process topology ------------------------------------------


def _clean_env() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["PYTHONUNBUFFERED"] = "1"
    return env


def _wait_line(proc, pattern: str, timeout_s: float = 90.0) -> str:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"process exited rc={proc.returncode} before '{pattern}'"
                )
            time.sleep(0.05)
            continue
        if re.search(pattern, line):
            return line
    raise TimeoutError(f"no line matching {pattern!r} within {timeout_s}s")


def _get(url: str, timeout: float = 10.0) -> bytes:
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read()


def _post_json(url: str, doc: dict, timeout: float = 10.0) -> int:
    req = urllib.request.Request(
        url, data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json"}, method="POST",
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        resp.read()
        return resp.status


@pytest.fixture(scope="module")
def kafka_topology():
    broker = KafkaBroker(host="127.0.0.1")
    broker.start()
    bootstrap = f"127.0.0.1:{broker.port}"

    daemon_env = _clean_env()
    daemon_env.update({
        "KAFKA_ADDR": bootstrap,
        "ANOMALY_OTLP_PORT": "0",
        "ANOMALY_OTLP_GRPC_PORT": "0",
        "ANOMALY_METRICS_PORT": "0",
        "ANOMALY_BATCH": "128",
        "ANOMALY_PUMP_INTERVAL_S": "0.05",
        # Small geometry: the e2e tests the topology, not the sketch
        # sizes (full geometry costs minutes of XLA CPU compile).
        "ANOMALY_NUM_SERVICES": "16",
        "ANOMALY_CMS_WIDTH": "512",
        "ANOMALY_HLL_P": "8",
        "ANOMALY_WARMUP_BATCHES": "6",
        # The z gate must open BEFORE the flood: EWMA baselines keep
        # adapting during warmup, so a burst that arrives while the
        # service is still warming is absorbed into the mean instead of
        # scored against it. 40 healthy order-batches is a ~10 s warm
        # phase here.
        "ANOMALY_Z_WARMUP_BATCHES": "40",
    })
    daemon = subprocess.Popen(
        [sys.executable, "-m", "opentelemetry_demo_tpu.runtime.daemon"],
        cwd=REPO, env=daemon_env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
    )
    shop = None
    try:
        line = _wait_line(daemon, r"anomaly-detector: otlp-http :\d+")
        metrics_port = int(re.search(r"metrics :(\d+)", line).group(1))
        shop = subprocess.Popen(
            [
                sys.executable, "scripts/serve_shop.py",
                "--host", "127.0.0.1", "--port", "0", "--users", "0",
                "--kafka", bootstrap,
            ],
            cwd=REPO, env=_clean_env(),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        line = _wait_line(shop, r"shop gateway on http://")
        shop_port = int(re.search(r"http://[^:]+:(\d+)", line).group(1))
        yield {
            "broker": broker,
            "shop": f"http://127.0.0.1:{shop_port}",
            "daemon_metrics": f"http://127.0.0.1:{metrics_port}",
        }
    finally:
        for proc in (shop, daemon):
            if proc is not None:
                proc.terminate()
        for proc in (shop, daemon):
            if proc is not None:
                try:
                    proc.wait(timeout=15)
                except subprocess.TimeoutExpired:
                    proc.kill()
        broker.stop()


def _checkout_http(base: str, session: str) -> None:
    _post_json(f"{base}/api/cart", {
        "userId": session,
        "item": {"productId": "TEL-DOB-10", "quantity": 1},
    })
    _post_json(f"{base}/api/checkout", {
        "userId": session,
        "email": f"{session}@example.com",
        "currencyCode": "USD",
    })


def test_flood_flag_lights_detector_through_the_broker(kafka_topology):
    """The full reference order path, three processes: HTTP checkout →
    shop → Produce v3 → broker → daemon's anomaly-detector group → z/
    CUSUM flag on the orders lane; accounting + fraud-detection commit
    beside it."""
    broker: KafkaBroker = kafka_topology["broker"]
    shop = kafka_topology["shop"]
    metrics = kafka_topology["daemon_metrics"]

    # Healthy phase: steady 1-order batches until the orders lane is
    # past its z warmup (40 observed batches) — the burst must be
    # scored against a SETTLED baseline, not absorbed into a warming
    # one. Each checkout is one record, and at this pacing one batch.
    deadline = time.monotonic() + 360.0
    ingested = 0.0
    i = 0
    while time.monotonic() < deadline:
        _checkout_http(shop, f"warm-{i}")
        i += 1
        text = _get(f"{metrics}/metrics").decode()
        m = re.search(
            r"^app_anomaly_spans_processed_total (\d+\.?\d*)", text, re.M
        )
        if m and float(m.group(1)) >= 55:
            ingested = float(m.group(1))
            break
        time.sleep(0.15)
    assert ingested >= 55, "daemon never ingested orders off the broker"

    # Flood: kafkaQueueProblems makes checkout re-publish each order N
    # times (producer flood, main.go:603-613) — a rate burst on the
    # checkout-orders lane the detector must flag.
    status = _post_json(f"{shop}/feature/api/write-to-file", {"data": {
        "flags": {
            "kafkaQueueProblems": {
                "state": "ENABLED",
                "variants": {"on": 80, "off": 0},
                "defaultVariant": "on",
            }
        }
    }})
    assert status == 200

    flagged = False
    deadline = time.monotonic() + 240.0
    j = 0
    while time.monotonic() < deadline and not flagged:
        _checkout_http(shop, f"flood-{j}")
        j += 1
        text = _get(f"{metrics}/metrics").decode()
        if re.search(
            r'app_anomaly_flags_total\{service="checkout-orders"\} [1-9]',
            text,
        ):
            flagged = True
            break
        time.sleep(0.3)
    assert flagged, "flood never lit the detector on the orders lane"

    # All three consumer groups advanced on the SAME broker — the
    # reference's fan-out consumption pattern, wire-visible.
    for group in ("accounting", "fraud-detection", "anomaly-detector"):
        assert broker.committed(group, "orders") > 0, group
