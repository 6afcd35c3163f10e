"""Cross-process e2e: the compose topology on localhost sockets.

VERDICT r1 "Next #9": shop gateway and detector daemon as SEPARATE
processes (the docker-compose.yml:226-256 wiring), spans crossing a
real process boundary over OTLP/HTTP, a fault flag injected over the
flag-editor HTTP surface, and the detector flagging the right service —
observed on the daemon's own Prometheus port.

Heavier than the in-proc suites (two interpreters, jit compile in the
daemon), so everything funnels through one module-scoped topology.
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

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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
def topology():
    env = dict(_clean_env())
    env.update({
        "ANOMALY_OTLP_PORT": "0",
        "ANOMALY_OTLP_GRPC_PORT": "0",
        "ANOMALY_METRICS_PORT": "0",
        "ANOMALY_BATCH": "128",
        "ANOMALY_PUMP_INTERVAL_S": "0.05",
        # Small sketch geometry: the default (cms 8192 × hll 4096) takes
        # minutes of XLA CPU compile; the e2e tests the topology, not
        # the geometry.
        "ANOMALY_NUM_SERVICES": "16",
        "ANOMALY_CMS_WIDTH": "512",
        "ANOMALY_HLL_P": "8",
        "ANOMALY_WARMUP_BATCHES": "8",
    })
    daemon = subprocess.Popen(
        [sys.executable, "-m", "opentelemetry_demo_tpu.runtime.daemon"],
        cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )
    shop = None
    try:
        line = _wait_line(daemon, r"anomaly-detector: otlp-http :\d+")
        otlp_port = int(re.search(r"otlp-http :(\d+)", line).group(1))
        metrics_port = int(re.search(r"metrics :(\d+)", line).group(1))

        shop = subprocess.Popen(
            [
                sys.executable, "scripts/serve_shop.py",
                "--host", "127.0.0.1", "--port", "0", "--users", "0",
                "--otlp-endpoint", f"http://127.0.0.1:{otlp_port}",
            ],
            cwd=REPO, env=_clean_env(),
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        line = _wait_line(shop, r"shop gateway on http://")
        shop_port = int(re.search(r"http://[^:]+:(\d+)", line).group(1))
        yield {
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


def _checkout(base: str, session: str) -> None:
    _post_json(f"{base}/api/cart", {
        "userId": session,
        "item": {"productId": "TEL-DOB-10", "quantity": 1},
    })
    try:
        _post_json(f"{base}/api/checkout", {
            "userId": session,
            "email": f"{session}@example.com",
            "currencyCode": "USD",
        })
    except urllib.error.HTTPError:
        pass  # paymentFailure phase: 500 is the expected shape


def test_fault_flag_lights_detector_across_process_boundary(topology):
    shop = topology["shop"]
    daemon_metrics = topology["daemon_metrics"]

    # Warmup: the daemon's FIRST batch triggers the detector's jit
    # compile, during which its pump is stalled and spans pile into a
    # few giant batches; and the sync harvester keeps one report in
    # flight for overlap, so the counter needs a SECOND batch to appear.
    # Keep trickling checkouts until the first harvested report shows —
    # pacing only matters after that.
    deadline = time.monotonic() + 120.0
    compiled = False
    i = 0
    while time.monotonic() < deadline:
        _checkout(shop, f"warmup-{i}")
        i += 1
        text = _get(f"{daemon_metrics}/metrics").decode()
        if re.search(r"^app_anomaly_spans_processed_total \d", text, re.M):
            compiled = True
            break
        time.sleep(0.3)
    assert compiled, "daemon never harvested its first report (compile?)"

    # Phase 1 — healthy traffic: enough payment batches to warm the
    # detector's per-service baselines (warmup_batches=8 via env).
    for i in range(16):
        _checkout(shop, f"user-{i}")
        time.sleep(0.07)  # spread across pump windows → distinct batches

    # The daemon has genuinely ingested spans across the boundary.
    deadline = time.monotonic() + 60.0
    spans_seen = 0.0
    while time.monotonic() < deadline:
        text = _get(f"{daemon_metrics}/metrics").decode()
        m = re.search(
            r"^app_anomaly_spans_processed_total (\d+\.?\d*)", text, re.M
        )
        if m and float(m.group(1)) >= 100:
            spans_seen = float(m.group(1))
            break
        time.sleep(0.5)
    assert spans_seen >= 100, "daemon never ingested the shop's spans"

    # Phase 2 — inject paymentFailure over the flag-editor HTTP surface
    # (the flagd-ui path), the cross-process analogue of flipping the
    # flag in flagd's config.
    status = _post_json(f"{shop}/feature/api/write-to-file", {"data": {
        "flags": {
            "paymentFailure": {
                "state": "ENABLED",
                "variants": {"on": 1.0, "off": 0.0},
                "defaultVariant": "on",
            }
        }
    }})
    assert status == 200

    # Error bursts: several failing charges per pump window integrate
    # the payment CUSUM to alarm within a few batches.
    for round_ in range(14):
        for j in range(4):
            _checkout(shop, f"fault-{round_}-{j}")
        time.sleep(0.07)

    deadline = time.monotonic() + 60.0
    flagged = ""
    while time.monotonic() < deadline:
        text = _get(f"{daemon_metrics}/metrics").decode()
        m = re.search(
            r'app_anomaly_flags_total\{service="payment"\} (\d+\.?\d*)', text
        )
        if m and float(m.group(1)) >= 1:
            flagged = m.group(0)
            break
        time.sleep(0.5)
    assert flagged, "paymentFailure never flagged across the process boundary"


def test_error_logs_cross_to_daemon_store(topology):
    """The third signal (otelcol-config.yml:128-131): checkout's ERROR
    logs during the paymentFailure phase cross the process boundary via
    the shop collector's /v1/logs exporter and land in the daemon's
    bounded log store (counted + stored, with the error-rate lane fed).

    Runs after the fault test (module-scoped topology): paymentFailure
    is still enabled, so failing checkouts keep emitting ERROR logs.
    """
    shop = topology["shop"]
    daemon_metrics = topology["daemon_metrics"]

    deadline = time.monotonic() + 60.0
    seen = 0.0
    stored = 0.0
    i = 0
    while time.monotonic() < deadline:
        _checkout(shop, f"log-leg-{i}")
        i += 1
        text = _get(f"{daemon_metrics}/metrics").decode()
        m = re.search(
            r"^app_anomaly_log_records_processed_total (\d+\.?\d*)", text, re.M
        )
        s = re.search(r"^app_anomaly_log_docs_stored (\d+\.?\d*)", text, re.M)
        if m and float(m.group(1)) >= 1 and s and float(s.group(1)) >= 1:
            seen = float(m.group(1))
            stored = float(s.group(1))
            break
        time.sleep(0.4)
    assert seen >= 1, "no shop log record reached the daemon over /v1/logs"
    assert stored >= 1, "log records counted but none stored"
