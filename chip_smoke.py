#!/usr/bin/env python3
"""Chip smoke: the anomaly-detector sidecar's served path on one TPU.

Runs the compose deployment's geometry (the ``DetectorConfig`` defaults:
S=32, p=12, D=4, W=8192, windows 1/10/60 s; ``ANOMALY_BATCH=2048`` with
the sketch impl auto-selected; 2 decode workers) in two phases:

(a) **Device step** (a child process that holds the chip): the jitted
    ``detector_step`` at B=2048 with the auto-selected impl, then with
    ``sketch_impl="xla"`` on the same seeded span batches. The integer
    HLL/CMS banks must be bit-equal between the impls and equal to the
    host references in ``tests/references.py``.
(b) **The daemon through its normal door** (``python -m
    opentelemetry_demo_tpu``, a child that holds the chip) beside the
    in-repo Kafka broker (``scripts/serve_kafka.py``, pinned to the
    CPU). This process is the load side: seeded OTLP/HTTP protobuf spans
    from ~20 shop services at the Locust-profile rate of 2,000 spans/s
    plus ``orders`` records over Kafka, a latency fault on one service
    after warm-up, then checks of /healthz, the processed-span counter,
    the fault's flag and the query endpoints. It imports the repo's
    encoders with JAX pinned to the CPU and never initialises a backend.

Exactly one process holds the chip at a time. Any failed phase exits
non-zero; off the chip the first phase fails ("no TPU found"). The last
line of stdout is ``{"ok": true, "device": {...}}`` with the device as
the chip-holding process reports it.

Usage: python chip_smoke.py [--seed N]
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
# Children that need the chip get the environment as it came in; the
# load side gets JAX_PLATFORMS=cpu.
CHIP_ENV = dict(
    os.environ, PYTHONUNBUFFERED="1",
    PYTHONPATH=os.pathsep.join(filter(None, (REPO, os.environ.get("PYTHONPATH")))),
)
CPU_ENV = dict(CHIP_ENV, JAX_PLATFORMS="cpu")

B = 2048  # ANOMALY_BATCH, the compose deployment's batch
STEP_BATCHES = 6
RATE = 2_000.0  # spans/s — the Locust-profile rate
POST_EVERY_S = 0.05
ORDERS_PER_S = 2.0
CLEAN_AFTER_WARM_S = 25.0  # >= two 10 s windows rotate after warm-up
WARM_BATCHES = 60  # DetectorConfig.z_warmup_batches
FAULT_SERVICE = "cart"
FAULT_FACTOR = 8.0  # latency multiplier while the fault is on
HLL_SE = 1.04 / math.sqrt(1 << 12)  # p=12
# The shop's services (the reference demo's span sources) with their
# share of spans and base latency in µs.
SERVICES = (
    ("frontend", 0.16, 3000.0), ("frontend-proxy", 0.14, 3500.0),
    ("cart", 0.08, 900.0), ("checkout", 0.05, 6000.0),
    ("currency", 0.07, 150.0), ("payment", 0.03, 1200.0),
    ("shipping", 0.04, 800.0), ("email", 0.02, 2500.0),
    ("ad", 0.05, 1800.0), ("recommendation", 0.05, 2200.0),
    ("product-catalog", 0.09, 400.0), ("quote", 0.03, 600.0),
    ("accounting", 0.02, 700.0), ("fraud-detection", 0.02, 900.0),
    ("image-provider", 0.04, 5000.0), ("load-generator", 0.03, 8000.0),
    ("flagd", 0.03, 200.0), ("product-reviews", 0.02, 1500.0),
    ("valkey-cart", 0.02, 120.0), ("kafka", 0.01, 300.0),
)


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)
    print(f"  ok: {what}", flush=True)


# --- phase (a): the device step, in a child that holds the chip ------


def step_phase(seed: int) -> dict:
    import numpy as np

    sys.path.insert(0, REPO)
    from opentelemetry_demo_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SmokeFailure(
            f"no TPU found: JAX's device 0 is {dev.platform!r} "
            f"({dev.device_kind})"
        )
    from opentelemetry_demo_tpu.models import detector as det
    from opentelemetry_demo_tpu.ops import cms, fused
    from opentelemetry_demo_tpu.runtime import SpanTensorizer
    from tests.references import CMSRef, HLLRef

    config = det.DetectorConfig()
    auto = fused.resolve_impl(
        config.sketch_impl, batch=B, cms_depth=config.cms_depth,
        cms_width=config.cms_width, num_services=config.num_services,
        hll_p=config.hll_p,
    )
    print(f"  device: {dev.platform} / {dev.device_kind}; "
          f"compile cache {cache_dir}", flush=True)
    print(f"  impl auto-selected at B={B}: {auto}; xla's CMS engine: "
          f"{'mxu' if cms._mxu_hist_usable(config.cms_depth * config.cms_width, config.cms_depth * B) else 'sort'}",
          flush=True)
    check(auto == "pallas",
          f"auto-select picks the one-pass Pallas kernel at B={B}")

    rng = np.random.default_rng(seed)
    tz = SpanTensorizer(num_services=config.num_services, batch_size=B)
    batches = []
    for i in range(STEP_BATCHES):
        n = B - 97 * i  # partial batches exercise the valid mask
        trace_pool = rng.integers(0, 2**63, size=n // 3, dtype=np.uint64)
        batches.append(tz.pack_arrays(
            svc=rng.integers(0, len(SERVICES), size=n),
            lat_us=rng.gamma(4.0, 250.0, size=n).astype(np.float32),
            trace_id=trace_pool[rng.integers(0, trace_pool.size, size=n)],
            is_error=(rng.random(n) < 0.02).astype(np.float32),
            attr_key=rng.zipf(1.5, size=n).astype(np.uint64),
        ))
    rotate = jnp.zeros(config.num_windows, bool)
    dt = jnp.float32(0.05)

    out = {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()), "impl_auto": auto,
    }
    banks = {}
    for impl in (auto, "xla"):
        cfg = config._replace(sketch_impl=impl)
        step = jax.jit(
            lambda s, *a, cfg=cfg: det.detector_step(cfg, s, *a),
            donate_argnums=0,
        )
        state = det.detector_init(cfg)
        args = [tuple(jnp.asarray(x) for x in tb[:8]) for tb in batches]
        t0 = time.perf_counter()
        compiled = step.lower(state, *args[0], dt, rotate).compile()
        out[f"compile_s_{impl}"] = time.perf_counter() - t0
        for a in args:
            state, report = compiled(state, *a, dt, rotate)
        banks[impl] = (
            np.asarray(state.hll_bank), np.asarray(state.cms_bank),
            np.asarray(report.svc_count),
        )
        finite = all(
            bool(np.isfinite(np.asarray(x)).all())
            for x in (report.lat_z, report.err_z, report.rate_z, report.cusum)
        )
        check(finite, f"{impl}: report z-scores and CUSUM are finite")
        print(f"  {impl}: compile {out[f'compile_s_{impl}']:.3f} s, "
              f"{STEP_BATCHES} steps", flush=True)

    for a, b_, name in zip(banks[auto], banks["xla"], ("hll", "cms", "svc_count")):
        check(np.array_equal(a, b_), f"{auto} and xla {name} bit-equal")

    # Host reference over the same hashed keys: per-service HLL
    # registers and the CMS table (no rotation ⇒ every window's current
    # bank holds every batch; the previous banks stay empty).
    hll_ref = [HLLRef(config.hll_p) for _ in range(config.num_services)]
    cms_ref = CMSRef(config.cms_depth, config.cms_width)
    for tb in batches:
        n = int(tb.num_valid)
        th = (tb.trace_hi[:n].astype(np.uint64) << np.uint64(32)) | tb.trace_lo[:n]
        ah = (tb.attr_hi[:n].astype(np.uint64) << np.uint64(32)) | tb.attr_lo[:n]
        for s_, t_, a_ in zip(tb.svc[:n].tolist(), th.tolist(), ah.tolist()):
            hll_ref[s_].add_hash(t_)
            cms_ref.add_hash(a_)
    want_hll = np.asarray([r.regs for r in hll_ref], np.int32)
    hll_bank, cms_bank, svc_count = banks[auto]
    for w in range(config.num_windows):
        check(np.array_equal(hll_bank[w, 0], want_hll),
              f"window {w}: HLL bank equals the host reference")
        check(np.array_equal(cms_bank[w, 0], cms_ref.table),
              f"window {w}: CMS bank equals the host reference")
    check(not hll_bank[:, 1].any() and not cms_bank[:, 1].any(),
          "previous-window banks stay empty without rotation")
    last = batches[-1]
    want_cnt = np.bincount(
        last.svc[: int(last.num_valid)], minlength=config.num_services
    )
    check(np.array_equal(svc_count, want_cnt.astype(np.float32)),
          "last report's per-service counts equal the host count")
    return out


# --- phase (b): the daemon through its normal door --------------------


class Child:
    """A child process whose merged output is drained to a log file
    (and kept for the matcher) on a reader thread."""

    def __init__(self, name: str, argv: list[str], env: dict):
        os.makedirs(OUT_DIR, exist_ok=True)
        self.name = name
        self.lines: list[str] = []
        self._log = open(os.path.join(OUT_DIR, f"{name}.log"), "w")
        self.proc = subprocess.Popen(
            argv, cwd=REPO, env=env, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True,
        )
        self._reader = threading.Thread(target=self._drain, daemon=True)
        self._reader.start()

    def _drain(self) -> None:
        for line in self.proc.stdout:
            self.lines.append(line)
            self._log.write(line)
            self._log.flush()

    def wait_line(self, pattern: str, timeout_s: float) -> re.Match:
        deadline = time.monotonic() + timeout_s
        seen = 0
        while time.monotonic() < deadline:
            while seen < len(self.lines):
                m = re.search(pattern, self.lines[seen])
                seen += 1
                if m:
                    return m
            if self.proc.poll() is not None and seen >= len(self.lines):
                raise SmokeFailure(
                    f"{self.name} exited rc={self.proc.returncode}; "
                    f"tail:\n{''.join(self.lines[-30:])}"
                )
            time.sleep(0.05)
        raise SmokeFailure(f"{self.name}: no line matching {pattern!r}")

    def stop(self) -> None:
        if self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=20)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        self._reader.join(timeout=5)
        self._log.close()


def http_get(url: str, timeout: float = 10.0) -> tuple[int, bytes]:
    try:
        with urllib.request.urlopen(url, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def metric(text: str, name: str, **labels) -> float:
    """Sum of a Prometheus sample family's series matching ``labels``."""
    total = 0.0
    for m in re.finditer(
        rf"^{re.escape(name)}(\{{[^}}]*\}})? ([0-9.eE+-]+)$", text, re.M
    ):
        got = dict(re.findall(r'(\w+)="([^"]*)"', m.group(1) or ""))
        if all(got.get(k) == v for k, v in labels.items()):
            total += float(m.group(2))
    return total


class Load:
    """The load side: paced OTLP/HTTP protobuf posts and Kafka orders
    from a seeded generator that keeps the exact distinct trace keys it
    had accepted per service."""

    def __init__(self, otlp_url: str, kafka_addr: str, seed: int):
        import numpy as np

        from opentelemetry_demo_tpu.runtime.kafka_client import KafkaProducer
        from opentelemetry_demo_tpu.runtime.kafka_orders import (
            Order, encode_order,
        )
        from opentelemetry_demo_tpu.runtime.otlp_export import (
            encode_export_request,
        )
        from opentelemetry_demo_tpu.runtime.tensorize import SpanRecord

        self._encode = encode_export_request
        self._record = SpanRecord
        self._order = Order
        self._encode_order = encode_order
        self.producer = KafkaProducer(kafka_addr)
        self.otlp_url = otlp_url
        self.rng = np.random.default_rng(seed)
        names = [s[0] for s in SERVICES]
        share = np.asarray([s[1] for s in SERVICES])
        self.names = names
        self.p = share / share.sum()
        self.base = {s[0]: s[2] for s in SERVICES}
        self.recent: list[bytes] = []
        self.distinct: dict[str, set] = {n: set() for n in names}
        self.distinct["checkout-orders"] = set()
        self.spans_ok = 0
        self.orders_ok = 0
        self.rejected = 0
        self.fault = False
        self.error: Exception | None = None
        self.stop = threading.Event()
        self.late_s = 0.0  # how far the sender fell behind its schedule

    def _spans(self, n: int):
        out, keys = [], []
        svcs = self.rng.choice(len(self.names), size=n, p=self.p)
        for i in svcs.tolist():
            name = self.names[i]
            # ~60% of spans join a recent trace (fan-out), the rest
            # start one: distinct traces per service < spans.
            if self.recent and self.rng.random() < 0.6:
                tid = self.recent[int(self.rng.integers(len(self.recent)))]
            else:
                tid = self.rng.bytes(16)
                self.recent = (self.recent + [tid])[-64:]
            lat = float(self.rng.gamma(4.0, self.base[name] / 4.0))
            if self.fault and name == FAULT_SERVICE:
                lat *= FAULT_FACTOR
            out.append(self._record(
                service=name, duration_us=lat, trace_id=tid,
                is_error=bool(self.rng.random() < 0.01),
                attr=f"SKU-{int(self.rng.zipf(1.5)) % 500}",
            ))
            keys.append((name, tid[:8]))
        return out, keys

    def _post(self, body: bytes) -> int:
        req = urllib.request.Request(
            self.otlp_url, data=body, method="POST",
            headers={"Content-Type": "application/x-protobuf"},
        )
        try:
            with urllib.request.urlopen(req, timeout=10) as resp:
                resp.read()
                return resp.status
        except urllib.error.HTTPError as e:
            return e.code

    def run(self) -> None:
        try:
            self._run()
        except Exception as e:  # noqa: BLE001 — reported by the main thread
            self.error = e
        finally:
            self.producer.close()

    def _run(self) -> None:
        per_post = int(RATE * POST_EVERY_S)
        next_at = time.monotonic()
        next_order = next_at
        n_order = 0
        while not self.stop.is_set():
            now = time.monotonic()
            if now < next_at:
                time.sleep(next_at - now)
                continue
            self.late_s = max(self.late_s, now - next_at)
            next_at += POST_EVERY_S
            recs, keys = self._spans(per_post)
            if self._post(self._encode(recs)) == 200:
                self.spans_ok += len(recs)
                for name, k in keys:
                    self.distinct[name].add(k)
            else:
                self.rejected += len(recs)
            if now >= next_order:
                next_order += 1.0 / ORDERS_PER_S
                # A uuid-shaped id: the daemon keys an order by the
                # first 8 bytes of its id.
                oid = self.rng.bytes(16).hex()
                n_order += 1
                self.producer.send("orders", self._encode_order(self._order(
                    order_id=oid, tracking_id=f"track-{n_order}",
                    shipping_cost_units=float(self.rng.gamma(4.0, 2.5)),
                    item_count=1, product_ids=("SKU-1",), total_quantity=1,
                )))
                self.orders_ok += 1
                self.distinct["checkout-orders"].add(oid.encode()[:8])


def serve_phase(seed: int, step: dict) -> dict:
    out: dict = {}
    broker = daemon = None
    try:
        broker = Child(
            "broker",
            [sys.executable, "scripts/serve_kafka.py", "--host", "127.0.0.1",
             "--port", "0"],
            CPU_ENV,
        )
        kafka = broker.wait_line(r"kafka broker on ([\d.]+:\d+)", 60).group(1)
        t_boot = time.monotonic()
        daemon = Child(
            "daemon",
            [sys.executable, "-m", "opentelemetry_demo_tpu"],
            dict(
                CHIP_ENV, KAFKA_ADDR=kafka, ANOMALY_OTLP_PORT="0",
                ANOMALY_OTLP_GRPC_PORT="0", ANOMALY_METRICS_PORT="0",
                ANOMALY_QUERY_PORT="0", ANOMALY_INGEST_WORKERS="2",
                ANOMALY_BATCH=str(B),
            ),
        )
        m = daemon.wait_line(
            r"otlp-http :(\d+) .*metrics :(\d+) .*query :(\d+) .*"
            r"device (\w+) \((.*)\)",
            300,
        )
        otlp, mport, qport, platform, kind = m.groups()
        print(f"  daemon up in {time.monotonic() - t_boot:.3f} s on "
              f"{platform} ({kind})", flush=True)
        check(platform == step["platform"] and kind == step["kind"],
              "the daemon serves on the device phase (a) ran on")
        metrics_url = f"http://127.0.0.1:{mport}"
        query_url = f"http://127.0.0.1:{qport}"

        # Only now, with both chip users launched, does this process
        # import the repo (JAX pinned to the CPU, no backend touched).
        os.environ["JAX_PLATFORMS"] = "cpu"
        sys.path.insert(0, REPO)
        from opentelemetry_demo_tpu.runtime import native

        loaded = native.available()
        print(f"  native decoder loaded: {loaded}"
              + ("" if loaded else f" ({native.load_error()})"), flush=True)
        out["native_decoder"] = loaded

        # The adaptive-width ladder precompiles in the background.
        deadline = time.monotonic() + 600
        while True:
            text = http_get(f"{metrics_url}/metrics")[1].decode()
            done = re.findall(
                r'^anomaly_width_warmups_total\{([^}]*)\} ', text, re.M
            )
            if len(done) >= 4 or time.monotonic() > deadline:
                break
            time.sleep(0.5)
        ladder_s = time.monotonic() - t_boot
        widths = sorted(
            (int(re.search(r'width="(\d+)"', d).group(1)),
             re.search(r'outcome="(\w+)"', d).group(1))
            for d in done
        )
        print(f"  ladder compiled {widths} {ladder_s:.3f} s after launch",
              flush=True)
        check([w for w, _ in widths] == [B, 2 * B, 4 * B, 8 * B]
              and all(o == "ok" for _, o in widths),
              "every adaptive width compiled")
        out["ladder_compile_s"] = ladder_s

        # The run must fit one 60 s window of the daemon's (monotonic,
        # boundary-aligned) clock: its 60 s bank then holds exactly
        # what was sent, the count the cardinality check compares to.
        need = 50.0
        phase = time.monotonic() % 60.0
        if phase > 60.0 - need:
            time.sleep(60.0 - phase + 0.5)
        window_start = time.monotonic()

        load = Load(f"http://127.0.0.1:{otlp}/v1/traces", kafka, seed)
        sender = threading.Thread(target=load.run, daemon=True)
        sender.start()

        # Warm-up: the fault service's z-scores open after 60 batches.
        deadline = time.monotonic() + 60
        while True:
            st, body = http_get(
                f"{query_url}/query/zscore?service={FAULT_SERVICE}"
            )
            obs = json.loads(body)["data"]["obs_batches"] if st == 200 else 0
            if obs > WARM_BATCHES or time.monotonic() > deadline:
                break
            time.sleep(0.25)
        check(obs > WARM_BATCHES,
              f"{FAULT_SERVICE} warmed ({obs:.0f} batches)")
        time.sleep(CLEAN_AFTER_WARM_S)

        text = http_get(f"{metrics_url}/metrics")[1].decode()
        flags_before = metric(text, "app_anomaly_flags_total",
                              service=FAULT_SERVICE)
        t_fault = time.monotonic()
        load.fault = True
        ttd = None
        while time.monotonic() - t_fault < 30:
            text = http_get(f"{metrics_url}/metrics")[1].decode()
            if metric(text, "app_anomaly_flags_total",
                      service=FAULT_SERVICE) > flags_before:
                ttd = time.monotonic() - t_fault
                break
            time.sleep(0.05)
        load.fault = False
        load.stop.set()
        sender.join(timeout=10)
        t_end = time.monotonic()
        check(load.error is None and not sender.is_alive(),
              f"the load generator ran to its end ({load.error!r})")
        check(ttd is not None,
              f"latency fault ×{FAULT_FACTOR:g} on {FAULT_SERVICE} flagged")
        print(f"  time to detect: {ttd:.3f} s (metrics polled every 0.05 s)",
              flush=True)
        out["ttd_s"] = ttd
        check(int(window_start // 60) == int(t_end // 60),
              "the run fit one 60 s window")

        # Settle: every accepted span dispatched, harvested, and in a
        # fresh query snapshot (staleness budget 2 s).
        sent = load.spans_ok + load.orders_ok
        deadline = time.monotonic() + 30
        while True:
            text = http_get(f"{metrics_url}/metrics")[1].decode()
            processed = metric(text, "app_anomaly_spans_processed_total")
            if processed >= sent or time.monotonic() > deadline:
                break
            time.sleep(0.25)
        time.sleep(2.5)
        print(f"  sent {load.spans_ok} spans over OTLP + {load.orders_ok} "
              f"orders over Kafka; processed {processed:.0f}; rejected "
              f"{load.rejected}; sender ran at most {load.late_s:.3f} s late",
              flush=True)
        out.update(spans_sent=load.spans_ok, orders_sent=load.orders_ok,
                   spans_processed=processed, sender_late_s=load.late_s)
        check(load.rejected == 0, "every OTLP post was accepted")
        check(processed == sent,
              "app_anomaly_spans_processed_total covers what was sent")
        st, body = http_get(f"{metrics_url}/healthz")
        check(st == 200 and json.loads(body)["status"] == "ok",
              "/healthz is ok")

        st, body = http_get(f"{query_url}/query/services")
        listed = json.dumps(json.loads(body)["data"]) if st == 200 else ""
        check(st == 200 and all(n in listed for n in load.names)
              and "checkout-orders" in listed,
              "/query/services lists every service, Kafka leg included")
        st, body = http_get(f"{query_url}/query/topk?service=frontend")
        check(st == 200 and json.loads(body)["data"], "/query/topk answers")
        for svc in (FAULT_SERVICE, "checkout-orders"):
            st, body = http_get(f"{query_url}/query/cardinality?service={svc}")
            check(st == 200, f"/query/cardinality?service={svc} answers")
            data = json.loads(body)["data"]
            w = data["windows_s"].index(60.0)
            est = data["estimate"][w]
            exact = len(load.distinct[svc])
            err = abs(est - exact) / max(exact, 1)
            print(f"  {svc}: 60 s window estimate {est:.1f} vs exact "
                  f"{exact} (rel. error {err:.5f})", flush=True)
            check(err <= 3 * HLL_SE,
                  f"{svc} cardinality within 3 HLL standard errors")
        return out
    finally:
        for child in (daemon, broker):
            if child is not None:
                child.stop()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--phase", choices=("step",), help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args.phase == "step":  # the child of phase (a)
        try:
            res = step_phase(args.seed)
        except SmokeFailure as e:
            print(f"chip_smoke step: FAIL: {e}", flush=True)
            return 1
        print("STEP " + json.dumps(res), flush=True)
        return 0

    try:
        print("phase (a): device step", flush=True)
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--phase", "step",
             "--seed", str(args.seed)],
            cwd=REPO, env=CHIP_ENV, stdout=subprocess.PIPE, text=True,
            timeout=600,
        )
        print(proc.stdout, end="", flush=True)
        m = re.search(r"^STEP (\{.*\})$", proc.stdout, re.M)
        if proc.returncode != 0 or m is None:
            raise SmokeFailure(f"device step phase exited {proc.returncode}")
        step = json.loads(m.group(1))
        print("phase (b): daemon", flush=True)
        served = serve_phase(args.seed, step)
    except (SmokeFailure, subprocess.TimeoutExpired) as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr, flush=True)
        return 1
    summary = {
        "device": {k: step[k] for k in ("platform", "kind", "count")},
        "impl_auto": step["impl_auto"],
        "compile_s": {k: v for k, v in step.items() if k.startswith("compile_s")},
        **served,
    }
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
        json.dump(summary, f, indent=1)
    print("summary: " + json.dumps(summary), flush=True)
    print(json.dumps({"ok": True, "device": summary["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
