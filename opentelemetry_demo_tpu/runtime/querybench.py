"""Query-plane benchmark: read latency/QPS beside live ingest.

The overloadbench/replbench sibling for the read path: run a REAL
DetectorPipeline under steady span load, stand up the actual HTTP
query service (runtime.query) over the dispatch-lock snapshot helper,
and hammer it from concurrent clients while ingest keeps pumping:

- ``query_p99_ms`` / ``query_p50_ms`` — per-request wall time through
  the full stack (HTTP parse → snapshot cache → numpy sketch reads →
  JSON), the number an operator's dashboard refresh actually pays;
- ``query_qps`` — sustained answered queries/s at that latency;
- ``ingest_ratio`` — ingest spans/s WITH the query hammer running vs
  a query-free baseline measured the same way in the same process:
  the "reads must not degrade the write path" guard (bench.py's
  ingest/lag SLOs stay gated independently; this localizes any
  interference to the query plane itself).

``make querybench`` prints ONE json line; ``bench.py`` lifts
``query_p99_ms`` / ``query_qps`` into the flagship artifact (guarded
by ``BENCH_QUERY`` + try/except, the additive-field rule).
"""

from __future__ import annotations

import http.client
import json
import threading
import time

import numpy as np

from ..models import AnomalyDetector, DetectorConfig
from .ingestbench import make_columns
from .pipeline import DetectorPipeline
from .provenance import REASON_LATENCY, ProvenanceEngine
from .query import QueryEngine, QueryService

SERVICES = (
    "frontend", "cart", "checkout", "currency",
    "payment", "shipping", "email", "ad",
)


def _snapshot_fn(detector, pipe):
    """The daemon's snapshot discipline, bench-local: copy under the
    dispatch lock (dispatch donates), meta in the replication shape."""

    def snapshot():
        with pipe._dispatch_lock:
            arrays = {
                k: np.asarray(v)
                for k, v in detector.state._asdict().items()
            }
            clock_t_prev = detector.clock._t_prev
        return arrays, {
            "offsets": {},
            "service_names": pipe.tensorizer.service_names,
            "clock_t_prev": clock_t_prev,
            "config": list(detector.config._replace(sketch_impl=None)),
            "query": pipe.query_meta(),
        }

    return snapshot


def measure_query(
    seconds: float = 2.0,
    batch: int = 256,
    pump_interval_s: float = 0.01,
    query_threads: int = 4,
    query_interval_s: float = 0.02,
    seed: int = 0,
    config: DetectorConfig | None = None,
) -> dict:
    """Ingest-alone baseline, then ingest + concurrent query clients.

    Both phases run the identical pump loop in the same process, so
    the ingest_ratio isolates the query plane's interference instead
    of run-to-run weather. Clients are PACED (``query_interval_s``
    between requests, ~Grafana-refresh cadence ×N panels) rather than
    busy-looping: an unpaced hammer on a 2-core CI box measures GIL
    starvation of the pump thread, not the query plane — and no real
    dashboard polls in a hot loop."""
    config = config or DetectorConfig(
        num_services=8, hll_p=8, cms_width=512
    )
    detector = AnomalyDetector(config)
    prov = ProvenanceEngine(config)
    pipe = DetectorPipeline(detector, batch_size=batch, provenance=prov)
    for name in SERVICES:
        pipe.tensorizer.service_id(name)
    # Seed the explain ring so the /query/explain leg serves bundles of
    # realistic size (synthetic steady load rarely flags): built by the
    # REAL engine, landed the way replication lands them — the measured
    # cost is serialize + ship of true bundle payloads, not of "{}".
    pipe.restore_query_meta({
        "explains": [
            prov.build(
                t_batch=float(i), seq=i, service=i % len(SERVICES),
                label=SERVICES[i % len(SERVICES)],
                signals=[REASON_LATENCY], exemplars=[], state=None,
                hh_candidates=[], trace_id=None,
            )
            for i in range(8)
        ],
    })
    engine = QueryEngine(
        snapshot_fn=_snapshot_fn(detector, pipe), max_staleness_s=0.5
    )
    service = QueryService(engine, host="127.0.0.1", port=0)
    service.start()
    rng = np.random.default_rng(seed)

    def feed(t0: float, run_s: float) -> float:
        """Pump at cadence for run_s; returns spans/s over the phase."""
        spans0 = pipe.stats.spans
        t = t0
        t_end = time.monotonic() + run_s
        t_wall0 = time.monotonic()
        while time.monotonic() < t_end:
            cols = make_columns(rng, batch)
            cols = cols._replace(
                svc=(cols.svc % len(SERVICES)).astype(np.int32)
            )
            pipe.submit_columns(cols)
            pipe.pump(t)
            t += pump_interval_s
            time.sleep(pump_interval_s)
        pipe.drain()
        wall = max(time.monotonic() - t_wall0, 1e-6)
        return (pipe.stats.spans - spans0) / wall, t

    # Warmup (compile) + ingest-alone baseline.
    pipe.submit_columns(make_columns(rng, batch))
    pipe.pump(0.0)
    pipe.drain()
    baseline_rate, t_virtual = feed(pump_interval_s, seconds)

    # Query hammer beside live ingest.
    paths = [
        "/query/topk?service=frontend",
        "/query/cardinality?service=cart",
        "/query/zscore?service=checkout",
        "/query/anomalies?limit=10",
        "/query/explain?limit=5",
        "/query/services",
    ]
    latencies: list[float] = []
    explain_lat: list[float] = []
    errors = [0]
    lat_lock = threading.Lock()
    stop = threading.Event()

    def hammer(widx: int) -> None:
        conn = http.client.HTTPConnection(
            "127.0.0.1", service.port, timeout=5.0
        )
        i = widx
        while not stop.is_set():
            path = paths[i % len(paths)]
            i += 1
            t0 = time.perf_counter()
            try:
                conn.request("GET", path)
                resp = conn.getresponse()
                resp.read()
                ok = resp.status == 200
            except Exception:  # noqa: BLE001 — count, reconnect, go on
                ok = False
                conn.close()
                conn = http.client.HTTPConnection(
                    "127.0.0.1", service.port, timeout=5.0
                )
            dt = time.perf_counter() - t0
            with lat_lock:
                if ok:
                    latencies.append(dt)
                    # The explain leg gets its own percentile (bundles
                    # are the fattest answers on the plane) while still
                    # counting toward the aggregate QPS above.
                    if path.startswith("/query/explain"):
                        explain_lat.append(dt)
                else:
                    errors[0] += 1
            if query_interval_s > dt:
                time.sleep(query_interval_s - dt)
        conn.close()

    workers = [
        threading.Thread(target=hammer, args=(w,), daemon=True)
        for w in range(query_threads)
    ]
    t_q0 = time.monotonic()
    for w in workers:
        w.start()
    try:
        query_rate, _ = feed(t_virtual, seconds)
    finally:
        stop.set()
        for w in workers:
            w.join(timeout=5.0)
        query_wall = max(time.monotonic() - t_q0, 1e-6)
        service.stop()
    lat_ms = np.asarray(latencies) * 1e3
    exp_ms = np.asarray(explain_lat) * 1e3
    return {
        "query_p50_ms": (
            round(float(np.percentile(lat_ms, 50)), 3) if len(lat_ms) else None
        ),
        "query_p99_ms": (
            round(float(np.percentile(lat_ms, 99)), 3) if len(lat_ms) else None
        ),
        "explain_p99_ms": (
            round(float(np.percentile(exp_ms, 99)), 3) if len(exp_ms) else None
        ),
        "explain_queries": int(len(exp_ms)),
        "query_qps": round(len(lat_ms) / query_wall, 1),
        "query_errors": int(errors[0]),
        "queries_total": int(len(lat_ms)),
        "query_threads": int(query_threads),
        "ingest_spans_per_sec": round(query_rate, 1),
        "ingest_spans_per_sec_baseline": round(baseline_rate, 1),
        "ingest_ratio": round(query_rate / max(baseline_rate, 1e-9), 3),
        "spans_fed": int(pipe.stats.spans),
    }


def main() -> None:
    print(json.dumps(measure_query()))


if __name__ == "__main__":
    main()
