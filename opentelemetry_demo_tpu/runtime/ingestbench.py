"""Host-ingest throughput measurement: OTLP bytes → pipeline columns.

The device side does tens of millions of spans/sec (bench.py); this
measures the other half of the ≥200k spans/sec budget (SURVEY.md §7
hard part (a)) — wire decode + attribute hashing + interning — so the
artifact can show the host keeps the chip fed. One methodology, two
callers: ``scripts/bench_ingest.py`` (the standalone CLI, all decode
paths + the worker-count sweep) and ``bench.py`` (the driver artifact).

Three engines, same bytes → ``SpanColumns`` work:

- ``measure_python``  — pure-Python record path (no compiler needed).
- ``measure_native``  — the r5 serial path: one ctypes decode + one
  tensorize per request, on one thread. Kept as the BEFORE number.
- ``measure_pooled``  — the parallel ingest engine
  (``runtime.ingest_pool``): batched ``decode_many``, pooled scratch
  buffers, coalesced tensorize, N workers. ``measure_scaling`` sweeps
  the worker count into the ``host_ingest_scaling`` curve bench.py
  prints.
"""

from __future__ import annotations

import time

import numpy as np

from . import native, wire
from .otlp import MONITORED_ATTR_KEYS, decode_export_request
from .tensorize import SpanColumns, SpanTensorizer


def make_columns(rng, rows: int) -> SpanColumns:
    return SpanColumns(
        svc=rng.integers(0, 20, size=rows).astype(np.int32),
        lat_us=rng.gamma(4.0, 250.0, size=rows).astype(np.float32),
        is_error=(rng.random(rows) < 0.02).astype(np.float32),
        trace_key=rng.integers(0, 2**63, size=rows, dtype=np.uint64),
        attr_crc=rng.zipf(1.5, size=rows).astype(np.uint64),
    )


def make_payloads(n_requests: int = 64, spans_per_request: int = 128,
                  seed: int = 0) -> list[bytes]:
    """Realistic OTLP ExportTraceServiceRequest payloads (shop-shaped
    service names, product-id attrs, ~2% error spans)."""
    rng = np.random.default_rng(seed)
    services = [
        "frontend", "checkout", "cart", "payment", "currency",
        "product-catalog", "shipping", "ad", "recommendation", "quote",
    ]

    def anyval(s):
        return wire.encode_len(1, s.encode())

    def kv(k, v):
        return wire.encode_len(1, k.encode()) + wire.encode_len(2, anyval(v))

    payloads = []
    for _ in range(n_requests):
        svc = services[int(rng.integers(0, len(services)))]
        # Joined once per request — += over a growing bytes would make
        # big-request generation quadratic (60k spans took minutes).
        span_bufs = []
        for _ in range(spans_per_request):
            start = int(rng.integers(10**18, 2 * 10**18))
            span = (
                wire.encode_len(1, bytes(rng.integers(0, 256, 16, dtype=np.uint8)))
                + wire.encode_len(5, b"oteldemo.rpc/Call")
                + wire.encode_fixed64(7, start)
                + wire.encode_fixed64(8, start + int(rng.integers(10**5, 10**9)))
                + wire.encode_len(9, kv("app.product.id", f"P-{int(rng.integers(0, 100))}"))
                + wire.encode_len(9, kv("rpc.system", "grpc"))
            )
            if rng.random() < 0.02:
                span += wire.encode_len(15, wire.encode_int(3, 2))
            span_bufs.append(wire.encode_len(2, span))
        resource = wire.encode_len(1, kv("service.name", svc))
        rs = wire.encode_len(1, resource) + wire.encode_len(2, b"".join(span_bufs))
        payloads.append(wire.encode_len(1, rs))
    return payloads


def measure(fn, payloads: list[bytes], n_spans: int, repeat: int = 5) -> float:
    """Best-of-``repeat`` spans/sec of ``fn`` over all payloads."""
    fn(payloads[0])  # warmup
    best = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter()
        for p in payloads:
            fn(p)
        best = min(best, time.perf_counter() - t0)
    return n_spans / best


def measure_native(n_requests: int = 64, spans_per_request: int = 128,
                   repeat: int = 5,
                   payloads: list[bytes] | None = None) -> float | None:
    """Native C++ columnar decode rate (spans/s), or None when the
    native library is unavailable in this environment. Pass prebuilt
    ``payloads`` (from :func:`make_payloads` with the same dims) to
    share generation across paths."""
    if not native.available():
        return None
    if payloads is None:
        payloads = make_payloads(n_requests, spans_per_request)
    tz = SpanTensorizer(num_services=32)
    return measure(
        lambda p: tz.columns_from_columnar(
            native.decode_otlp(p, MONITORED_ATTR_KEYS)
        ),
        payloads,
        n_requests * spans_per_request,
        repeat=repeat,
    )


def measure_python(n_requests: int = 64, spans_per_request: int = 128,
                   repeat: int = 5,
                   payloads: list[bytes] | None = None) -> float:
    """Pure-Python record-path decode rate (spans/s)."""
    if payloads is None:
        payloads = make_payloads(n_requests, spans_per_request)
    tz = SpanTensorizer(num_services=32)
    return measure(
        lambda p: tz.columns_from_records(decode_export_request(p)),
        payloads,
        n_requests * spans_per_request,
        repeat=repeat,
    )


def measure_pooled(workers: int = 2, n_requests: int = 64,
                   spans_per_request: int = 128, repeat: int = 4,
                   passes: int = 16, coalesce: int = 256,
                   payloads: list[bytes] | None = None) -> float | None:
    """Parallel-ingest-engine rate (spans/s), or None without native.
    Thin wrapper over :func:`measure_pooled_detail` for callers that
    only want the headline number."""
    got = measure_pooled_detail(
        workers=workers, n_requests=n_requests,
        spans_per_request=spans_per_request, repeat=repeat,
        passes=passes, coalesce=coalesce, payloads=payloads,
    )
    return None if got is None else got["spans_per_sec"]


def measure_pooled_detail(workers: int = 2, n_requests: int = 64,
                          spans_per_request: int = 128, repeat: int = 4,
                          passes: int = 16, coalesce: int = 256,
                          payloads: list[bytes] | None = None,
                          ) -> dict | None:
    """Parallel-ingest-engine rate + PHASE BREAKDOWN, or None without
    native.

    End-to-end through the REAL :class:`~.ingest_pool.IngestPool` —
    submit tickets, bounded queue, batched decode into pooled buffers,
    coalesced tensorize — into a null pipeline sink, so the number is
    the engine's, not a stripped-down proxy. ``passes`` replays the
    payload set per timed region so the queue stays deep enough for
    coalescing to engage (the production regime the pool exists for).

    ``phase_share`` attributes flush wall time between the native
    decode, the CRC manifest (verify), the intern/column pass
    (tensorize) and the pipeline merge (submit) — the attribution that
    makes the zero-copy spine's win visible instead of folded into one
    opaque number.
    """
    if not native.available():
        return None
    from .ingest_pool import IngestPool

    if payloads is None:
        payloads = make_payloads(n_requests, spans_per_request)
    n_spans = n_requests * spans_per_request * passes
    tz = SpanTensorizer(num_services=32)
    sink = lambda cols: None  # noqa: E731 — decode+tensorize is the cost
    pool = IngestPool(
        sink, tz, workers=workers, coalesce_max=coalesce,
        max_pending=n_requests * passes + 8,
    )
    try:
        for p in payloads:  # warmup: compile nothing, size the scratch
            pool.submit(p)
        pool.drain()
        best = float("inf")
        for _ in range(repeat):
            t0 = time.perf_counter()
            for _ in range(passes):
                for p in payloads:
                    pool.submit(p)
            pool.drain()
            best = min(best, time.perf_counter() - t0)
        stats = pool.stats()
    finally:
        pool.close()
    phase = stats["phase_s"]
    from .ingest_pool import TOP_PHASES

    # Share over the TOP-LEVEL phases only: scan/extract are
    # sub-phases INSIDE the decode envelope (the native two-pass
    # split) — summing them into the denominator would double-count.
    total = sum(phase.get(k, 0.0) for k in TOP_PHASES) or 1.0
    decode_s = phase.get("decode", 0.0) or 1.0
    return {
        "spans_per_sec": n_spans / best,
        "phase_share": {
            k: round(phase.get(k, 0.0) / total, 4) for k in TOP_PHASES
        },
        # How the decode envelope itself splits between the boundary
        # scan and the column extraction (fractions of decode time;
        # the remainder is the ctypes/scratch glue around the call).
        "decode_split": {
            "scan": round(phase.get("scan", 0.0) / decode_s, 4),
            "extract": round(phase.get("extract", 0.0) / decode_s, 4),
        },
        "tickets_parked": stats["tickets_parked"],
        "tickets_recycled": stats["tickets_recycled"],
    }


def measure_raw(n_requests: int = 64, spans_per_request: int = 128,
                repeat: int = 5,
                payloads: list[bytes] | None = None) -> dict | None:
    """Raw two-pass scanner microbench (`make decodebench`): pass-1
    scan vs pass-2 extract vs whole-call throughput PER THREAD, with
    no pool, no tensorize, no CRC manifest — the number a future
    decode regression is attributable against without running the full
    engine. None when native is unavailable.
    """
    if not native.available():
        return None
    if payloads is None:
        payloads = make_payloads(n_requests, spans_per_request)
    n_spans = n_requests * spans_per_request
    # The per-pass times come from INSIDE the one batched call
    # (ingest.cc stamps scan_s/extract_s around its own passes), so
    # neither number carries ctypes call overhead or buffer churn —
    # it is the native pass itself, per thread.
    total = sum(map(len, payloads))
    scratch = native.alloc_scratch(
        *native.scratch_dims(total, len(payloads))
    )
    phases: dict[str, float] = {}
    native.decode_otlp_many(
        payloads, MONITORED_ATTR_KEYS, scratch, phases=phases
    )  # warmup
    decode_t = float("inf")
    scan_t = float("inf")
    extract_t = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter()
        native.decode_otlp_many(
            payloads, MONITORED_ATTR_KEYS, scratch, phases=phases
        )
        decode_t = min(decode_t, time.perf_counter() - t0)
        scan_t = min(scan_t, phases.get("scan") or decode_t)
        extract_t = min(extract_t, phases.get("extract") or decode_t)
    return {
        "scan_spans_per_sec": n_spans / scan_t,
        "extract_spans_per_sec": n_spans / extract_t,
        "decode_spans_per_sec": n_spans / decode_t,
        "scan_bytes_per_sec": total / scan_t,
        "payload_bytes": total,
    }


def measure_fat_payload_scaling(
    spans: int = 65536, threads_list=(1, 2), repeat: int = 3
) -> dict | None:
    """ONE oversized OTLP export decoded with N native extraction
    threads (the pass-2 sharding leg `make ingestbench` gates): a
    single fat payload must not serialize on one core. Returns
    {"1": spans/s, "2": spans/s, ..., "scaling": rate_N/rate_1} or
    None when native is unavailable.
    """
    if not native.available():
        return None
    payload = make_payloads(1, spans, seed=3)[0]
    scratch = native.alloc_scratch(
        *native.scratch_dims(len(payload), 1)
    )
    out: dict = {}
    for t in threads_list:
        best = float("inf")
        native.decode_otlp_many(
            [payload], MONITORED_ATTR_KEYS, scratch, threads=t,
            shard_min_bytes=0,
        )
        for _ in range(repeat):
            t0 = time.perf_counter()
            native.decode_otlp_many(
                [payload], MONITORED_ATTR_KEYS, scratch, threads=t,
                shard_min_bytes=0,
            )
            best = min(best, time.perf_counter() - t0)
        out[str(t)] = spans / best
    rates = [out[str(t)] for t in threads_list]
    out["scaling"] = round(rates[-1] / rates[0], 3) if rates[0] else None
    return out


def measure_scaling(workers_list=(1, 2, 3, 4), n_requests: int = 64,
                    spans_per_request: int = 128, repeat: int = 3,
                    payloads: list[bytes] | None = None,
                    detail: dict | None = None) -> dict[str, float]:
    """Worker-count → spans/s curve (the bench artifact's
    ``host_ingest_scaling``); {} when native is unavailable.

    Pass ``detail`` (a dict) to ALSO receive each worker count's phase
    breakdown (``detail[str(w)] = {"phase_share": ..., ...}``) — the
    tensorize+submit share the scaling sweep alone never showed.
    """
    if payloads is None:
        payloads = make_payloads(n_requests, spans_per_request)
    out: dict[str, float] = {}
    for w in workers_list:
        got = measure_pooled_detail(
            workers=w, n_requests=n_requests,
            spans_per_request=spans_per_request, repeat=repeat,
            payloads=payloads,
        )
        if got is None:
            return {}
        out[str(w)] = round(got["spans_per_sec"], 1)
        if detail is not None:
            detail[str(w)] = {
                k: v for k, v in got.items() if k != "spans_per_sec"
            }
    return out
