"""Front-door benchmark + million-key cardinality soak.

Two measurements this repo never had, one module, one JSON line:

- ``measure_frontdoor_vs_pool`` — the tentpole's perf gate: OTLP/HTTP
  spans/s through the NATIVE front door (socket → native buffer →
  ticket → decode pool, zero Python per payload) vs the in-process
  pool baseline (``ingestbench.measure_pooled``) at MATCHED workers
  and payload geometry. The front door pays real sockets and HTTP
  framing that the in-process number never does, so meeting the
  baseline means the native acceptor's framing is genuinely free
  relative to decode — the claim the Python receiver could not make
  (pooled ingest flat at ~6.1M spans/s on a one-core CPU runner,
  because the front end, not decode, was the wall).

- ``measure_million_key_soak`` — the repo's first scale-of-keys run:
  a synthetic shop-fleet generator drives ≥1M distinct
  (tenant × service) keys through ingest → sketch → query. Since the
  keyspace plane (runtime/keyspace.py) the intern table is BOUNDED:
  the first ``capacity`` distinct keys win dense slots, every later
  key folds into the overflow bucket UNMEMORIZED — so the soak's
  memory claim flipped from "report the leak" (~935 MB per million
  keys, measured against the old append-only table) to "prove the
  bound" (``soak_rss_ok``: RSS per million keys must stay under
  ``SOAK_RSS_CEILING_MB_PER_MILLION``). Read-back identity, overflow
  accounting, drift refusal at scale and zero frame corruption ride
  along as before.

- ``measure_churn_soak`` — the key-lifecycle plane's survival gate: a
  keyspace-ENABLED pipeline streams ≥3× its key budget of distinct
  keys with churn (a stable live cohort re-shipped every wave + fresh
  one-shot churn keys), while the ``KeyspaceManager`` watchdog clocks
  the degradation ladder and the evictor folds idle keys into a real
  on-disk history tier. Proves: steady-state RSS slope ≈ 0, live-key
  ids bit-stable across every sweep (no mis-attribution), evicted
  keys still answerable via ``/query/*`` with ``source:"evicted"``,
  generation-drifted fleet merges refused, zero frame corruption.

Callers: ``make frontdoorbench`` (standalone, full-size soak) and
``bench.py``'s BENCH_FRONTDOOR leg (additive artifact fields).
"""

from __future__ import annotations

import socket
import threading
import time

from . import native, wire
from .ingest_pool import IngestPool, IngestPoolSaturated
from .ingestbench import make_payloads, measure_pooled
from .tensorize import SpanTensorizer

ONE_MILLION = 1_000_000

# The bounded-interner memory gate: RSS growth per million distinct
# keys streamed must stay under this. The OLD append-only table leaked
# ~935 MB/M (BENCH_r19's measured baseline — every name memorized
# forever); the bounded table admits ``capacity`` names and refuses
# the rest unmemorized, so steady-state growth is wave buffers + JAX
# scratch, far below the old leak. The ceiling is deliberately set at
# the old measured baseline: crossing it means the bomb leaks again.
SOAK_RSS_CEILING_MB_PER_MILLION = 900.0


# ---------------------------------------------------------------------------
# synthetic shop fleet: many DISTINCT services per request
# ---------------------------------------------------------------------------

def make_named_payload(names: list[str]) -> bytes:
    """One OTLP trace payload with one single-span resource_spans
    block per name in ``names`` — the span body is one shared
    template; what varies per key is the resource's service.name,
    which is the axis the interner, the sketches and the fleet table
    all key on."""

    def anyval(s: bytes) -> bytes:
        return wire.encode_len(1, s)

    def kv(k: bytes, v: bytes) -> bytes:
        return wire.encode_len(1, k) + wire.encode_len(2, anyval(v))

    start = 1_700_000_000_000_000_000
    span = (
        wire.encode_len(1, bytes(range(16)))
        + wire.encode_len(5, b"oteldemo.rpc/Call")
        + wire.encode_fixed64(7, start)
        + wire.encode_fixed64(8, start + 5_000_000)
        + wire.encode_len(9, kv(b"app.product.id", b"P-7"))
        + wire.encode_len(9, kv(b"rpc.system", b"grpc"))
    )
    # ResourceSpans.field2 = ScopeSpans, ScopeSpans.field2 = Span —
    # the same double wrap ingestbench.make_payloads emits.
    scope_spans = wire.encode_len(2, wire.encode_len(2, span))
    rs_bufs = []
    for name in names:
        resource = wire.encode_len(1, kv(b"service.name", name.encode()))
        rs_bufs.append(
            wire.encode_len(1, wire.encode_len(1, resource) + scope_spans)
        )
    return b"".join(rs_bufs)


def make_fleet_payloads(
    n_requests: int,
    services_per_request: int = 4096,
    tenants: int = 16,
    start_index: int = 0,
) -> list[bytes]:
    """OTLP trace payloads whose every span belongs to a DISTINCT
    (tenant × service) key — one resource_spans block per service,
    one span each.

    ``ingestbench.make_payloads`` models today's demo (~10 services,
    fat resource blocks); this models the paper's north star (millions
    of users → millions of live keys).
    """
    payloads = []
    key = start_index
    for _ in range(n_requests):
        names = []
        for _ in range(services_per_request):
            names.append(f"t{key % tenants:02d}.svc-{key:07d}")
            key += 1
        payloads.append(make_named_payload(names))
    return payloads


# ---------------------------------------------------------------------------
# HTTP client for the front door (bench-side: Python is fine HERE —
# the claim under test is the SERVER's per-payload loop, not the load
# generator's)
# ---------------------------------------------------------------------------

def _post_loop(
    port: int,
    payloads: list[bytes],
    stop: threading.Event,
    counts: dict,
    lock: threading.Lock,
    depth: int = 4,
    path: bytes = b"/v1/traces",
) -> None:
    """Keep-alive client: send ``depth`` pipelined POSTs, read ``depth``
    responses, repeat until ``stop``. Pipelining keeps the connection's
    ticket slot busy without one thread per in-flight request."""
    reqs = [
        b"POST %s HTTP/1.1\r\nHost: bench\r\nContent-Length: %d\r\n\r\n"
        % (path, len(p)) + p
        for p in payloads
    ]
    s = socket.create_connection(("127.0.0.1", port))
    s.settimeout(30.0)
    try:
        i = 0
        buf = b""
        while not stop.is_set():
            burst = [reqs[(i + k) % len(reqs)] for k in range(depth)]
            i += depth
            s.sendall(b"".join(burst))
            need = depth
            ok = 0
            while need > 0:
                # Responses are header-only (Content-Length: 0), so a
                # complete response == one blank-line terminator.
                chunk = s.recv(65536)
                if not chunk:
                    raise ConnectionError("front door closed mid-burst")
                buf += chunk
                while b"\r\n\r\n" in buf and need > 0:
                    head, buf = buf.split(b"\r\n\r\n", 1)
                    if head.split(b" ", 2)[1] == b"200":
                        ok += 1
                    need -= 1
            with lock:
                counts["ok"] = counts.get("ok", 0) + ok
                counts["sent"] = counts.get("sent", 0) + depth
    except Exception:  # noqa: BLE001 — a bench client dying ends its lane
        pass
    finally:
        s.close()


def _run_frontdoor_clients(
    port: int,
    payloads: list[bytes],
    seconds: float,
    clients: int,
    depth: int,
) -> dict:
    stop = threading.Event()
    counts: dict = {}
    lock = threading.Lock()
    threads = [
        threading.Thread(
            target=_post_loop,
            args=(port, payloads, stop, counts, lock, depth),
            daemon=True,
        )
        for _ in range(clients)
    ]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(seconds)
    stop.set()
    for t in threads:
        t.join(timeout=30.0)
    counts["elapsed"] = time.perf_counter() - t0
    return counts


def measure_frontdoor_vs_pool(
    workers: int = 2,
    n_requests: int = 12,
    spans_per_request: int = 4096,
    seconds: float = 4.0,
    clients: int = 16,
    depth: int = 2,
    repeat: int = 2,
    payloads: list[bytes] | None = None,
) -> dict | None:
    """Front-door spans/s vs the in-process pool at matched geometry.

    Same payload set, same worker count, same null sink, same
    tensorizer width — the ONLY difference is the door: in-process
    ``pool.submit(bytes)`` vs real sockets through native framing
    into the same pool. Fat payloads (default 4096 spans/request) are
    deliberate: the gate is about the steady-state span path, and a
    49-byte request would measure connection scheduling, not ingest.
    Returns None when the native decoder or front door can't build.
    """
    if not native.available() or not native.frontdoor_available():
        return None
    from .frontdoor import FrontDoorServer

    if payloads is None:
        payloads = make_payloads(n_requests, spans_per_request)
    pool_rate = measure_pooled(
        workers=workers, repeat=repeat, passes=16, coalesce=64,
        payloads=payloads,
        n_requests=n_requests, spans_per_request=spans_per_request,
    )
    if pool_rate is None:
        return None

    tz = SpanTensorizer(num_services=32)
    sink = lambda cols: None  # noqa: E731 — matched with measure_pooled
    pool = IngestPool(
        sink, tz, workers=workers, coalesce_max=64,
        max_pending=max(clients * depth * 4, 256),
    )
    fd = FrontDoorServer(
        pool,
        port=0,
        max_body_bytes=64 << 20,
        batch_max=64,
        max_conns=clients + 4,
    )
    try:
        # Warmup off the clock: size scratch, fault in the whole path.
        warm = _run_frontdoor_clients(
            fd.port, payloads, min(seconds, 1.0), clients, depth
        )
        timed = _run_frontdoor_clients(
            fd.port, payloads, seconds, clients, depth
        )
    finally:
        fd.stop()
        pool.close()
    fd_rate = (
        timed.get("ok", 0) * spans_per_request / timed["elapsed"]
        if timed.get("ok") else 0.0
    )
    return {
        "workers": workers,
        "spans_per_request": spans_per_request,
        "clients": clients,
        "pipeline_depth": depth,
        "pool_spans_per_sec": round(pool_rate, 1),
        "frontdoor_spans_per_sec": round(fd_rate, 1),
        "frontdoor_vs_pool": round(fd_rate / pool_rate, 4) if pool_rate else None,
        "requests_ok": timed.get("ok", 0),
        "requests_sent": timed.get("sent", 0),
        "warmup_ok": warm.get("ok", 0),
    }


# ---------------------------------------------------------------------------
# million-key soak
# ---------------------------------------------------------------------------

def _rss_kb() -> int | None:
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1])
    except OSError:
        pass
    try:
        import resource

        return int(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss)
    except Exception:  # noqa: BLE001 — RSS is best-effort off-linux
        return None


def measure_million_key_soak(
    target_keys: int = 1_048_576,
    services_per_request: int = 4096,
    tenants: int = 16,
    workers: int = 2,
    num_services: int = 4096,
    batch: int = 4096,
    via_frontdoor: bool = True,
    clients: int = 2,
) -> dict | None:
    """Drive ``target_keys`` DISTINCT (tenant × service) keys through
    ingest → sketch → query and report what scale actually costs.

    Payloads are generated in waves (a resident list of a million-key
    corpus would bill its own footprint to the thing under test);
    every wave goes through the REAL path — front door sockets when
    the native library is up, ``pool.submit`` otherwise — into a real
    ``DetectorPipeline`` + device sketch step, then the query-side
    checks run against the drained state:

    - the intern table is BOUNDED (keyspace plane): exactly
      ``min(target_keys, capacity)`` keys must hold dense slots after
      the storm (``intern_exact``) — WHICH keys win the slots is
      admission-order across concurrent lanes, so the check counts
      live rows, it does not enumerate names;
    - every published (name → id) pair must read back bit-stable
      through a batched re-intern (``readback_ok``);
    - keys past capacity fold into the overflow bucket UNMEMORIZED by
      contract — ``overflow_keys`` reports the refused-assign count,
      because a soak that silently dropped 99% of its keys would be a
      lie;
    - ``merge_shard_arrays`` must still REFUSE a drifted geometry at
      this table size (``drift_refused``);
    - ``frames_corrupt`` must be 0 across every pooled flush.

    RSS is sampled before generation and after the final drain;
    ``rss_per_million_keys_mb`` is the headline and ``soak_rss_ok``
    gates it under ``SOAK_RSS_CEILING_MB_PER_MILLION`` — the bounded
    table's whole point is that a million-key bomb no longer buys a
    gigabyte.
    """
    if not native.available():
        return None
    import numpy as np

    from ..models.detector import AnomalyDetector, DetectorConfig
    from .frontdoor import FrontDoorServer
    from .pipeline import DetectorPipeline

    n_requests = -(-target_keys // services_per_request)
    total_keys = n_requests * services_per_request
    rss_before = _rss_kb()

    config = DetectorConfig(
        num_services=num_services, hll_p=8, cms_width=1024
    )
    det = AnomalyDetector(config)
    reports = [0]
    pipe = DetectorPipeline(
        det,
        on_report=lambda t, r, flagged: reports.__setitem__(
            0, reports[0] + 1
        ),
        batch_size=batch,
    )
    pool = IngestPool(
        pipe.submit_columns, pipe.tensorizer, workers=workers,
        coalesce_max=64, max_pending=512,
    )
    use_fd = via_frontdoor and native.frontdoor_available()
    fd = (
        FrontDoorServer(pool, port=0, max_body_bytes=64 << 20,
                        max_conns=clients + 2)
        if use_fd else None
    )

    pump_stop = threading.Event()

    def pump_loop() -> None:
        while not pump_stop.is_set():
            pipe.pump()
            time.sleep(0.001)

    pump = threading.Thread(target=pump_loop, name="soak-pump", daemon=True)
    pump.start()

    def ship(wave: list[bytes]) -> None:
        if fd is not None:
            counts: dict = {}
            lock = threading.Lock()
            # One pass over the wave per client lane, no repeat loop:
            # _post_loop cycles forever, so ship waves directly here.
            per = -(-len(wave) // clients)
            lanes = [wave[i * per:(i + 1) * per] for i in range(clients)]

            def lane(payloads: list[bytes]) -> None:
                s = socket.create_connection(("127.0.0.1", fd.port))
                s.settimeout(60.0)
                try:
                    for p in payloads:
                        s.sendall(
                            b"POST /v1/traces HTTP/1.1\r\nHost: soak\r\n"
                            b"Content-Length: %d\r\n\r\n" % len(p) + p
                        )
                        buf = b""
                        while b"\r\n\r\n" not in buf:
                            chunk = s.recv(65536)
                            if not chunk:
                                raise ConnectionError("closed")
                            buf += chunk
                        with lock:
                            if buf.split(b" ", 2)[1] == b"200":
                                counts["ok"] = counts.get("ok", 0) + 1
                finally:
                    s.close()

            threads = [
                threading.Thread(target=lane, args=(ln,), daemon=True)
                for ln in lanes if ln
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300.0)
        else:
            for p in wave:
                while True:
                    try:
                        pool.submit(p)
                        break
                    except IngestPoolSaturated:
                        pipe.pump()
                        time.sleep(0.001)

    t0 = time.perf_counter()
    wave_requests = max(1, (32 << 20) // (services_per_request * 120))
    shipped = 0
    try:
        while shipped < n_requests:
            n = min(wave_requests, n_requests - shipped)
            wave = make_fleet_payloads(
                n, services_per_request, tenants,
                start_index=shipped * services_per_request,
            )
            ship(wave)
            shipped += n
            pipe.pump()
        pool.drain()
        pipe.pump()
        pipe.drain()
    finally:
        if fd is not None:
            fd.stop()
        pump_stop.set()
        pump.join(timeout=10.0)
        pool_stats = pool.stats()
        pool.close()
    elapsed = time.perf_counter() - t0
    rss_after = _rss_kb()

    tz = pipe.tensorizer
    capacity = tz.capacity
    expected_live = min(total_keys, capacity)
    distinct = tz.live_keys
    # Refused-assign count BEFORE the read-back below re-consults the
    # table (a re-intern of live names assigns nothing, but reading
    # the counter first keeps the number honest either way).
    overflow_keys = int(tz.overflow_assigns_total)
    # Read-back identity: every (name → id) pair the table PUBLISHED
    # must survive a batched re-intern bit-stable. The sample comes
    # from the actual snapshot, not the generated sequence — which
    # keys won the dense slots is admission-order across concurrent
    # client lanes, and the bounded table refused the rest by design.
    snap = tz._svc_snapshot  # noqa: SLF001 — the lock-free read surface
    live_names = list(snap)
    sample = live_names[:: max(len(live_names) // 1024, 1)] or live_names
    readback_ok = bool(sample) and (
        tz.intern_many(sample) == [snap[n] for n in sample]
    )

    # Fleet drift refusal at scale: a shard whose sketch geometry
    # drifted by one row must still be REFUSED when the shared table
    # is a million keys deep.
    from .fleet import ShardMergeError, merge_shard_arrays

    rows = max(num_services, 1 << 14)
    a = {"cms_bank": np.ones((rows, 64), np.int32)}
    b = {"cms_bank": np.ones((rows + 1, 64), np.int32)}
    try:
        merge_shard_arrays(a, b)
        drift_refused = False
    except ShardMergeError:
        drift_refused = True

    keys_m = total_keys / ONE_MILLION
    rss_delta_mb = (
        (rss_after - rss_before) / 1024.0
        if rss_after is not None and rss_before is not None else None
    )
    rss_per_million = (
        round(rss_delta_mb / keys_m, 1)
        if rss_delta_mb is not None else None
    )
    return {
        "target_keys": target_keys,
        "distinct_keys": total_keys,
        "distinct_interned": distinct,
        "intern_capacity": capacity,
        "intern_exact": bool(distinct == expected_live),
        "readback_ok": bool(readback_ok),
        "overflow_keys": overflow_keys,
        "sketch_num_services": num_services,
        "tenants": tenants,
        "reports": reports[0],
        "frames_corrupt": int(pool_stats.get("frames_corrupt", 0)),
        "decode_errors": int(pool_stats.get("decode_errors", 0)),
        "drift_refused": bool(drift_refused),
        "via_frontdoor": bool(use_fd),
        "elapsed_s": round(elapsed, 2),
        "keys_per_sec": round(total_keys / elapsed, 1),
        "rss_before_kb": rss_before,
        "rss_after_kb": rss_after,
        "rss_per_million_keys_mb": rss_per_million,
        # The bounded-memory gate: None where RSS is unmeasurable (no
        # /proc and no rusage) or the run was trimmed below half a
        # million keys — fixed overhead (JAX compile caches, device
        # buffers) divided by a small key count swamps the per-million
        # normalization, so a short run can't measure the claim. Same
        # null-when-ineligible convention as bench.py's decode_wall_ok.
        "soak_rss_ok": (
            bool(rss_per_million <= SOAK_RSS_CEILING_MB_PER_MILLION)
            if rss_per_million is not None
            and total_keys >= ONE_MILLION // 2 else None
        ),
        "soak_ok": bool(
            distinct == expected_live
            and readback_ok
            and drift_refused
            and pool_stats.get("frames_corrupt", 0) == 0
        ),
    }


# ---------------------------------------------------------------------------
# churn soak: the key-lifecycle plane's survival gate
# ---------------------------------------------------------------------------

def measure_churn_soak(
    num_services: int = 512,
    live_cohort: int = 32,
    churn_multiple: int = 3,
    waves: int = 8,
    tenants: int = 16,
    workers: int = 2,
    via_frontdoor: bool = True,
    idle_s: float = 0.25,
    hold_s: float = 0.02,
    rss_slope_limit_mb: float = 64.0,
) -> dict | None:
    """Stream ``churn_multiple`` × the key budget of DISTINCT keys
    through a keyspace-ENABLED pipeline and prove the lifecycle plane
    survives the bomb without losing the legitimate tenants.

    Every wave ships a fresh batch of one-shot churn keys plus the
    SAME ``live_cohort`` of legitimate services (re-shipped right
    before each eviction tick, so recency — not a whitelist — is what
    keeps them alive). The ``KeyspaceManager`` is ticked manually
    between waves: pressure saturates at the high watermark, the
    ladder engages after ``hold_s``, idle churn keys fold into a REAL
    on-disk history tier and their ids recycle under a generation
    bump. The gates:

    - ``live_ids_stable``: the live cohort's intern ids are
      bit-identical after every sweep — no eviction ever
      mis-attributed a legitimate key's rows;
    - ``evicted_query_ok``: an evicted churn key still answers on the
      query plane from history, labeled ``source:"evicted"``;
    - ``gen_refused``: a fleet merge across the generation bump
      raises ``ShardMergeError`` (the drift-refusal contract extended
      to recycled ids);
    - ``frames_corrupt == 0`` across every pooled flush;
    - ``rss_slope_ok``: RSS growth from mid-soak to end stays under
      ``rss_slope_limit_mb`` (steady-state slope ≈ 0 — the table is
      bounded, so sustained churn buys sweeps, not memory).

    Returns None when the native decoder can't build (same
    eligibility rule as the million-key soak).
    """
    if not native.available():
        return None
    import tempfile

    import numpy as np

    from ..models.detector import AnomalyDetector, DetectorConfig
    from .fleet import ShardMergeError, merge_shard_arrays
    from .frontdoor import FrontDoorServer
    from .history import HistoryReader, HistoryStore, HistoryWriter
    from .keyspace import KeyspaceManager
    from .pipeline import DetectorPipeline
    from .query import QueryEngine

    capacity = num_services - 1
    churn_per_wave = max(1, -(-churn_multiple * capacity // waves))
    live_names = [
        f"t{i % tenants:02d}.live-{i:03d}" for i in range(live_cohort)
    ]
    rungs = (0.5, 60.0)

    config = DetectorConfig(
        num_services=num_services, hll_p=8, cms_width=1024
    )
    det = AnomalyDetector(config)
    reports = [0]
    pipe = DetectorPipeline(
        det,
        on_report=lambda t, r, flagged: reports.__setitem__(
            0, reports[0] + 1
        ),
        batch_size=num_services,
        keyspace_enable=True,
        keyspace_high_watermark=0.85,
        keyspace_low_watermark=0.70,
        keyspace_hold_s=hold_s,
        # The churn soak exercises the EVICT rung; a huge refill rate
        # keeps a transient THROTTLE excursion from parking churn keys
        # (the throttle rung has its own unit coverage).
        keyspace_newkey_rate=1e9,
        keyspace_retry_after_s=0.5,
    )
    tz = pipe.tensorizer

    def snap() -> tuple[dict, dict]:
        with pipe._dispatch_lock:  # noqa: SLF001 — the snapshot contract
            arrays = {
                k: np.asarray(v)
                for k, v in pipe.detector.state._asdict().items()
            }
        meta = {
            "service_names": tz.service_names,
            "config": list(config._replace(sketch_impl=None)),
            "generation": tz.generation,
            "query": {},
        }
        return arrays, meta

    pool = IngestPool(
        pipe.submit_columns, pipe.tensorizer, workers=workers,
        coalesce_max=64, max_pending=256,
    )
    use_fd = via_frontdoor and native.frontdoor_available()
    fd = (
        FrontDoorServer(pool, port=0, max_body_bytes=8 << 20, max_conns=4)
        if use_fd else None
    )
    conn = (
        socket.create_connection(("127.0.0.1", fd.port))
        if fd is not None else None
    )
    if conn is not None:
        conn.settimeout(30.0)

    shed = [0]

    def post(payload: bytes) -> None:
        if conn is not None:
            conn.sendall(
                b"POST /v1/traces HTTP/1.1\r\nHost: churn\r\n"
                b"Content-Length: %d\r\n\r\n" % len(payload) + payload
            )
            buf = b""
            while b"\r\n\r\n" not in buf:
                chunk = conn.recv(65536)
                if not chunk:
                    raise ConnectionError("front door closed mid-soak")
                buf += chunk
            if buf.split(b" ", 2)[1] != b"200":
                shed[0] += 1
        else:
            while True:
                try:
                    pool.submit(payload)
                    return
                except IngestPoolSaturated:
                    pipe.pump()
                    time.sleep(0.001)

    tmpdir = tempfile.TemporaryDirectory(prefix="churnsoak-")
    store = HistoryStore(tmpdir.name)
    # Writer thread NOT started: the regular window ladder is not
    # under test; the evictor calls record_eviction directly.
    writer = HistoryWriter(store, snap, rungs=rungs)
    mgr = KeyspaceManager(
        pipe, idle_s=idle_s, evict_batch=num_services,
        history_writer=writer,
    )

    live_ids: list[int] = []
    live_stable = True
    max_level = 0
    rss_mid = rss_end = None
    t0 = time.perf_counter()
    try:
        for w in range(waves):
            churn = [
                f"t{j % tenants:02d}.churn-{w:03d}-{j:05d}"
                for j in range(churn_per_wave)
            ]
            post(make_named_payload(churn))
            pool.drain()
            pipe.pump()
            max_level = max(max_level, mgr.tick()["level"])
            # Let this wave's churn go idle; the live cohort is
            # re-shipped AFTER the sleep so recency protects it at
            # the eviction tick below.
            time.sleep(idle_s + 0.05)
            post(make_named_payload(live_names))
            pool.drain()
            pipe.pump()
            max_level = max(max_level, mgr.tick()["level"])
            if not live_ids:
                snapshot = tz._svc_snapshot  # noqa: SLF001
                live_ids = [snapshot.get(n) for n in live_names]
            else:
                snapshot = tz._svc_snapshot  # noqa: SLF001
                live_stable = live_stable and all(
                    snapshot.get(n) == sid
                    for n, sid in zip(live_names, live_ids)
                )
            # De-escalation ticks: eviction dropped fill below the low
            # watermark; walk the ladder back down so a long soak
            # never staircases to the shed rung.
            for _ in range(3):
                time.sleep(hold_s + 0.01)
                mgr.tick()
            if w == waves // 2:
                rss_mid = _rss_kb()
        pool.drain()
        pipe.pump()
        pipe.drain()
        rss_end = _rss_kb()
    finally:
        if conn is not None:
            conn.close()
        if fd is not None:
            fd.stop()
        pool_stats = pool.stats()
        pool.close()
    elapsed = time.perf_counter() - t0

    live_stable = live_stable and bool(live_ids) and all(
        sid is not None for sid in live_ids
    )
    arrays, _meta = snap()
    live_rows_ok = bool(live_ids) and all(
        sid is not None and bool(np.any(arrays["hll_bank"][:, :, sid, :]))
        for sid in live_ids
    )

    # An evicted churn key must still answer from history, labeled.
    evicted_name = next(
        (
            n for w in range(waves) for n in (
                f"t{j % tenants:02d}.churn-{w:03d}-{j:05d}"
                for j in range(churn_per_wave)
            )
            if n not in tz._svc_snapshot  # noqa: SLF001
        ),
        None,
    ) if mgr.evictions else None
    evicted_query_ok = False
    if evicted_name is not None:
        engine = QueryEngine(
            snap, history=HistoryReader(store, rungs=rungs)
        )
        try:
            got = engine.cardinality(evicted_name)
            evicted_query_ok = got["meta"].get("source") == "evicted"
        except Exception:  # noqa: BLE001 — a failed read is a failed gate
            evicted_query_ok = False

    # Fleet pair across the generation bump: REFUSED.
    a = {"cms_bank": np.ones((64, 16), np.int32)}
    b = {"cms_bank": np.ones((64, 16), np.int32)}
    try:
        merge_shard_arrays(
            a, b, dst_generation=tz.generation, src_generation=0
        )
        gen_refused = False
    except ShardMergeError:
        gen_refused = True

    rss_slope_mb = (
        (rss_end - rss_mid) / 1024.0
        if rss_end is not None and rss_mid is not None else None
    )
    rss_slope_ok = (
        bool(rss_slope_mb <= rss_slope_limit_mb)
        if rss_slope_mb is not None else None
    )
    frames_corrupt = int(pool_stats.get("frames_corrupt", 0))
    tmpdir.cleanup()
    return {
        "capacity": capacity,
        "distinct_streamed": live_cohort + churn_per_wave * waves,
        "live_cohort": live_cohort,
        "waves": waves,
        "evictions": int(mgr.evictions),
        "sweeps": int(mgr.sweeps),
        "generation": int(tz.generation),
        "evicted_total": int(tz.evicted_total),
        "overflow_assigns": int(tz.overflow_assigns_total),
        "eviction_records": int(writer.evictions_recorded),
        "max_level": int(max_level),
        "shed_responses": int(shed[0]),
        "reports": int(reports[0]),
        "via_frontdoor": bool(use_fd),
        "elapsed_s": round(elapsed, 2),
        "live_ids_stable": bool(live_stable),
        "live_rows_ok": bool(live_rows_ok),
        "evicted_query_ok": bool(evicted_query_ok),
        "gen_refused": bool(gen_refused),
        "frames_corrupt": frames_corrupt,
        "rss_mid_kb": rss_mid,
        "rss_end_kb": rss_end,
        "rss_slope_mb": (
            round(rss_slope_mb, 1) if rss_slope_mb is not None else None
        ),
        "rss_slope_ok": rss_slope_ok,
        "churn_ok": bool(
            mgr.evictions > 0
            and tz.generation > 0
            and live_stable
            and live_rows_ok
            and evicted_query_ok
            and gen_refused
            and frames_corrupt == 0
            and rss_slope_ok is not False
        ),
    }


def main() -> None:
    import json
    import os

    perf = measure_frontdoor_vs_pool(
        workers=int(os.environ.get("BENCH_FRONTDOOR_WORKERS", "2")),
        seconds=float(os.environ.get("BENCH_FRONTDOOR_SECONDS", "4.0")),
    )
    soak = measure_million_key_soak(
        target_keys=int(
            os.environ.get("BENCH_FRONTDOOR_KEYS", str(1_048_576))
        ),
    )
    churn = measure_churn_soak(
        waves=int(os.environ.get("BENCH_CHURN_WAVES", "8")),
    )
    eligible = (os.cpu_count() or 1) >= 2
    print(
        json.dumps(
            {
                "metric": "frontdoor_vs_pool_and_million_key_soak",
                "frontdoor": perf or {},
                "soak": soak or {},
                "churn": churn or {},
                # Same null-when-ineligible convention as bench.py's
                # decode_wall_ok: on a 1-core box neither door can
                # overlap anything, so pass/fail is unmeasurable.
                "frontdoor_ok": (
                    bool(
                        perf["frontdoor_spans_per_sec"]
                        >= perf["pool_spans_per_sec"]
                    )
                    if perf is not None and eligible else None
                ),
                "soak_ok": (soak or {}).get("soak_ok"),
                "soak_rss_ok": (soak or {}).get("soak_rss_ok"),
                "churn_ok": (churn or {}).get("churn_ok"),
            },
            sort_keys=True,
        )
    )


if __name__ == "__main__":
    main()
