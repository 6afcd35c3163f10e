"""Parallel host-ingest engine: decode pool + pooled buffers + coalesce.

The device side of the detector sustains ~119M spans/s (bench.py); the
r5 host-ingest path topped out at ~2.26M spans/s — 53× below the rate
it must feed (SURVEY.md §7 hard part (a)). Profiling put the gap almost
entirely on the HOST glue around the native decoder, not in it: the C
scan runs ~7M spans/s single-threaded on the CI box, but every request
paid one ctypes round trip, eight fresh ``np.empty`` output arrays,
eight ``.copy()`` slices, an intern pass, and one pipeline-lock
acquisition — all serial on the receiver thread. This module removes
each of those per-REQUEST costs by making them per-FLUSH:

- **Sharded decode pool** — N worker threads pull raw payloads off one
  bounded queue. ``ctypes.CDLL`` drops the GIL for the duration of the
  native call (runtime/native.py module doc), so workers decode in
  true parallel and scale with cores.
- **Coalesced batch decode** — each worker drains up to
  ``coalesce_max`` queued requests and decodes them with ONE
  ``native.decode_otlp_many`` call: one GIL round trip amortized over
  the whole batch. Per-payload verdicts ride back in ``payload_rows``,
  so a malformed request still answers 400 for exactly that request
  while its batchmates proceed.
- **Pooled zero-copy output buffers with ticketed release** — decode
  writes into a :class:`ScratchPool` freelist of column arrays sized
  by high-watermark: steady-state decode performs zero numpy
  allocations. The flush hands the pipeline VIEWS into the scratch —
  no per-flush copy-out at all (the r7 frame round trip copied every
  row once per flush; the spine removes it). Safety is the ticket: a
  scratch whose views escaped is PARKED, re-entering the freelist only
  once no pipeline reference to its memory remains, and its
  decode-time CRC manifest (``frame.span_column_crcs``) is re-checked
  at recycle — a buffer scribbled while rows were live surfaces as
  ``anomaly_frame_corrupt_total{hop="ingest"}`` + quarantine evidence
  instead of silently feeding the sketches another request's rows
  (tests/test_ingest_pool.py + tests/test_frame.py pin this).
- **One tensorize + one merge per flush** — a single intern pass over
  the batch-wide service list and a single
  ``SpanColumns``/``submit_columns`` call per flush, so the pipeline
  lock and the interner are touched once per thousands of spans, not
  once per request.

Overload semantics are PRESERVED: admission control still lives in
``pipeline.submit_columns`` (shed/brownout/429 watermarks fire exactly
as before — the pool sits in front of the same gate), and the pool's
own queue is bounded — a full queue raises
:class:`IngestPoolSaturated`, which the receivers answer as the same
retryable 429/``RESOURCE_EXHAUSTED`` they use for pipeline saturation.
No unbounded buffer ever forms ahead of the pool. Receivers resolve a
request's ticket only AFTER its rows hit ``submit_columns``, so a 200
still means "enqueued", exactly the serial path's contract.

Latency: coalescing is opportunistic, not timed — a worker drains
whatever is queued RIGHT NOW and decodes immediately, so an idle
deployment sees single-request latency (no flush-interval tax) while a
loaded one sees deep batches automatically (the queue fills while
workers are busy — the same self-clocking the reference collector's
batch processor exhibits under load).

The r15 decode-wall rework sharpened the engine on three axes:

- **Two-pass native scanner** (ingest.cc): decode is now a structural
  boundary scan (pass 1 → span index) plus an index-driven column
  extraction (pass 2), reported separately to the
  ``anomaly_phase_seconds{phase=scan|extract}`` histograms.
- **Intra-call sharding**: a flush carrying ≥
  ``ANOMALY_INGEST_SHARD_MIN_BYTES`` of payload splits its pass-2
  extraction across up to ``ANOMALY_INGEST_NATIVE_THREADS`` native OS
  threads at span-record boundaries — mid-payload included, so ONE
  oversized OTLP export spreads over cores instead of serializing on
  whichever worker drained it.
- **Per-worker arena interning** (tensorize.InternArena): each worker
  resolves the flush's service names against worker-local memory; only
  a never-seen name pays one batched reconciliation against the shared
  read-mostly table. Intern ids stay bit-identical to the serial path.

Knob registry: ``utils.config.INGEST_KNOBS`` (workers / coalesce /
max-pending / native-threads / shard-min-bytes), threaded through the
daemon env, the compose overlay and the k8s generator;
scripts/sanitycheck.py pins the correspondence.
"""

from __future__ import annotations

import queue
import sys
import threading
import time
from collections import deque
from typing import Callable, NamedTuple, Sequence

from . import frame, native, selftrace
from .otlp import MONITORED_ATTR_KEYS, decode_export_request
from .selftrace import (
    PHASE_DECODE,
    PHASE_EXTRACT,
    PHASE_SCAN,
    PHASE_SUBMIT,
    PHASE_TENSORIZE,
    PHASE_VERIFY,
    SPAN_DECODE,
    SPAN_SUBMIT,
    SPAN_TENSORIZE,
    SPAN_VERIFY,
)
from .tensorize import InternArena, SpanColumns, SpanRecord, SpanTensorizer

# Phases whose durations PARTITION a flush's wall time. PHASE_SCAN /
# PHASE_EXTRACT are sub-phases INSIDE the decode envelope (the native
# two-pass split) — share computations over TOP_PHASES stay a true
# breakdown while the sub-phases ride the same histograms for
# attribution.
TOP_PHASES = (PHASE_DECODE, PHASE_VERIFY, PHASE_TENSORIZE, PHASE_SUBMIT)


class IngestPoolSaturated(RuntimeError):
    """The bounded request queue ahead of the pool is full — the
    receivers' cue to answer retryable 429/RESOURCE_EXHAUSTED."""


class IngestWorkerError(RuntimeError):
    """A flush failed SERVER-side (e.g. the pipeline sink raised) after
    decode — distinct from a per-payload decode verdict so the
    receivers answer 5xx/INTERNAL for our bugs and 400 only for the
    client's bad bytes (the serial path's 'server bugs must surface,
    not masquerade as a client error' contract)."""


class DecodeTicket:
    """Per-request decode verdict: the receiver blocks on ``result()``
    to answer 400 (malformed) vs 200 (decoded AND enqueued).

    The Event is allocated LAZILY, only when a waiter arrives before
    the verdict: fire-and-forget submitters (the Kafka pump, benches)
    then pay one flag write instead of a kernel-object allocation per
    request. The ``_done``-before-``_event`` publication order below
    makes the lock-free handshake safe under the GIL: whichever of
    {resolver reads ``_event``, waiter re-reads ``_done``} happens
    second sees the other side's write.
    """

    __slots__ = ("_done", "_error", "_event")

    def __init__(self) -> None:
        self._done = False
        self._error: BaseException | None = None
        self._event: threading.Event | None = None

    def _resolve(self, error: BaseException | None = None) -> None:
        self._error = error
        self._done = True  # publish BEFORE checking for a waiter
        ev = self._event
        if ev is not None:
            ev.set()

    def done(self) -> bool:
        """Non-blocking: has the request's flush landed (success or
        error)? Safe from any thread — ``_done`` is published last by
        the resolver. The front-door pump polls this to defer a
        wedged-flush verdict instead of abandoning a borrowed native
        buffer (``runtime/frontdoor.py``)."""
        return self._done

    def result(self, timeout: float = 30.0) -> None:
        """Block until the request's flush lands; re-raise its decode
        error (``ValueError`` for malformed wire data) if any."""
        if not self._done:
            ev = self._event
            if ev is None:
                ev = threading.Event()
                self._event = ev
                if self._done:  # resolver ran before our store landed
                    ev.set()
            if not ev.wait(timeout):
                raise TimeoutError("ingest pool did not resolve the request")
        if self._error is not None:
            raise self._error


class _ParkedScratch(NamedTuple):
    """A ticketed scratch: held OUT of the freelist until no pipeline
    view references its memory, then CRC-verified and recycled."""

    scratch: object  # native.DecodeScratch
    cols: object  # native.ColumnarSpans — the decode views, retained
    crcs: dict  # frame.span_column_crcs manifest from decode time


class ScratchPool:
    """Freelist of :class:`native.DecodeScratch` buffer sets, sized by
    high-watermark: the first few flushes grow the dims, after which
    every acquire is a pop — zero allocator churn on the hot path. At
    most ``keep`` sets are retained (one per worker is enough; an
    occasional burst allocates and is dropped on release).

    **Ticketed release** (the zero-copy ingest spine): a flush that
    handed SCRATCH VIEWS to the pipeline parks its scratch instead of
    releasing it. A parked scratch re-enters the freelist only once no
    outside reference to its column memory remains — checked by
    refcount under the GIL: each retained decode view holds exactly one
    reference to its backing array, and every pipeline slice holds one
    more (numpy collapses ``view.base`` to the owning array), so a
    quiescent lane shows exactly the pool's own references. Before
    recycling, the decode-time CRC manifest is re-verified against the
    scratch memory: a mismatch means something scribbled the buffer
    while rows were still live — the aliasing bug class the old
    frame-copy-out caught per flush — and the scratch is discarded with
    the evidence queued for the owner to count + quarantine. A scratch
    whose views outlive demand simply stays parked; ``acquire`` then
    allocates fresh (visible in ``allocations``) rather than ever
    recycling live memory.
    """

    def __init__(self, keep: int = 4):
        self._free: list = []
        self._lock = threading.Lock()
        self._keep = keep
        self._hw = (0, 0, 0)
        self._parked: list[_ParkedScratch] = []
        self.allocations = 0  # how often acquire had to allocate
        self.tickets_parked = 0  # flushes that handed out scratch views
        self.tickets_recycled = 0  # parked scratches returned to the freelist
        # Scavenged entries whose memory no longer matched the decode
        # manifest: (cols, bad_column_names) for the owner to count and
        # quarantine (detection is at recycle time — after the rows were
        # consumed — so this is an audit trail, not a gate). The deque
        # bounds EVIDENCE retention only; corrupt_total is the honest
        # monotone count (an event storm past the deque bound must not
        # undercount the counter the audit trail exists to feed).
        self.corrupt: deque = deque(maxlen=16)
        self.corrupt_total = 0

    @staticmethod
    def _quiescent(entry: _ParkedScratch) -> bool:
        """True when no reference outside the parked entry can reach
        the scratch memory (CPython refcounts, checked under the GIL).

        Per retained view: the entry's cols tuple plus this frame's
        local are the only holders (refcount 3 incl. the getrefcount
        temp); per backing array: the scratch namedtuple, that one
        view's ``.base`` slot and this frame's local (refcount 4 incl.
        temp) — any pipeline slice of a handed-out view keeps a base
        reference to the backing array and shows up here. Another
        thread mid-read merely elevates a count for one round — the
        check is conservative, never unsafe. Iterates every ARRAY
        field of the ColumnarSpans (the trailing ``services`` string
        list has no ``.dtype``), so a future column can't silently
        escape the quiescence check."""
        for i in range(len(entry.cols)):
            view = entry.cols[i]
            if not hasattr(view, "dtype"):
                continue  # services list, not a column array
            if sys.getrefcount(view) > 3:
                return False
            base = view.base
            if base is not None and sys.getrefcount(base) > 4:
                return False
        return True

    def _scavenge_locked(self) -> None:
        still: list[_ParkedScratch] = []
        for entry in self._parked:
            if not self._quiescent(entry):
                still.append(entry)
                continue
            bad = frame.verify_span_columns(entry.cols, entry.crcs)
            if bad:
                # Scribbled while parked: never recycle the buffer,
                # surface the evidence (drained by the ingest pool
                # into anomaly_frame_corrupt_total{hop="ingest"}).
                self.corrupt_total += 1
                self.corrupt.append((entry.cols, bad))
            else:
                self.tickets_recycled += 1
                if len(self._free) < self._keep:
                    self._free.append(entry.scratch)
        self._parked = still

    def parked(self) -> int:
        with self._lock:
            return len(self._parked)

    def park(self, scratch, cols, crcs: dict) -> None:
        """Ticketed release: hold ``scratch`` until the pipeline drops
        every view into it (see class doc), then verify + recycle."""
        with self._lock:
            self._parked.append(_ParkedScratch(scratch, cols, crcs))
            self.tickets_parked += 1

    def acquire(self, cap: int, svc_cap: int, rs_cap: int):
        with self._lock:
            self._scavenge_locked()
            self._hw = (
                max(self._hw[0], cap),
                max(self._hw[1], svc_cap),
                max(self._hw[2], rs_cap),
            )
            for i, s in enumerate(self._free):
                if s.cap >= cap and s.svc_cap >= svc_cap and s.rs_cap >= rs_cap:
                    return self._free.pop(i)
            hw = self._hw
            self.allocations += 1
        return native.alloc_scratch(*hw)

    def release(self, scratch) -> None:
        with self._lock:
            if len(self._free) < self._keep:
                self._free.append(scratch)


_STOP = object()


class _JobQueue:
    """Bounded MPMC queue with BATCHED consume.

    ``queue.Queue`` costs one lock round trip per ``get`` — 64 of them
    per coalesced flush. ``get_batch`` pops the whole coalesce window
    under ONE lock acquisition, which is where the pool's per-request
    overhead has to live for the flush amortization to mean anything.
    ``put`` blocks up to ``timeout`` for space and then raises
    ``queue.Full`` (the bounded-admission contract).
    """

    def __init__(self, maxsize: int):
        self._d: deque = deque()
        self._max = int(maxsize)
        lock = threading.Lock()
        self._not_empty = threading.Condition(lock)
        self._not_full = threading.Condition(lock)

    def put(self, item, timeout: float) -> None:
        with self._not_full:
            if len(self._d) >= self._max:
                deadline = time.monotonic() + timeout
                while len(self._d) >= self._max:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        raise queue.Full
                    self._not_full.wait(remaining)
            self._d.append(item)
            self._not_empty.notify()

    def put_unbounded(self, item) -> None:
        """Bypass the bound (shutdown sentinels only)."""
        with self._not_empty:
            self._d.append(item)
            self._not_empty.notify()

    def get_batch(self, max_n: int) -> list:
        with self._not_empty:
            while not self._d:
                self._not_empty.wait()
            n = min(len(self._d), max_n)
            batch = [self._d.popleft() for _ in range(n)]
            self._not_full.notify(n)
            return batch

    def qsize(self) -> int:
        return len(self._d)


class IngestPool:
    """N decode workers between the receivers and the pipeline.

    ``submit(payload)`` (OTLP/HTTP + OTLP/gRPC protobuf bodies) returns
    a :class:`DecodeTicket`; ``submit_records(records)`` (the Kafka
    pump and any already-decoded source) folds record batches into the
    same coalesced flushes. The off switch lives at the call site: the
    daemon simply doesn't construct a pool when
    ``ANOMALY_INGEST_WORKERS=0`` (receivers then keep the serial
    in-thread decode path), so a constructed pool always has ≥1 worker.
    """

    SUBMIT_TIMEOUT_S = 1.0  # bounded wait for queue space before 429

    def __init__(
        self,
        submit_columns: Callable[[SpanColumns], None],
        tensorizer: SpanTensorizer,
        workers: int = 2,
        coalesce_max: int = 64,
        max_pending: int = 512,
        attr_keys: Sequence[str] = MONITORED_ATTR_KEYS,
        phase_observe=None,
        selftrace=None,
        native_threads: int = 2,
        shard_min_bytes: int = native.SHARD_MIN_BYTES_DEFAULT,
    ):
        if workers <= 0:
            raise ValueError("IngestPool needs workers >= 1 (0 = no pool)")
        self.submit_columns = submit_columns
        self.tensorizer = tensorizer
        self.workers = int(workers)
        self.coalesce_max = max(int(coalesce_max), 1)
        # Intra-call sharding (the two-pass scanner's pass 2): a flush
        # carrying >= shard_min_bytes of payload splits its extraction
        # across up to native_threads OS threads at span-record
        # boundaries — one oversized export no longer serializes on
        # one core even when only one pool worker holds it.
        # native_threads <= 1 keeps extraction serial per call.
        self.native_threads = int(native_threads)
        self.shard_min_bytes = int(shard_min_bytes)
        self.attr_keys = tuple(attr_keys)
        # Self-telemetry (runtime.selftrace): ``phase_observe(phase,
        # seconds)`` feeds the promoted anomaly_phase_seconds
        # histograms per flush; ``selftrace.flush_segment`` records the
        # same durations as an ingest segment the next sampled batch
        # trace absorbs. Both optional and both cheap — one callback /
        # one bounded append per FLUSH, never per request.
        self.phase_observe = phase_observe
        self.selftrace = selftrace
        self._q = _JobQueue(max_pending)
        self._scratch = ScratchPool(keep=self.workers + 1)
        # Stats (guarded by _stats_lock; read by the daemon's scrape).
        self._stats_lock = threading.Lock()
        self.flushes = 0
        self.flushed_spans = 0
        self.coalesced_requests = 0
        self.decode_errors = 0
        self.worker_failures = 0  # server-side flush failures (per flush)
        # Parked-scratch CRC mismatches (lifecycle bugs, memory
        # corruption): counted + quarantined at scavenge time.
        # Exported as anomaly_frame_corrupt_total{hop="ingest"}.
        self.frames_corrupt = 0
        # Per-phase flush wall time (decode / verify / tensorize /
        # submit) — the attribution the spine's win is measured by
        # (ingestbench phase breakdown).
        self.phase_s = {
            PHASE_DECODE: 0.0, PHASE_SCAN: 0.0, PHASE_EXTRACT: 0.0,
            PHASE_VERIFY: 0.0, PHASE_TENSORIZE: 0.0, PHASE_SUBMIT: 0.0,
        }
        self._scratch_corrupt_seen = 0
        self.busy_s = 0.0  # summed across workers
        # Drain accounting: jobs submitted but not yet fully processed.
        self._inflight = 0
        self._idle = threading.Condition(self._stats_lock)
        self._stop = False
        self._threads: list[threading.Thread] = []
        for i in range(self.workers):
            self._spawn(i)

    def _spawn(self, idx: int) -> None:
        t = threading.Thread(
            target=self._run, name=f"ingest-pool-{idx}", daemon=True
        )
        t.start()
        if idx < len(self._threads):
            self._threads[idx] = t
        else:
            self._threads.append(t)

    # -- producer side -------------------------------------------------

    def submit(self, payload: bytes) -> DecodeTicket:
        """Enqueue one protobuf ExportTraceServiceRequest body.

        Blocks briefly for queue space; a still-full queue raises
        :class:`IngestPoolSaturated` — the bounded-admission contract
        (never an unbounded buffer ahead of the pool).
        """
        ticket = DecodeTicket()
        self._enqueue(("payload", payload, ticket))
        return ticket

    def submit_records(
        self, records: list[SpanRecord]
    ) -> DecodeTicket | None:
        """Enqueue already-decoded records (Kafka pump etc.) for the
        same coalesced tensorize+merge pass. Returns a ticket that
        resolves once the batch's flush reached the pipeline (the
        pump's at-least-once bookkeeping waits on it), or None for an
        empty batch. The ticket's Event is lazy, so fire-and-forget
        callers pay nothing for ignoring it."""
        if not records:
            return None
        ticket = DecodeTicket()
        self._enqueue(("records", records, ticket))
        return ticket

    def _enqueue(self, item) -> None:
        with self._stats_lock:
            self._inflight += 1
        try:
            self._q.put(item, timeout=self.SUBMIT_TIMEOUT_S)
        except queue.Full:
            with self._stats_lock:
                self._inflight -= 1
                if self._inflight == 0:
                    self._idle.notify_all()
            raise IngestPoolSaturated(
                f"ingest queue full ({self._q._max} pending requests)"
            ) from None

    def depth(self) -> int:
        return self._q.qsize()

    # -- worker side ---------------------------------------------------

    def _run(self) -> None:
        # Per-worker intern arena: the flush's service names resolve
        # against worker-local memory; only a genuinely new name pays
        # ONE batched reconciliation with the shared tensorizer table.
        # Ids are bit-identical to the serial service_id path.
        arena = InternArena(self.tensorizer)
        while True:
            batch = self._q.get_batch(self.coalesce_max)
            jobs = [b for b in batch if b is not _STOP]
            n_stop = len(batch) - len(jobs)
            # A batched pop can swallow sentinels meant for sibling
            # workers: hand the extras back before exiting.
            for _ in range(n_stop - 1):
                self._q.put_unbounded(_STOP)
            if jobs:
                t0 = time.perf_counter()
                try:
                    self._process(jobs, arena)
                except Exception as e:  # noqa: BLE001 — worker survives
                    # Unexpected (non-decode) failure: resolve every
                    # ticket with a SERVER-fault wrapper so no receiver
                    # hangs and none of them mistakes our bug for a
                    # malformed payload; counted as a worker failure
                    # (per flush), NOT as decode_errors — that counter
                    # means "client sent wire garbage" and must stay
                    # honest for triage.
                    err = IngestWorkerError(f"{type(e).__name__}: {e}")
                    err.__cause__ = e
                    for _kind, _data, ticket in jobs:
                        if ticket is not None and not ticket._done:
                            ticket._resolve(err)
                    with self._stats_lock:
                        self.worker_failures += 1
                finally:
                    dt = time.perf_counter() - t0
                    with self._stats_lock:
                        self.busy_s += dt
                        self._inflight -= len(jobs)
                        if self._inflight == 0:
                            self._idle.notify_all()
            if n_stop:
                return

    def _process(self, batch: list, arena: InternArena | None = None) -> None:
        payload_jobs = [(d, t) for kind, d, t in batch if kind == "payload"]
        record_jobs = [(d, t) for kind, d, t in batch if kind == "records"]
        parts: list[SpanColumns] = []
        errors: dict[int, BaseException] = {}  # job index → decode error
        # Per-flush phase ledger: the same durations feed the lifetime
        # phase_s counters, the anomaly_phase_seconds histograms and
        # (when a tracer rides along) the ingest segment of the next
        # sampled batch trace — one measurement, three consumers.
        seg: dict[str, float] = {}

        def ledger(phase: str, dt: float) -> None:
            self._phase(phase, dt, seg)

        if payload_jobs:
            if native.available():
                parts += self._decode_native(payload_jobs, errors, ledger, arena)
            else:
                parts += self._decode_python(payload_jobs, errors, ledger)
        if record_jobs:
            with selftrace.span(SPAN_TENSORIZE, observe=ledger):
                merged: list[SpanRecord] = []
                for records, _t in record_jobs:
                    merged.extend(records)
                parts.append(self.tensorizer.columns_from_records(merged))
        cols = SpanColumns.concat(parts) if parts else None
        n_rows = cols.rows if cols is not None else 0
        if n_rows:
            with selftrace.span(SPAN_SUBMIT, observe=ledger):
                self.submit_columns(cols)
        if self.selftrace is not None and seg:
            self.selftrace.flush_segment(seg)
        del parts, cols  # drop the worker's view refs: the rows stay
        # alive exactly as long as the PIPELINE holds them (the ticket
        # discipline the parked-scratch scavenge keys on)
        self._drain_scratch_corruption()
        with self._stats_lock:
            self.flushes += 1
            self.coalesced_requests += len(batch)
            self.flushed_spans += n_rows
            self.decode_errors += len(errors)
        # Tickets resolve AFTER submit_columns: a 200 means the rows
        # are enqueued (the serial path's contract), and error-lane
        # rows can never reorder past their own flush boundary.
        for i, (_payload, ticket) in enumerate(payload_jobs):
            if ticket is not None:
                ticket._resolve(errors.get(i))
        for _records, ticket in record_jobs:
            if ticket is not None:
                ticket._resolve(None)

    def _decode_native(self, payload_jobs, errors, ledger, arena=None) -> list[SpanColumns]:
        payloads = [p for p, _t in payload_jobs]
        total = sum(len(p) for p in payloads)
        scratch = None
        parked = False
        native_phases: dict[str, float] = {}
        try:
            # The decode span is observed BEFORE the empty-flush
            # return: an all-malformed flood burns real decode time and
            # the attribution must show it. scan/extract are the native
            # call's own two-pass split — sub-phases of the decode
            # envelope, never added into a share denominator
            # (TOP_PHASES).
            with selftrace.span(SPAN_DECODE, observe=ledger):
                scratch = self._scratch.acquire(
                    *native.scratch_dims(total, len(payloads))
                )
                cols, payload_rows = native.decode_otlp_many(
                    payloads, self.attr_keys, scratch,
                    threads=self.native_threads,
                    shard_min_bytes=self.shard_min_bytes,
                    phases=native_phases,
                )
                for i, rows in enumerate(payload_rows):
                    if rows < 0:
                        errors[i] = ValueError("malformed OTLP payload")
            ledger(PHASE_SCAN, native_phases.get("scan", 0.0))
            ledger(PHASE_EXTRACT, native_phases.get("extract", 0.0))
            if not cols.duration_us.shape[0]:
                return []
            # Zero-copy hand-off (the ingest spine): the pipeline
            # receives VIEWS into the decode scratch — the per-flush
            # frame-buffer copy-out is gone. Integrity moves from
            # copy-then-verify to ticketed release: the decode views'
            # CRC manifest is taken NOW (frame.span_column_crcs, the
            # same native crc32c the frame trailer used), the scratch
            # is PARKED instead of released, and it re-enters the
            # freelist only once no pipeline view references it — at
            # which point the manifest is re-checked, so a buffer that
            # was scribbled while rows were live still surfaces as
            # anomaly_frame_corrupt_total{hop="ingest"} + quarantine
            # evidence (see ScratchPool). The recycled-early race the
            # old copy guarded against cannot happen: a still-
            # referenced scratch is simply never handed out again.
            with selftrace.span(SPAN_VERIFY, observe=ledger):
                crcs = frame.span_column_crcs(cols)
            with selftrace.span(SPAN_TENSORIZE, observe=ledger):
                out = self.tensorizer.columns_from_columnar(
                    cols, copy=False, arena=arena
                )
            if cols.duration_us.base is scratch.duration:
                self._scratch.park(scratch, cols, crcs)
                parked = True
            # else: decode grew past the pooled scratch mid-call and
            # returned views into a bigger private buffer (or copies)
            # — plain GC owns that memory; OUR scratch saw no views
            # and goes straight back to the freelist.
            return [out]
        finally:
            if scratch is not None and not parked:
                self._scratch.release(scratch)

    def _phase(self, name: str, dt: float, seg: dict | None = None) -> None:
        """Accumulate per-phase flush time (decode / verify /
        tensorize / submit) — how an operator attributes a flush's
        wall time between the native decoder, the integrity manifest,
        the intern/column pass and the pipeline merge. Also fans the
        sample out to the promoted histogram (``phase_observe``) and
        the caller's per-flush segment ledger (``seg``)."""
        with self._stats_lock:
            self.phase_s[name] += dt
        if seg is not None:
            seg[name] = seg.get(name, 0.0) + dt
        if self.phase_observe is not None:
            self.phase_observe(name, dt)

    def _drain_scratch_corruption(self) -> None:
        """Surface parked-scratch CRC mismatches (see ScratchPool):
        count anomaly_frame_corrupt_total{hop="ingest"} and write the
        frame-encoded rows aside as quarantine evidence. Detection is
        at recycle time — after consumption — so this is the audit
        trail for a lifecycle bug, not an admission gate. The COUNT
        comes from the monotone corrupt_total (evidence past the
        bounded deque still counts); the deque holds what forensics
        gets."""
        total = self._scratch.corrupt_total  # int read: GIL-atomic
        delta = total - self._scratch_corrupt_seen
        if delta > 0:
            self._scratch_corrupt_seen = total
            with self._stats_lock:
                self.frames_corrupt += delta
        while True:
            try:
                cols, _bad = self._scratch.corrupt.popleft()
            except IndexError:
                return
            try:
                frame.quarantine(frame.encode_spans(cols), "ingest")
            except Exception:  # noqa: BLE001 — forensics must never
                pass  # compound the fault (same rule as quarantine())

    def _decode_python(self, payload_jobs, errors, ledger) -> list[SpanColumns]:
        """No-compiler fallback: per-request wire decode, still ONE
        coalesced tensorize pass per flush."""
        merged: list[SpanRecord] = []
        with selftrace.span(SPAN_DECODE, observe=ledger):
            for i, (payload, _t) in enumerate(payload_jobs):
                try:
                    merged.extend(decode_export_request(payload))
                except Exception as e:  # noqa: BLE001 — per-request verdict
                    errors[i] = e
        if not merged:
            return []
        return [self.tensorizer.columns_from_records(merged)]

    # -- lifecycle / supervision --------------------------------------

    def alive(self) -> bool:
        """Supervisor probe: every worker thread is running."""
        return not self._stop and all(t.is_alive() for t in self._threads)

    def restart_workers(self) -> None:
        """Respawn dead workers (the supervisor's restart hook)."""
        if self._stop:
            return
        for i, t in enumerate(self._threads):
            if not t.is_alive():
                self._spawn(i)

    def drain(self, timeout: float = 30.0) -> bool:
        """Block until every submitted job has been processed."""
        deadline = time.monotonic() + timeout
        with self._idle:
            while self._inflight:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._idle.wait(remaining)
        return True

    def close(self) -> None:
        """Flush everything, then stop the workers."""
        self.drain()
        self._stop = True
        for _ in self._threads:
            self._q.put_unbounded(_STOP)
        for t in self._threads:
            t.join(timeout=5.0)

    # -- telemetry -----------------------------------------------------

    def stats(self) -> dict:
        """Point-in-time counters for the daemon's metrics scrape."""
        with self._stats_lock:
            return {
                "depth": self._q.qsize(),
                "flushes": self.flushes,
                "flushed_spans": self.flushed_spans,
                "coalesced_requests": self.coalesced_requests,
                "decode_errors": self.decode_errors,
                "worker_failures": self.worker_failures,
                "frames_corrupt": self.frames_corrupt,
                "busy_s": self.busy_s,
                "phase_s": dict(self.phase_s),
                "tickets_parked": self._scratch.tickets_parked,
                "tickets_recycled": self._scratch.tickets_recycled,
                "workers": self.workers,
            }
