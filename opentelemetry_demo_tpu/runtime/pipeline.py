"""Streaming pipeline: records → batches → device, without host syncs.

The latency budget (<100 ms p99 detection lag, BASELINE north_star)
shapes this module: JAX dispatch is asynchronous, so the pipeline keeps
exactly one report in flight — batch *k* is dispatched before batch
*k-1*'s report is fetched, overlapping host tensorization, host→device
transfer, and device compute the way the reference's async Kafka
producer overlaps order handling
(/root/reference/src/checkout/kafka/producer.go:15-43).

Flag gating per the north star: ``anomalyDetectorEnabled`` switches the
whole device path off (records are drained and dropped);
``anomalyDetectorZThreshold`` adjusts flagging at report time without
recompiling (the jitted step's threshold only feeds the report's
``flags`` bool — the z-scores themselves are always emitted).

Ingest seam: ``submit_columns`` is the ONE admission gate — the serial
receiver paths (``submit``/``submit_columnar``) and the parallel
ingest engine (``runtime.ingest_pool``, which coalesces many requests
into one columns batch per flush) all merge through it, so shed/
brownout/saturation semantics are identical regardless of which decode
architecture fed the queue, and the pipeline lock is taken once per
flush instead of once per request on the pooled path.

Overload protection (``queue_max_rows`` > 0): the pending queue is
row-budgeted with high/low watermarks — the reference collector's
``memory_limiter`` + ``sending_queue`` discipline rebuilt at the
pipeline seam. Over budget, the OLDEST OK-lane rows are shed first and
error/exception-lane rows are never shed (``SHED_LANES``); between the
watermarks a saturation flag (hysteresis) tells the OTLP receivers to
answer retryable 429/``RESOURCE_EXHAUSTED``; and under SUSTAINED
saturation a deterministic brownout ladder head-samples OK-lane rows
(1/2, 1/4, …) so detection stays live — degraded and counted — instead
of lagging unboundedly. tests/test_overload.py is the proof.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Iterable

import jax
import numpy as np

from ..models.detector import (
    AnomalyDetector,
    DetectorReport,
    evidence_unpack,
    report_unpack,
)
from ..ops.hashing import splitmix64_np
from ..utils.flags import FlagEvaluator
from . import selftrace
from .provenance import (
    REASON_CARDINALITY,
    REASON_CUSUM,
    REASON_ERROR_RATE,
    REASON_LATENCY,
    REASON_THROUGHPUT,
    candidate_columns,
)
from .selftrace import (
    PHASE_HARVEST_LAG,
    PHASE_QUEUE_WAIT,
    PHASE_REPORT_WAIT,
    PHASE_RING_WAIT,
    SPAN_DISPATCH,
    SPAN_EVIDENCE,
    SPAN_EXPORT,
    SPAN_FLAG,
    SPAN_HARVEST,
)
from .tensorize import SpanColumns, SpanRecord, SpanTensorizer

FLAG_ENABLED = "anomalyDetectorEnabled"
FLAG_THRESHOLD = "anomalyDetectorZThreshold"

# Admission contract: the lanes the shed policy is ALLOWED to drop.
# The error/exception lane is deliberately absent — under any overload
# the rows that explain an incident are the last ones a detector may
# throw away. scripts/sanitycheck.py pins this constant (and the tests
# assert the error-lane counter stays 0 under a 5x flood), so a future
# edit that widens it is a visible contract change, not a drive-by.
SHED_LANES = ("ok",)

# Keyspace degradation ladder (runtime/keyspace.py drives the clock;
# KEYSPACE_KNOBS is the registry). One rung per ANOMALY_KEYSPACE_HOLD_S
# of SUSTAINED pressure, two-edge hysteresis exactly like the brownout
# ladder — each rung degrades NEW-key admission harder while existing
# keys' detection stays untouched:
#   0 normal · 1 evict idle keys · 2 per-tenant new-key throttle ·
#   3 overflow-collapse all new keys · 4 shed ingest (429 Retry-After).
KEYSPACE_LEVEL_EVICT = 1
KEYSPACE_LEVEL_THROTTLE = 2
KEYSPACE_LEVEL_COLLAPSE = 3
KEYSPACE_LEVEL_SHED = 4
KEYSPACE_MAX_LEVEL = KEYSPACE_LEVEL_SHED


def _pow2_ceil(n: int) -> int:
    """Smallest power of two ≥ n — the width ladder's rounding rule
    (constructor cap AND escalation factor must agree, or a width
    leaves the precompiled ladder and compiles mid-incident)."""
    return 1 << max(n - 1, 0).bit_length()


@dataclass
class PipelineStats:
    batches: int = 0
    spans: int = 0
    dropped_disabled: int = 0
    # Dispatched rows whose verdict has been harvested: at each
    # harvested report, the cumulative ``spans`` as of that report's
    # batch. A skipped report's rows are credited by the next harvested
    # report, which covers them (device state folded them in order).
    spans_harvested: int = 0
    flag_events: int = 0
    # Reports dropped unfetched past the in-flight bound (their batches
    # still updated device state; only the host-side readback skipped).
    reports_skipped: int = 0
    # Bounded window: the exported p99 tracks *current* lag, and memory
    # stays constant in a sidecar that pumps for days.
    lag_ms: deque = field(default_factory=lambda: deque(maxlen=2048))
    # Overload accounting (bounded admission): rows dropped by the
    # overflow shed, per lane. The "error" key exists so the
    # zero-error-lane-loss invariant is an asserted number, not an
    # absence — it must stay 0 (SHED_LANES).
    shed_rows: dict = field(default_factory=lambda: {"ok": 0, "error": 0})
    # Per-tenant quota shed (the fleet's noisy-tenant isolation,
    # ANOMALY_FLEET_TENANT_QUOTA_ROWS_S): OK-lane rows a tenant lost
    # to ITS OWN bucket, keyed by tenant — exported as
    # anomaly_shed_rows_total{tenant=}. Other tenants' admission is
    # untouched by construction (one bucket per tenant).
    shed_rows_tenant: dict = field(default_factory=dict)
    # OK-lane rows dropped by the brownout head-sampler (deliberate,
    # deterministic degradation — distinct from the overflow shed).
    brownout_rows: int = 0
    # Times the queue crossed the high watermark (one event per
    # saturation episode, not per refused request).
    saturation_events: int = 0
    # Keyspace ladder accounting (runtime/keyspace.py): NEW keys a
    # tenant's token bucket deferred to overflow at the throttle rung,
    # and new keys collapsed wholesale at the collapse rung — both
    # keyed by tenant, exported as
    # anomaly_keyspace_newkey_throttled_total{tenant=} /
    # anomaly_keyspace_overflow_keys_total{tenant=}.
    newkey_throttled_tenant: dict = field(default_factory=dict)
    overflow_keys_tenant: dict = field(default_factory=dict)
    # Times keyspace pressure crossed its high watermark (one event
    # per pressure episode, mirroring saturation_events).
    keyspace_pressure_events: int = 0

    def lag_p99_ms(self) -> float:
        if not self.lag_ms:
            return 0.0
        return float(np.percentile(np.asarray(self.lag_ms), 99))


class DetectorPipeline:
    """Drives an :class:`AnomalyDetector` from a span-record source."""

    def __init__(
        self,
        detector: AnomalyDetector,
        flags: FlagEvaluator | None = None,
        on_report: Callable[[float, DetectorReport, list[str]], None] | None = None,
        batch_size: int = 2048,
        max_wait_s: float = 0.05,
        adaptive_batching: bool = False,
        max_batch_growth: int = 8,
        queue_max_rows: int = 0,
        high_watermark: float = 0.85,
        low_watermark: float = 0.5,
        brownout_hold_s: float = 2.0,
        brownout_max_level: int = 4,
        retry_after_s: float = 1.0,
        exemplar_ring: int = 8,
        hh_candidates: int = 64,
        spine_ring: int = 0,
        spine_chunk_rows: int = 0,
        phase_observe: Callable[[str, float], None] | None = None,
        selftrace=None,
        history_capture: Callable[[object, float], None] | None = None,
        tenant_of: Callable[[str], str] | None = None,
        tenant_quota_rows_s: float = 0.0,
        provenance=None,
        explain_ring: int = 64,
        keyspace_enable: bool = False,
        keyspace_high_watermark: float = 0.85,
        keyspace_low_watermark: float = 0.70,
        keyspace_hold_s: float = 5.0,
        keyspace_newkey_rate: float = 64.0,
        keyspace_retry_after_s: float = 2.0,
    ):
        self.detector = detector
        # Time-travel span capture (runtime.history.HistoryWriter
        # .capture, or None): every dispatched batch's host columns +
        # virtual timebase, the replay corpus replaybench re-feeds.
        # The callee copies and enqueues (bounded, drop-oldest) — the
        # pump thread pays one memcpy, never an encode or a disk write.
        self.history_capture = history_capture
        # Self-telemetry (runtime.selftrace): ``phase_observe(phase,
        # seconds)`` feeds the promoted per-phase histograms (dispatch/
        # stage/put-wait/harvest/harvest-lag/flag) one sample per batch;
        # ``selftrace`` (a SelfTracer or None) samples whole batch
        # lifecycles into exported traces. Both default off and both
        # cost nothing when None — the hot path pays one None check.
        self.phase_observe = phase_observe
        self._selftrace = selftrace
        self.flags = flags or FlagEvaluator()
        self.on_report = on_report
        self.tensorizer = SpanTensorizer(
            num_services=detector.config.num_services, batch_size=batch_size
        )
        # Device-put spine (runtime.spine; knob registry:
        # utils.config.SPINE_KNOBS): pack+put move off the pump thread
        # onto a stager working a ring of ``spine_ring`` pre-allocated
        # host buffers, so batch k+1's host→device transfer overlaps
        # batch k's in-flight donated step. 0 = the classic in-tick
        # pack+put path. Dispatch itself NEVER moves: the spine owns no
        # detector state and every state touch stays on the pump thread
        # under _dispatch_lock (the donation-race contract).
        self._spine = None
        if spine_ring > 0:
            from .spine import DevicePutSpine

            self._spine = DevicePutSpine(
                self.tensorizer,
                depth=spine_ring,
                chunk_rows=spine_chunk_rows,
                phase_observe=phase_observe,
            )
        self.max_wait_s = max_wait_s
        # Reports are harvested on the pump thread, every pump. Set
        # while drain() runs: the adaptive hold lets go and the spine
        # waits for every staged put, so the backlog dispatches.
        self._harvest_flush = False
        # Adaptive batch growth (VERDICT r4 weak #1): when harvest can't
        # keep pace with dispatch, reports past the in-flight bound get
        # dropped unfetched EXACTLY when the operator most wants them. The
        # controller widens the dispatch batch (powers of two up to
        # ``max_batch_growth``×) until dispatch rate ≤ harvest rate, so
        # every span still reaches device state AND ~every report is
        # fetched; when the skip pressure clears, the width decays back
        # for report granularity. Each ladder width is its own compiled
        # shape — ``warm_widths()`` precompiles them off the hot path.
        self.adaptive_batching = adaptive_batching
        self._width = batch_size
        # Round the growth cap UP to a power of two: the controller
        # moves in pow2 steps, so a non-pow2 cap would clamp the width
        # off the precompiled ladder (an unwarmed shape = a compile
        # mid-incident).
        self._max_width = batch_size * _pow2_ceil(max(int(max_batch_growth), 1))
        self._adapt_lock = threading.Lock()
        self._adapt_events = 0
        self._adapt_skips = 0
        self._adapt_clean = 0
        # Decay hysteresis: each decay that promptly re-escalates (the
        # operating point sits ON the boundary) doubles the clean
        # windows required before the next decay — damping oscillation
        # between a clean width and a skipping one.
        self._adapt_clean_needed = 2
        self._last_decay = 0.0
        self._last_dispatch = time.monotonic()
        self.stats = PipelineStats()
        # Pending work is columnar ((SpanColumns, enqueue_clock) chunks
        # + a total row count): both the per-record path and the native
        # decoder land here, and batch assembly is array slicing, not
        # object pops. The enqueue clock makes the lag metric honest
        # under the adaptive accumulate-hold — lag is measured from the
        # OLDEST row's arrival, so pre-dispatch queue time counts.
        # The lock covers queue+counter as a unit — producers are
        # receiver/consumer threads, the consumer is the pump thread,
        # and the row counter plus multi-chunk batch assembly are
        # read-modify-write sequences a bare deque can't make atomic.
        self._pending: deque = deque()
        self._pending_rows = 0
        self._pending_lock = threading.Lock()
        # Set by every admission that queued rows; a serve loop waits
        # on it (and clears it) to take new rows at once instead of at
        # its next interval. Edge-triggered: rows the pump leaves
        # pending on purpose (the adaptive hold) set nothing again.
        self.wake = threading.Event()
        # Bounded admission (queue_max_rows > 0): the pending queue is
        # row-budgeted — the memory_limiter analogue for THIS process.
        # Above the budget the overflow shed drops the OLDEST OK-lane
        # rows (freshness beats completeness for telemetry, the
        # reference sending_queue's discipline) and NEVER error-lane
        # rows (SHED_LANES). Watermark hysteresis drives the saturation
        # signal the receivers propagate as 429/RESOURCE_EXHAUSTED:
        # saturated at >= high, admitting again only at <= low — so a
        # producer retrying on Retry-After doesn't flap the gate.
        if queue_max_rows:
            if not 0.0 < low_watermark < high_watermark <= 1.0:
                raise ValueError(
                    "watermarks must satisfy 0 < low < high <= 1 "
                    f"(got low={low_watermark}, high={high_watermark})"
                )
            if queue_max_rows < batch_size:
                raise ValueError(
                    f"queue_max_rows={queue_max_rows} below one batch "
                    f"({batch_size}): the pipeline could never dispatch"
                )
        self.queue_max_rows = int(queue_max_rows)
        self._high_rows = int(queue_max_rows * high_watermark)
        self._low_rows = int(queue_max_rows * low_watermark)
        self.brownout_hold_s = brownout_hold_s
        self.brownout_max_level = int(brownout_max_level)
        self.retry_after_s = retry_after_s
        self._saturated = False
        self._brownout_level = 0
        self._sat_since = 0.0
        self._unsat_since = time.monotonic()
        self._level_changed_at = 0.0
        # Guards the watermark/ladder read-modify-writes: updates come
        # from every receiver thread AND the pump; an unguarded race
        # could double-step the ladder inside one hold window.
        self._admission_lock = threading.Lock()
        # (t_batch, dispatch_clock, report, cols) — cols is the host
        # SpanColumns of the dispatched batch, kept for flag-time
        # exemplar capture (bounded: the deque holds ≤3 entries).
        self._inflight: deque = deque()
        self._inflight_lock = threading.Lock()
        # Serializes detector-state advancement: observe_packed is a
        # read-modify-write on detector.state, and warm_widths() may run
        # on a background thread beside the pump thread.
        self._dispatch_lock = threading.Lock()
        self._last_t: float | None = None
        # Query-plane capture (runtime.query): bounded per-service rings
        # of (a) exemplar trace ids taken AT FLAG TIME from the flagged
        # batch's trace-id column — every anomaly links to a concrete
        # Jaeger trace — and (b) recent attribute-CRC candidates, the
        # host-side candidate set a CMS top-k query needs (a CMS can
        # answer "how often?" but never enumerate its keys). Everything
        # here is JSON-able (query_meta) so it rides the replication
        # meta block and a read replica answers the same queries from
        # the same data. Guarded by its own lock: the writer is the pump
        # thread (candidates at dispatch, exemplars at harvest), readers
        # the replication/query snapshot threads.
        # Per-tenant sketch-namespace quota (the fleet tier's
        # noisy-tenant isolation; knob registry: utils.config
        # FLEET_KNOBS): one token bucket per tenant — capacity = one
        # second's quota, refill = quota rows/s — consulted in
        # submit_columns AHEAD of the global row budget, so a tenant
        # over quota sheds its OWN OK-lane rows (error lane always
        # passes, SHED_LANES discipline) while every other tenant's
        # admission, brownout state and TTD are untouched. tenant_of
        # maps a service NAME to its tenant (the ANOMALY_FLEET_TENANTS
        # map); quota 0 = the path costs one comparison.
        self._tenant_of = tenant_of
        self.tenant_quota_rows_s = float(tenant_quota_rows_s)
        self._tenant_buckets: dict[str, tuple[float, float]] = {}
        # Key lifecycle plane (runtime/keyspace.py; knob registry:
        # utils.config.KEYSPACE_KNOBS). The pipeline owns the per-key
        # last-seen clock (one vectorized scatter per admitted flush),
        # the keyspace degradation ladder (same two-edge hysteresis as
        # the brownout ladder, but clocked by the keyspace watchdog's
        # tick, not the queue depth), and the NEW-key admission gate
        # the tensorizer consults on a genuine intern miss. Eviction
        # itself — folding idle rows into history and retiring ids —
        # lives in keyspace.KeyspaceManager, which writes detector
        # state only under _dispatch_lock.
        self.keyspace_enable = bool(keyspace_enable)
        self.keyspace_high_watermark = float(keyspace_high_watermark)
        self.keyspace_low_watermark = float(keyspace_low_watermark)
        self.keyspace_hold_s = float(keyspace_hold_s)
        self.keyspace_newkey_rate = float(keyspace_newkey_rate)
        self.keyspace_retry_after_s = float(keyspace_retry_after_s)
        self._keyspace_level = 0
        self._ks_saturated = False
        self._ks_sat_since = 0.0
        self._ks_unsat_since = time.monotonic()
        self._ks_level_changed_at = 0.0
        self._ks_newkey_buckets: dict[str, tuple[float, float]] = {}
        self._last_seen = np.zeros(
            detector.config.num_services, np.float64
        )
        if self.keyspace_enable:
            self.tensorizer.new_key_gate = self.keyspace_newkey_gate
        self._exemplar_ring = int(exemplar_ring)
        self._hh_cand_max = int(hh_candidates)
        self._query_lock = threading.Lock()
        self._exemplars: dict[int, deque] = {}
        self._hh_cands: dict[int, deque] = {}
        self._anomaly_ring: deque = deque(maxlen=64)
        self.exemplars_captured = 0
        # Verdict provenance (runtime.provenance; knob registry:
        # utils.config.PROVENANCE_KNOBS): the evidence engine builds
        # one bundle per flagged service at flag time; the bundle RING
        # lives here beside the anomaly ring so it rides query_meta
        # replication and the read replica's /query/explain answers
        # bit-identically. The built counter follows the
        # exemplars_captured delta discipline (never restored).
        self._provenance = provenance
        self._explain_ring: deque = deque(maxlen=max(int(explain_ring), 1))
        self.explanations_built = 0
        # Bytes the flag-time evidence gathers moved to the host
        # (anomaly_provenance_fetch_bytes_total; delta discipline as
        # explanations_built).
        self.provenance_fetch_bytes = 0
        # Bundles awaiting OTLP log export, drained by the daemon's
        # export tick (bounded, drop-oldest — freshness over
        # completeness, the exporter queue's own discipline).
        self._explain_export: deque = deque(maxlen=max(int(explain_ring), 1))

    # -- ingestion -----------------------------------------------------

    def submit(self, records: Iterable[SpanRecord]) -> None:
        """Queue records; called from receiver/consumer threads."""
        records = list(records)
        if records:
            self.submit_columns(self.tensorizer.columns_from_records(records))

    def submit_columnar(self, columnar) -> None:
        """Queue a native-decoder batch (runtime.native.ColumnarSpans)."""
        self.submit_columns(self.tensorizer.columns_from_columnar(columnar))

    def submit_columns(self, cols: SpanColumns) -> None:
        if not cols.rows:
            return
        # Per-key liveness clock: a key is "seen" when rows ARRIVE for
        # it, before any shed/brownout thins them — idleness means the
        # world stopped sending, not that we dropped what it sent.
        # Duplicate ids in one scatter are benign (same timestamp) and
        # cross-thread races are too (both write "now"). Ids past the
        # table (synthetic columns that bypassed the tensorizer) clip
        # to the overflow slot, exactly like the device scatter does.
        self._last_seen[
            np.minimum(cols.svc, self._last_seen.shape[0] - 1)
        ] = time.monotonic()
        if self.tenant_quota_rows_s > 0:
            cols = self._tenant_quota_sample(cols)
            if not cols.rows:
                return
        level = self._brownout_level
        if level:
            cols = self._brownout_sample(cols, level)
            if not cols.rows:
                return
        with self._pending_lock:
            self._pending.append((cols, selftrace.clock()))
            self._pending_rows += cols.rows
            if self.queue_max_rows and self._pending_rows > self.queue_max_rows:
                self._shed_locked()
            rows = self._pending_rows
        self.wake.set()
        self._admission_update(rows)

    # -- bounded admission / brownout ----------------------------------

    def _tenant_quota_sample(self, cols: SpanColumns) -> SpanColumns:
        """Per-tenant admission quota (token bucket, 1 s burst).

        Runs AHEAD of the global row budget and the brownout ladder so
        one noisy tenant is clipped to its quota BEFORE it can push
        the shared queue toward saturation — the isolation is per
        tenant by construction (one bucket each), so a quiet tenant's
        rows are admitted untouched whatever its neighbors do. Error-
        lane rows always pass (the SHED_LANES discipline: incident
        evidence is never droppable telemetry). Shed rows land in
        ``stats.shed_rows_tenant[tenant]``, exported as
        anomaly_shed_rows_total{tenant=}.
        """
        quota = self.tenant_quota_rows_s
        now = time.monotonic()
        names = self.tensorizer.service_names
        svc = cols.svc
        ok = ~(cols.is_error > 0.0)
        # Group the batch's service ids by tenant (a tenant may own
        # several services; the bucket is per TENANT).
        by_tenant: dict[str, list[int]] = {}
        for sid in np.unique(svc):
            sid = int(sid)
            name = names[sid] if sid < len(names) else f"svc-{sid}"
            tenant = (
                self._tenant_of(name)
                if self._tenant_of is not None else "default"
            )
            by_tenant.setdefault(tenant, []).append(sid)
        drop = np.zeros(cols.rows, dtype=bool)
        with self._admission_lock:  # buckets are shared across
            # receiver threads; refill+consume is read-modify-write
            for tenant, sids in by_tenant.items():
                tokens, t_last = self._tenant_buckets.get(
                    tenant, (quota, now)
                )
                tokens = min(tokens + (now - t_last) * quota, quota)
                mask = np.isin(svc, np.asarray(sids, svc.dtype)) & ok
                n = int(mask.sum())
                allow = min(n, int(tokens))
                if allow < n:
                    # Keep the OLDEST rows within the quota (rows are
                    # enqueue-ordered): head-of-line fairness, and a
                    # deterministic choice two replicas agree on.
                    rank = np.cumsum(mask)
                    drop |= mask & (rank > allow)
                    shed = self.stats.shed_rows_tenant
                    shed[tenant] = shed.get(tenant, 0) + (n - allow)
                self._tenant_buckets[tenant] = (tokens - allow, now)
        if not drop.any():
            return cols
        return cols.compress(~drop)

    def _brownout_sample(self, cols: SpanColumns, level: int) -> SpanColumns:
        """Deterministic head sampling: keep 1/2^level of OK-lane rows.

        The keep decision hashes the trace key (splitmix64) rather than
        using its raw low bits — Kafka-order keys are ASCII order ids
        whose low byte is constant, and a sampler biased by encoding
        would black-hole a whole source instead of thinning it. Hashing
        makes the decision uniform AND deterministic: the same trace is
        kept or dropped at every level crossing (head sampling, so a
        kept trace stays internally consistent), and two replicas
        sampling the same stream agree. Error-lane rows always pass —
        brownout degrades the OK lane only.
        """
        mask = np.uint64((1 << level) - 1)
        keep = (cols.is_error > 0.0) | (
            (splitmix64_np(cols.trace_key) & mask) == np.uint64(0)
        )
        dropped = int(cols.rows - keep.sum())
        if dropped == 0:
            return cols
        with self._admission_lock:  # += races across receiver threads
            self.stats.brownout_rows += dropped
        return cols.compress(keep)

    def _shed_locked(self) -> None:
        """Drop the oldest OK-lane rows until the queue fits its budget.

        Called under ``_pending_lock``. Error-lane rows are NEVER shed
        (SHED_LANES): a chunk keeps its error rows (and its original
        enqueue clock — partially-shed chunks still report honest lag)
        even when every OK row around them is dropped. If error rows
        alone exceed the budget (a pathological all-error flood) the
        queue holds them anyway — the bound is a promise about
        droppable telemetry, not a license to lose incident evidence —
        and the depth gauge makes the excursion visible.
        """
        need = self._pending_rows - self.queue_max_rows
        idx = 0
        shed = 0
        while need > 0 and idx < len(self._pending):
            cols, t_enq = self._pending[idx]
            err = cols.is_error > 0.0
            n_ok = int(cols.rows - err.sum())
            if n_ok == 0:
                idx += 1  # pure error-lane chunk: untouchable
                continue
            if n_ok <= need:
                kept = cols.compress(err)
                dropped = n_ok
            else:
                # Drop only the oldest `need` OK rows of this chunk
                # (rows are enqueue-ordered within a chunk).
                ok_rank = np.cumsum(~err)
                kept = cols.compress(err | (ok_rank > need))
                dropped = need
            if kept.rows:
                self._pending[idx] = (kept, t_enq)
                idx += 1
            else:
                del self._pending[idx]
            self._pending_rows -= dropped
            need -= dropped
            shed += dropped
        if shed:
            self.stats.shed_rows["ok"] += shed

    def _admission_update(self, rows: int, now: float | None = None) -> None:
        """Watermark hysteresis + brownout ladder (host wall clock).

        Saturation flips at the high watermark and clears only at the
        low one. The ladder moves one level per ``brownout_hold_s`` of
        SUSTAINED saturation (transient spikes never engage it) and
        relaxes one level per hold of sustained clearance — the same
        hysteresis in both directions, so an operating point near the
        boundary oscillates the gauge, not the sampling rate.
        """
        if not self.queue_max_rows:
            return
        now = time.monotonic() if now is None else now
        with self._admission_lock:
            if not self._saturated:
                if rows >= self._high_rows:
                    self._saturated = True
                    self._sat_since = now
                    self.stats.saturation_events += 1
            elif rows <= self._low_rows:
                self._saturated = False
                self._unsat_since = now
            if self._saturated:
                if (
                    self._brownout_level < self.brownout_max_level
                    and now - max(self._sat_since, self._level_changed_at)
                    >= self.brownout_hold_s
                ):
                    self._brownout_level += 1
                    self._level_changed_at = now
            elif self._brownout_level and (
                now - max(self._unsat_since, self._level_changed_at)
                >= self.brownout_hold_s
            ):
                self._brownout_level -= 1
                self._level_changed_at = now

    @property
    def saturated(self) -> bool:
        """True between the high-watermark crossing and the low one —
        what the OTLP receivers consult before admitting a request."""
        return self._saturated

    @property
    def brownout_level(self) -> int:
        """Current head-sampling level (0 = keep everything; level L
        keeps 1/2^L of OK-lane rows)."""
        return self._brownout_level

    def keyspace_update(
        self, fill: float, rss_over: bool = False,
        now: float | None = None,
    ) -> int:
        """Keyspace pressure hysteresis + degradation ladder.

        Driven by the keyspace watchdog's tick (runtime/keyspace.py)
        with the live-row fill fraction and the RSS-budget verdict.
        Pressure flips at the high watermark (or any RSS breach) and
        clears only at the low one; the ladder moves one rung per
        ``keyspace_hold_s`` of SUSTAINED state in either direction —
        identical discipline to :meth:`_admission_update`, so one fill
        spike never staircases straight to the 429 rung. Returns the
        post-update level.
        """
        now = time.monotonic() if now is None else now
        with self._admission_lock:
            if not self._ks_saturated:
                if fill >= self.keyspace_high_watermark or rss_over:
                    self._ks_saturated = True
                    self._ks_sat_since = now
                    self.stats.keyspace_pressure_events += 1
            elif fill <= self.keyspace_low_watermark and not rss_over:
                self._ks_saturated = False
                self._ks_unsat_since = now
            if self._ks_saturated:
                if (
                    self._keyspace_level < KEYSPACE_MAX_LEVEL
                    and now - max(
                        self._ks_sat_since, self._ks_level_changed_at
                    ) >= self.keyspace_hold_s
                ):
                    self._keyspace_level += 1
                    self._ks_level_changed_at = now
            elif self._keyspace_level and (
                now - max(
                    self._ks_unsat_since, self._ks_level_changed_at
                ) >= self.keyspace_hold_s
            ):
                self._keyspace_level -= 1
                self._ks_level_changed_at = now
            return self._keyspace_level

    @property
    def keyspace_level(self) -> int:
        """Current keyspace ladder rung (0 = normal; see the
        KEYSPACE_LEVEL_* constants)."""
        return self._keyspace_level

    def keyspace_newkey_gate(self, name: str) -> bool:
        """NEW-key admission gate, consulted by the tensorizer under
        its intern lock on a genuine miss (existing keys never reach
        it). Below the throttle rung every new key gets a slot; at the
        throttle rung each TENANT spends a token bucket refilled at
        ``keyspace_newkey_rate`` new keys/s (a UUID-spraying tenant
        exhausts its own bucket while a quiet tenant's first sighting
        still interns); at the collapse rung and above every new key
        folds to overflow. Refusals are counted per tenant — the key's
        ROWS are still admitted, they just share the overflow bucket.
        """
        level = self._keyspace_level
        if level < KEYSPACE_LEVEL_THROTTLE:
            return True
        tenant = (
            self._tenant_of(name)
            if self._tenant_of is not None else "default"
        )
        if level >= KEYSPACE_LEVEL_COLLAPSE:
            with self._admission_lock:
                d = self.stats.overflow_keys_tenant
                d[tenant] = d.get(tenant, 0) + 1
            return False
        rate = self.keyspace_newkey_rate
        if rate <= 0:
            return True
        now = time.monotonic()
        with self._admission_lock:
            tokens, t_last = self._ks_newkey_buckets.get(
                tenant, (rate, now)
            )
            tokens = min(tokens + (now - t_last) * rate, rate)
            if tokens >= 1.0:
                self._ks_newkey_buckets[tenant] = (tokens - 1.0, now)
                return True
            self._ks_newkey_buckets[tenant] = (tokens, now)
            d = self.stats.newkey_throttled_tenant
            d[tenant] = d.get(tenant, 0) + 1
        return False

    def admission_retry_after(self) -> float | None:
        """None while admitting; a Retry-After hint (seconds) while
        saturated — the receivers' single admission-control question.
        The keyspace ladder's shed rung answers here too, so ALL
        ingest doors (Python OTLP, gRPC, native front door) return
        429/RESOURCE_EXHAUSTED under a sustained cardinality bomb
        without any door-side change."""
        if self._saturated:
            return self.retry_after_s
        if self._keyspace_level >= KEYSPACE_LEVEL_SHED:
            return self.keyspace_retry_after_s
        return None

    def pending_rows(self) -> int:
        with self._pending_lock:
            return self._pending_rows

    def pump(self, t_now: float | None = None) -> None:
        """Form at most one batch and dispatch it (non-blocking).

        Callers drive either wall time or a virtual clock; when ``t_now``
        is omitted, reuse the caller's last timebase rather than mixing
        ``time.monotonic()`` into a virtual-time stream (which would
        poison dt/window rotation for the rest of the run).
        """
        if t_now is None:
            t_now = self._last_t if self._last_t is not None else time.monotonic()
        self._last_t = t_now
        if not self.flags.evaluate(FLAG_ENABLED, True):
            with self._pending_lock:
                self.stats.dropped_disabled += self._pending_rows
                self._pending.clear()
                self._pending_rows = 0
            if self._spine is not None:
                # Staged-but-undispatched batches are pending work too:
                # the off switch drops them with the queue.
                self.stats.dropped_disabled += self._spine.discard_pending()
            self._admission_update(0)
            return
        # Assemble up to one batch of rows from the columnar queue;
        # an oversized head chunk is split and its tail re-queued.
        width = self._width if self.adaptive_batching else self.tensorizer.batch_size
        with self._pending_lock:
            rows_avail = self._pending_rows
        # The consumer side of the admission loop: draining below the
        # low watermark reopens the gate, and an idle/paced pump is
        # what ticks the brownout ladder's relaxation clock.
        self._admission_update(rows_avail)
        # The accumulate-hold scales with the growth factor (at 8× it
        # is 8×max_wait_s — exactly the regime where a report every
        # ~0.4 s beats skipping half of them) and engages ONLY once the
        # controller has escalated: at base width a hold would spend up
        # to max_wait_s of the <100 ms lag budget for nothing — the
        # batch that would have dispatched now is the same batch either
        # way, just later.
        hold_s = self.max_wait_s * (width / self.tensorizer.batch_size)
        if (
            self.adaptive_batching
            and width > self.tensorizer.batch_size  # escalated regime only
            and not self._harvest_flush  # drain() must always dispatch
            and 0 < rows_avail < width
            and time.monotonic() - self._last_dispatch < hold_s
        ):
            # Widened regime: hold sub-width dispatches briefly so the
            # batch fills — max_wait_s bounds the added latency, and a
            # quiet stream still flushes on the next pump past it.
            self._harvest_one(keep=0)
            return
        with self._pending_lock:
            budget = width
            parts: list[SpanColumns] = []
            t_oldest = None
            while self._pending and budget:
                head, t_enq = self._pending.popleft()
                if t_oldest is None:
                    t_oldest = t_enq  # FIFO: the head is the oldest
                if head.rows > budget:
                    parts.append(head.slice(0, budget))
                    # The requeued tail keeps its original enqueue time.
                    self._pending.appendleft(
                        (head.slice(budget, head.rows), t_enq)
                    )
                    budget = 0
                else:
                    parts.append(head)
                    budget -= head.rows
            self._pending_rows -= sum(p.rows for p in parts)
            rows_after = self._pending_rows
            t_take = selftrace.clock()
        # Re-check with the batch removed: a drain that just crossed
        # the low watermark must reopen the gate THIS pump, not next.
        self._admission_update(rows_after)
        if not parts:
            # Nothing new to assemble — but batches staged by an
            # earlier pump may be ready now (their puts rode behind the
            # previous step): dispatch them before the idle harvest.
            if self._spine is not None and self._dispatch_staged():
                pass
            else:
                # Nothing to dispatch — but an idle pump must still
                # fetch in-flight reports (outside the pending lock:
                # the fetch blocks for an RTT and submitters must
                # not): a report that only ever harvests on the NEXT
                # batch's pump carries one extra batch interval of
                # detection lag.
                self._harvest_one(keep=0)
                return
        else:
            cols = SpanColumns.concat(parts)
            self._capture_candidates(cols)
            # Batch-lifecycle sampling gate: one splitmix64 + compare
            # per batch; None rides the whole path for unsampled ones.
            trace = (
                self._selftrace.begin()
                if self._selftrace is not None else None
            )
            if self._spine is not None:
                # Spine path: hand the columns to the stager (pack +
                # async device put off the pump thread) and dispatch
                # from the ring: with the device idle, every staged
                # batch once its put is issued; with a step running,
                # those whose transfer already rode behind it.
                # Ring bound first: past `depth` undispatched batches
                # the pump wait-dispatches the head — the ring IS the
                # backpressure, and the pump is the only consumer.
                while self._spine.pending() >= self._spine.depth:
                    self._pump_spine(force_wait=True)
                self._spine.stage(
                    cols, width, t_now, t_oldest, t_take, trace=trace
                )
                self._dispatch_staged()
            else:
                batch = self.tensorizer.pack_columns(cols, width=width)
                self._dispatch_batch(
                    batch, t_now, t_oldest, t_take, cols, batch.num_valid,
                    trace=trace,
                )
        # Adaptive overlap: with more batches queued, leave the newest
        # dispatch in flight (device compute overlaps the fetch — the
        # throughput regime); with the queue drained, fetch everything
        # now (the low-rate regime, where a kept report would wait a
        # whole batch interval).
        with self._pending_lock:
            keep = 1 if self._pending else 0
        self._harvest_one(keep=keep)

    def _dispatch_batch(
        self, batch, t_now, t_oldest, t_take, cols, n_valid: int, trace=None
    ) -> None:
        """Dispatch ONE packed batch (host- or device-resident) into
        the donated step — the single place detector state advances
        from the pump path, always under ``_dispatch_lock``."""
        self._last_dispatch = time.monotonic()
        if self.history_capture is not None and cols is not None:
            self.history_capture(cols, t_now)
        with selftrace.span(
            SPAN_DISPATCH, observe=self.phase_observe, trace=trace
        ) as dispatch:
            # Packed dispatch: the report comes back as ONE device
            # vector so harvest is a single transfer instead of one per
            # report leaf.
            with self._dispatch_lock:
                report = self.detector.observe_packed(batch, t_now)  # async dispatch
            try:
                # Start the device→host copy now; by harvest time the
                # bytes are (mostly) on host and device_get degenerates
                # to a wait.
                report.copy_to_host_async()
            except AttributeError:  # non-jax.Array stand-ins in tests
                pass
        if self.phase_observe is not None:
            # The batch's two waits before dispatch, observed after it
            # so nothing between take and dispatch takes a lock.
            self.phase_observe(PHASE_QUEUE_WAIT, t_take - t_oldest)
            self.phase_observe(PHASE_RING_WAIT, dispatch.t0 - t_take)
        if trace is not None:
            trace.attrs.append(("batch.rows", str(int(n_valid))))
        self.stats.batches += 1
        self.stats.spans += n_valid
        with self._inflight_lock:
            # Lag clock = the oldest row's enqueue time, not dispatch
            # time: under the adaptive accumulate-hold rows can wait up
            # to hold_s before dispatch, and that wait IS detection lag.
            # The dispatch's end stamp starts the report wait, and the
            # cumulative dispatched rows credit the harvested counter.
            # The host-side columns ride along so the harvest can
            # capture exemplar trace ids AT FLAG TIME from the exact
            # batch that flagged (bounded: ≤3 batches in flight). The
            # batch's sampled self-trace (or None) rides too — the
            # harvest finishes it after the flag decision.
            self._inflight.append((
                t_now, t_oldest, report, cols, trace, dispatch.t1,
                self.stats.spans,
            ))
            # Bound the in-flight window: stale reports are dropped
            # unfetched (their batches already updated device state) so
            # readback RTT never throttles dispatch.
            while len(self._inflight) > 2:
                self._inflight.popleft()
                self.stats.reports_skipped += 1
                self._note_outcome(skipped=True)

    def _device_idle(self) -> bool:
        """True when no step is running on the device: nothing is in
        flight, or the newest in-flight report is done (``is_ready()``,
        a non-blocking check on a ``jax.Array``; steps finish in
        dispatch order, so the newest being done means all are). An
        unharvested report is no running step. A report stand-in
        without ``is_ready`` (host values in tests) reads as finished."""
        with self._inflight_lock:
            if not self._inflight:
                return True
            report = self._inflight[-1][2]
        is_ready = getattr(report, "is_ready", None)
        return is_ready is None or bool(is_ready())

    def _dispatch_staged(self) -> bool:
        """Dispatch staged batches, oldest first, by one answer of
        ``_device_idle`` asked before the first take: with the device
        idle, every batch in the ring; with a step running, each whose
        put is done. One take per pump would keep a batch left behind
        (by a pump that met a running step) a pump period in the ring,
        and every later one after it, while each pump also stages a
        batch, as a serve loop woken by every arrival does."""
        if not self._spine.pending():
            return False  # an empty ring asks the device nothing
        idle = self._device_idle()
        took = False
        while self._pump_spine(idle=idle):
            took = True
        return took

    def _pump_spine(
        self, force_wait: bool = False, idle: bool | None = None
    ) -> bool:
        """Dispatch the oldest staged batch if available (spine path).

        Overlap discipline, decided by asking the device whether a step
        is still running (``_device_idle``): with a step running the
        pump takes only a batch whose put has COMPLETED (a not-ready
        batch dispatches on a later pump — its transfer keeps riding
        behind the running step, which is the whole point); with the
        device idle, under drain, or at the ring bound it waits. So a
        batch staged with the device idle dispatches in the pump that
        staged it: it waits for its pack and put, never a whole pump
        interval."""
        if not self._spine.pending():
            return False  # an empty ring asks the device nothing
        if idle is None:
            idle = self._device_idle()
        must_wait = force_wait or self._harvest_flush or idle
        staged = self._spine.take(wait=must_wait, idle=idle)
        if staged is None:
            return False
        # n_valid from the host row count: the device batch's own
        # valid.sum() would force a device sync on the dispatch path.
        self._dispatch_batch(
            staged.batch,
            staged.t_now,
            staged.t_oldest,
            staged.t_take,
            staged.cols,
            staged.cols.rows,
            trace=staged.trace,
        )
        return True

    def drain(self) -> None:
        """Harvest all in-flight reports (end of stream / shutdown)."""
        # Raise the flush flag BEFORE pumping the backlog: the adaptive
        # hold must not park rows, and the spine must wait for every
        # staged put.
        self._harvest_flush = True
        try:
            while self._pending or (
                self._spine is not None and self._spine.pending()
            ):
                self.pump()
            while self._harvest_one(keep=0):
                pass
        finally:
            self._harvest_flush = False

    def close(self) -> None:
        """Drain, then stop the spine's stager (if any)."""
        self.drain()
        if self._spine is not None:
            self._spine.close()

    def spine_stats(self) -> dict | None:
        """The spine's put/overlap counters (None when the spine is
        off) — the daemon's anomaly_spine_* export reads this."""
        return None if self._spine is None else self._spine.stats()

    # -- adaptive width controller ------------------------------------

    @property
    def batch_width(self) -> int:
        """Current dispatch width (== batch_size unless adaptive grew it)."""
        return self._width if self.adaptive_batching else self.tensorizer.batch_size

    def ladder_widths(self) -> list[int]:
        """Every dispatch width the adaptive controller may pick
        (``[]`` when adaptive mode is off): batch_size doubling up to
        the growth cap — one compiled shape each."""
        if not self.adaptive_batching:
            return []
        widths = []
        width = self.tensorizer.batch_size
        while width <= self._max_width:
            widths.append(width)
            width *= 2
        return widths

    def warm_widths(self) -> None:
        """Precompile every ladder width (adaptive mode only).

        A width change is a new compiled shape; on TPU that is tens of
        seconds the first time — paid here, off the streaming path, not
        mid-incident when the controller escalates. The warm steps run
        on a COPY of the detector state through the same jitted
        callable (same compile cache as live dispatch), so live pumping
        is never blocked behind a compile and neither the state nor the
        window clock is touched."""
        for width in self.ladder_widths():
            self.warm_width(width)

    def warm_width(self, width: int) -> None:
        """Compile and run one ladder width on a throwaway state copy
        (see :meth:`warm_widths`); raises what the compiler raises."""
        import jax.numpy as jnp

        # All-invalid batch: every lane hits the kernels' monoid
        # identities — and the step consumes a throwaway state copy
        # (the jit donates its argument; donating the live state
        # would invalidate it under the pump thread).
        cols = SpanColumns(
            svc=np.zeros(0, np.int32),
            lat_us=np.zeros(0, np.float32),
            is_error=np.zeros(0, np.float32),
            trace_key=np.zeros(0, np.uint64),
            attr_crc=np.zeros(0, np.uint64),
        )
        batch = self.tensorizer.pack_columns(cols, width=width)
        # Snapshot under the dispatch lock: live dispatch DONATES
        # the state buffers (the jit deletes them Python-side the
        # moment it dispatches), so an unlocked tree_map(copy)
        # could read a just-deleted array mid-snapshot. The lock is
        # held only for the (async-dispatched) copies — never for
        # the compile below.
        with self._dispatch_lock:
            state_copy = jax.tree_util.tree_map(
                jnp.copy, self.detector.state
            )
        # Args mirror AnomalyDetector._args dtype-for-dtype (same
        # compile-cache key) but bypass the clock tick — warming
        # must not advance window rotation.
        _, report = self.detector._step_packed(
            state_copy,
            jnp.asarray(batch.svc),
            jnp.asarray(batch.lat_us),
            jnp.asarray(batch.is_error),
            jnp.asarray(batch.trace_hi),
            jnp.asarray(batch.trace_lo),
            jnp.asarray(batch.attr_hi),
            jnp.asarray(batch.attr_lo),
            jnp.asarray(batch.valid),
            jnp.float32(0.0),
            jnp.asarray((False,) * len(self.detector.config.windows_s)),
        )
        jax.device_get(report)  # force the compile + execute

    def warm_evidence(self) -> None:
        """Compile and run the flag-time evidence gather (see
        :meth:`_provenance_evidence`) on a state copy, so the first
        flag compiles nothing; raises what the compiler raises."""
        import jax.numpy as jnp

        with self._dispatch_lock:
            state_copy = jax.tree_util.tree_map(
                jnp.copy, self.detector.state
            )
        cols = np.zeros(
            (self.detector.config.cms_depth, self._evidence_k), np.int32
        )
        jax.device_get(
            self.detector.gather_evidence(0, cols, state=state_copy)
        )

    @property
    def _evidence_k(self) -> int:
        """Candidate columns per gather: the candidate cap, so every
        gather has one compiled shape."""
        return max(self._hh_cand_max, 1)

    def _note_outcome(self, skipped: bool) -> None:
        """Feed the width controller one report outcome.

        Escalation is jump-to-target: over a 4-outcome window,
        dispatched/harvested ≈ dispatch-rate/harvest-rate, and that
        ratio IS the width factor that balances the two — so one window
        at 3-skips-to-1 jumps straight to 4×, instead of doubling three
        times while reports keep dropping. Decay: two consecutive
        all-clean 8-outcome windows halve the width, returning report
        granularity once the pressure clears. Counters are
        lock-guarded (lock order: _inflight_lock → _adapt_lock, never
        the reverse)."""
        if not self.adaptive_batching:
            return
        with self._adapt_lock:
            self._adapt_events += 1
            if skipped:
                self._adapt_skips += 1
            window = 4 if self._adapt_skips else 8
            if self._adapt_events < window:
                return
            skips = self._adapt_skips
            events = self._adapt_events
            self._adapt_events = 0
            self._adapt_skips = 0
            if (
                skips == 0
                and self._adapt_clean_needed > 2
                and time.monotonic() - self._last_decay >= 10.0
            ):
                # The last decay survived its 10 s re-escalation window
                # (or pressure cleared long ago): earn the hysteresis
                # back down toward the initial requirement, so a
                # transient oscillation doesn't leave a long-running
                # daemon permanently width-elevated behind a 32-window
                # decay price.
                self._adapt_clean_needed = max(
                    self._adapt_clean_needed // 2, 2
                )
            if skips > events // 4:
                self._adapt_clean = 0
                if time.monotonic() - self._last_decay < 10.0:
                    # The decay we just made re-skipped: the clean
                    # width is the one ABOVE the boundary — make the
                    # next decay much harder to earn.
                    self._adapt_clean_needed = min(
                        self._adapt_clean_needed * 2, 32
                    )
                harvested = max(events - skips, 1)
                factor = max(2, -(-events // harvested))  # ceil div
                # Pow2 rounding keeps the width on the precompiled
                # ladder (same rule as the constructor cap).
                self._width = min(
                    self._width * _pow2_ceil(factor), self._max_width
                )
            elif skips == 0 and self._width > self.tensorizer.batch_size:
                self._adapt_clean += 1
                if self._adapt_clean >= self._adapt_clean_needed:
                    # Floor at base: the width must never leave the
                    # [batch_size, max] ladder.
                    self._width = max(
                        self._width // 2, self.tensorizer.batch_size
                    )
                    self._adapt_clean = 0
                    self._last_decay = time.monotonic()
            else:
                self._adapt_clean = 0

    # -- report handling ----------------------------------------------

    def _harvest_one(self, keep: int = 1) -> bool:
        """Synchronous harvest of the oldest in-flight report beyond
        ``keep`` (keep=1 leaves one dispatch in flight for overlap)."""
        with self._inflight_lock:
            if len(self._inflight) <= keep:
                return False
            item = self._inflight.popleft()
        self._process_report(item)
        return True

    # -- query-plane capture ------------------------------------------

    def _capture_candidates(self, cols: SpanColumns) -> None:
        """Remember recent per-service attribute keys (pump thread).

        The CMS absorbs every span but can never list its keys; a
        top-k query therefore needs candidates. Heavy hitters are, by
        definition, frequent — any attr with real share appears in the
        recent stream, so a bounded ring of recently-seen distinct
        CRCs per service IS the candidate set (counts stay exact: they
        come from the full table at query time)."""
        if not self._hh_cand_max:
            return
        svcs = np.unique(cols.svc)
        # The O(services × rows) mask/unique pass runs lock-free:
        # query_meta() and exemplar capture contend on _query_lock
        # every refresh/snapshot, so only the ring mutation may hold
        # it — not per-batch numpy work.
        tails = []
        for s in svcs:
            vals = cols.attr_crc[cols.svc == s]
            # Distinct values in ARRIVAL order (np.unique sorts by
            # value — slicing that would keep the numerically
            # largest CRCs forever, not the recent ones): sort the
            # first-appearance indices back into stream order,
            # then keep the tail.
            _u, first = np.unique(vals, return_index=True)
            ordered = vals[np.sort(first)]
            tails.append(
                (int(s), [int(v) for v in ordered[-self._hh_cand_max:]])
            )
        with self._query_lock:
            for s, tail in tails:
                ring = self._hh_cands.get(s)
                if ring is None:
                    ring = self._hh_cands[s] = deque(
                        maxlen=self._hh_cand_max
                    )
                ring.extend(tail)

    def _flag_candidates(self, keys: list[int]) -> dict[int, list[int]]:
        """Each flagged key's top-k candidates, most recent first: the
        ring the top-k query reads, fixed here so the evidence gather
        and the bundle see the same list."""
        with self._query_lock:
            return {
                i: list(dict.fromkeys(
                    reversed(self._hh_cands.get(i) or ())
                ))[: self._hh_cand_max]
                for i in keys
            }

    def _provenance_evidence(
        self, cands: dict[int, list[int]], trace=None
    ) -> dict[int, dict] | None:
        """Flag-time evidence for each flagged key: its EWMA/CUSUM
        rows, current HLL registers and the current CMS counts at its
        candidates' columns, gathered on the device
        (``AnomalyDetector.gather_evidence``). Pump thread. The gathers
        are dispatched under ``_dispatch_lock`` — the donation-race
        contract; no step runs between them, so every key reads the
        same state — and fetched after it is released: they are fresh
        buffers no step donates. A failed fetch costs the bundles
        their state block, never the report path."""
        cfg = self.detector.config
        k = self._evidence_k
        try:
            cols = {
                i: np.zeros((cfg.cms_depth, k), np.int32) for i in cands
            }
            for i, crcs in cands.items():
                if crcs:
                    cols[i][:, : len(crcs)] = candidate_columns(
                        i, crcs, cfg.cms_depth, cfg.cms_width
                    )
            with selftrace.span(
                SPAN_EVIDENCE, observe=self.phase_observe, trace=trace
            ):
                with self._dispatch_lock:
                    flats = {
                        i: self.detector.gather_evidence(i, c)
                        for i, c in cols.items()
                    }
                    for flat in flats.values():
                        flat.copy_to_host_async()
                host = {i: np.asarray(flat) for i, flat in flats.items()}
            self.provenance_fetch_bytes += sum(
                h.nbytes for h in host.values()
            )
            return {i: evidence_unpack(h, cfg, k) for i, h in host.items()}
        except Exception:  # noqa: BLE001 — evidence is advisory; the
            # report (and the anomaly event) must land regardless.
            return None

    def _capture_exemplars(
        self, t_batch, cols, report, flags_np, threshold,
        evidence: dict[int, dict] | None = None,
        cands: dict[int, list[int]] | None = None,
        trace_id: str | None = None,
    ) -> list[str]:
        """At flag time: link each flagged service to concrete trace
        ids from the batch that flagged it (pump thread, at harvest).

        The exemplar is the first 8 bytes of the OTLP trace id (the
        tensorizer's ``trace_key``, little-endian) rendered as hex —
        exactly the prefix a Jaeger UI search matches on. A flag whose
        evidence is windowed (CUSUM/cardinality, no row of the service
        in THIS batch) still records the anomaly event; the ring keeps
        the service's most recent exemplars from earlier batches.

        ``exemplar_ring=0`` disables only the trace-id capture (the
        privacy knob) — anomaly EVENTS still land in the ring, or
        /query/anomalies and the Grafana annotations would go dark.

        Returns every trace-id hex captured across the flagged
        services — the span links a sampled batch trace's flag span
        carries (runtime.selftrace)."""
        if not flags_np.any():
            return []
        captured: list[str] = []
        cusum_thr = np.asarray(
            self.detector.config.cusum_thresholds, np.float32
        )
        now = time.time()
        with self._query_lock:
            for i in np.nonzero(flags_np)[0]:
                i = int(i)
                # Signal names come from the runtime.provenance
                # REASON_* table (the provenance-vocabulary staticcheck
                # pass fences this set — bundles, anomaly events and
                # dashboards all speak it).
                signals = [
                    name
                    for name, z in (
                        (REASON_LATENCY, report.lat_z[i]),
                        (REASON_ERROR_RATE, report.err_z[i]),
                        (REASON_THROUGHPUT, report.rate_z[i]),
                        (REASON_CARDINALITY, report.card_z[i]),
                    )
                    if np.abs(z).max() > threshold
                ] + (
                    [REASON_CUSUM]
                    if (report.cusum[i] > cusum_thr).any()
                    else []
                )
                traces: list[str] = []
                if self._exemplar_ring and cols is not None:
                    keys = cols.trace_key[cols.svc == i]
                    for v in keys[-self._exemplar_ring:]:
                        traces.append(int(v).to_bytes(8, "little").hex())
                if self._exemplar_ring:
                    ring = self._exemplars.get(i)
                    if ring is None:
                        ring = self._exemplars[i] = deque(
                            maxlen=self._exemplar_ring
                        )
                    sig = signals[0] if signals else "flag"
                    for tid in traces:
                        ring.append(
                            {"trace_id": tid, "t": now, "signal": sig}
                        )
                self.exemplars_captured += len(traces)
                captured.extend(traces)
                bundle_ref = None
                if self._provenance is not None:
                    # Evidence bundle per flagged service, from the
                    # candidates its evidence was gathered for; seq is
                    # the detector step of that evidence, so the id is
                    # a pure function of replicated coordinates.
                    ev = evidence.get(i) if evidence else None
                    seq = (
                        int(ev["step_idx"])
                        if ev is not None
                        else self.stats.flag_events
                    )
                    names = self.tensorizer.service_names
                    bundle = self._provenance.build(
                        t_batch=float(t_batch),
                        seq=seq,
                        service=i,
                        label=(
                            names[i] if i < len(names) else f"svc-{i}"
                        ),
                        signals=signals,
                        exemplars=traces,
                        state=ev,
                        hh_candidates=(cands or {}).get(i, []),
                        trace_id=trace_id,
                    )
                    self._explain_ring.append(bundle)
                    self._explain_export.append(bundle)
                    self.explanations_built += 1
                    bundle_ref = bundle["id"]
                self._anomaly_ring.append({
                    "t": now,
                    "t_batch": float(t_batch),
                    "service": i,
                    "signals": signals,
                    "exemplars": traces,
                    "bundle": bundle_ref,
                })
        return captured

    def query_meta(self) -> dict:
        """JSON-able query-plane block: exemplar rings, recent anomaly
        events, and top-k candidate keys. Shipped inside the
        replication meta so a read replica answers exemplar/anomaly/
        top-k queries from the same data the primary would — the
        bit-consistency contract runtime.query is built on."""
        with self._query_lock:
            return {
                "exemplars": {
                    str(svc): [dict(e) for e in ring]
                    for svc, ring in self._exemplars.items()
                },
                "anomalies": [dict(ev) for ev in self._anomaly_ring],
                "hh_candidates": {
                    # Most-recent-first distinct CRCs (the ring keeps
                    # arrival order; dict.fromkeys dedups stably).
                    str(svc): list(
                        dict.fromkeys(reversed(ring))
                    )[: self._hh_cand_max]
                    for svc, ring in self._hh_cands.items()
                },
                "exemplars_captured": self.exemplars_captured,
                # Evidence bundles are built once (on the primary, at
                # flag time) and ride here verbatim — the replica's
                # /query/explain answers from the SAME dicts, which is
                # what makes the parity pin bit-identical.
                "explains": [dict(b) for b in self._explain_ring],
                "explanations_built": self.explanations_built,
            }

    def restore_query_meta(self, block: dict) -> None:
        """Promotion hydration: refill the query-plane rings from a
        replicated :meth:`query_meta` block, so exemplar/anomaly/top-k
        answers survive the role flip — the mirror is the ONLY copy a
        promoting standby has, and without this the history would
        vanish the moment its snapshot cache expires post-promotion.

        ``exemplars_captured`` is deliberately NOT restored: it backs
        this process's Prometheus counter delta, and importing the dead
        primary's lifetime total would spike the promoted daemon's
        ``anomaly_exemplars_captured_total`` by traffic it never saw."""
        if not block:
            return
        with self._query_lock:
            if self._exemplar_ring:
                for svc, events in (block.get("exemplars") or {}).items():
                    ring = self._exemplars.get(int(svc))
                    if ring is None:
                        ring = self._exemplars[int(svc)] = deque(
                            maxlen=self._exemplar_ring
                        )
                    ring.extend(
                        dict(e) for e in events[-self._exemplar_ring:]
                    )
            for ev in (block.get("anomalies") or [])[
                -self._anomaly_ring.maxlen:
            ]:
                self._anomaly_ring.append(dict(ev))
            if self._hh_cand_max:
                for svc, crcs in (
                    block.get("hh_candidates") or {}
                ).items():
                    ring = self._hh_cands.get(int(svc))
                    if ring is None:
                        ring = self._hh_cands[int(svc)] = deque(
                            maxlen=self._hh_cand_max
                        )
                    # query_meta lists most-recent-FIRST; the rings
                    # keep arrival order (most recent at the right).
                    ring.extend(int(c) for c in reversed(crcs))
            # Bundle ring: restored (the mirror is the only copy), but
            # explanations_built is NOT — it backs this process's
            # Prometheus counter delta, same rule as
            # exemplars_captured above.
            for b in (block.get("explains") or [])[
                -self._explain_ring.maxlen:
            ]:
                self._explain_ring.append(dict(b))

    def take_explain_exports(self) -> list[dict]:
        """Drain bundles awaiting OTLP log export (daemon export
        tick). Bounded drop-oldest upstream, so a stalled exporter
        never grows this queue."""
        with self._query_lock:
            out = list(self._explain_export)
            self._explain_export.clear()
        return out

    # -- report processing --------------------------------------------

    def _process_report(self, item) -> None:
        t_batch, t_oldest, dev_report, cols, trace, t_sent, rows = item
        self._note_outcome(skipped=False)
        # Single-array fetch + host-side unpack (see pump()).
        with selftrace.span(
            SPAN_HARVEST, observe=self.phase_observe, trace=trace
        ) as fetch:
            report = report_unpack(
                jax.device_get(dev_report), self.detector.config
            )
        # Oldest row's enqueue → fetched: the batch's detection lag,
        # tiled exactly by queue_wait + ring_wait + dispatch +
        # report_wait + harvest (one clock, shared stamps).
        lag_s = fetch.t1 - t_oldest
        self.stats.lag_ms.append(lag_s * 1e3)
        self.stats.spans_harvested = max(self.stats.spans_harvested, rows)
        if self.phase_observe is not None:
            self.phase_observe(PHASE_REPORT_WAIT, fetch.t0 - t_sent)
            # Submit→harvest lag is its own histogram (the detection-lag
            # SLO's distribution), distinct from the fetch cost above.
            self.phase_observe(PHASE_HARVEST_LAG, lag_s)
        flags_np = report.flags
        if self._provenance is not None:
            # Ring the head trajectories on EVERY harvested report —
            # already host numpy, so the K-window evidence history
            # costs an append, never a device round trip.
            self._provenance.observe_report(float(t_batch), report)
        threshold = float(
            self.flags.evaluate(FLAG_THRESHOLD, self.detector.config.z_threshold)
        )
        if threshold != self.detector.config.z_threshold:
            # Re-derive flags from raw z-scores at the flagd-driven
            # threshold — no recompile, the report carries the scores.
            # The CUSUM alarms keep their own (unchanged) threshold; the
            # flag only tunes the instantaneous-z sensitivity.
            z = np.maximum.reduce(
                [
                    np.abs(report.lat_z).max(axis=1),
                    np.abs(report.err_z).max(axis=1),
                    np.abs(report.rate_z).max(axis=1),
                    np.abs(report.card_z).max(axis=1),
                ]
            )
            cusum_thr = np.asarray(
                self.detector.config.cusum_thresholds, np.float32
            )
            cusum_alarm = (report.cusum > cusum_thr[None, :]).any(axis=1)
            flags_np = (z > threshold) | cusum_alarm
        if flags_np.any():
            with selftrace.span(
                SPAN_FLAG, observe=self.phase_observe, trace=trace
            ) as flag:
                self.stats.flag_events += 1
                names = self.tensorizer.service_names
                keys = [int(i) for i in np.nonzero(flags_np)[0]]
                flagged = [
                    names[i] if i < len(names) else f"svc-{i}"
                    for i in keys
                ]
                cands = evidence = None
                if self._provenance is not None:
                    cands = self._flag_candidates(keys)
                    evidence = self._provenance_evidence(cands, trace)
                # The flag span carries span LINKS to the exemplar shop
                # traces captured from THIS batch — a detector batch
                # trace in Jaeger jumps straight to the evidence.
                flag.links = tuple(self._capture_exemplars(
                    t_batch, cols, report, flags_np, threshold,
                    evidence=evidence, cands=cands,
                    trace_id=(
                        trace.trace_id.hex() if trace is not None else None
                    ),
                ))
                flag.attrs = (("flagged.services", ",".join(flagged)),)
        else:
            flagged = []
        try:
            if self.on_report is not None:
                with selftrace.span(
                    SPAN_EXPORT, observe=self.phase_observe, trace=trace
                ):
                    self.on_report(t_batch, report, flagged)
        finally:
            if trace is not None:
                try:
                    self._selftrace.finish(trace)
                except Exception:  # noqa: BLE001 — self-telemetry
                    # export must never fail the report path it
                    # observes: the trace is advisory, the report is
                    # the product.
                    pass
