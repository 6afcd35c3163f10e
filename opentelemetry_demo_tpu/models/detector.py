"""The streaming anomaly detector: multi-window sketch bank + z-score heads.

This is the framework's flagship model — the TPU answer to the question
the reference system leaves to humans staring at Grafana
(/root/reference/src/grafana/provisioning/dashboards/demo/demo-dashboard.json):
"which service just went weird, on which signal?". It consumes the span
stream the shop emits (Kafka ``orders`` + OTLP; SURVEY.md §3.2) and flags,
per service:

- **latency** anomalies — EWMA z-score on span duration at 3 timescales
  (catches paymentFailure / imageSlowLoad-style degradations),
- **error-rate** anomalies — EWMA z-score on status-error fraction
  (catches adFailure / productCatalogFailure-style fault flags),
- **throughput** anomalies — EWMA z-score on spans/sec
  (catches kafkaQueueProblems / loadGeneratorFloodHomepage floods),
- **cardinality** anomalies — EWMA z-score on HLL distinct-trace counts
  per tumbling window (catches session/id explosions),
- **heavy-hitter** attributes — CMS count ratio per window (catches one
  product id / user dominating traffic).

Everything lives in one ``DetectorState`` pytree and advances by one
jitted, state-donating ``step`` — compiled once, static shapes, no
data-dependent control flow (window rotation is a masked select, not a
branch). On a mesh the same step runs SPMD with the batch axis sharded;
sketch deltas merge with ``psum``/``pmax`` (see ``parallel``).
"""

from __future__ import annotations

from functools import partial, update_wrapper
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import cms, fused, hll
from ..ops.collectives import NO_COMM, Comm
from ..runtime.tensorize import TensorBatch
from .windows import WindowClock

# Heavy-hitter candidate cap (detector_step §3c): spans queried against
# the CMS per step. Past this, candidates come from a fixed-stride
# subsample — counts stay exact (full table), only the candidate set is
# sampled, and anything with ≥0.1% share is in a 16k sample w.p. ~1.
HH_QUERY_CAP = 16384


def hh_sample_indices(b_total: int, bq: int) -> np.ndarray:
    """Evenly-distributed candidate indices: ``(i·B)//BQ`` for i<BQ.

    Host-side int64 on purpose: both sizes are static, and an int32
    DEVICE product ``i·B`` overflows from i=4096 at B=512k — wrapping
    negative and silently unsampling the middle of the batch. The
    result covers [0, B) end to end (no unsampled tail) and is strictly
    increasing whenever BQ ≤ B.
    """
    return (
        np.arange(bq, dtype=np.int64) * b_total // bq
    ).astype(np.int32)


class DetectorConfig(NamedTuple):
    """Static shape/threshold configuration (closed over at jit time).

    Defaults size the state for the shop: ~20 services (padded to 32 with
    an overflow bucket), 1s/10s/60s windows matching BASELINE config #5.
    """

    num_services: int = 32
    hll_p: int = 12
    cms_depth: int = 4
    cms_width: int = 8192
    windows_s: tuple[float, ...] = (1.0, 10.0, 60.0)  # tumbling (HLL/CMS)
    taus_s: tuple[float, ...] = (1.0, 10.0, 60.0)  # EWMA timescales
    z_threshold: float = 6.0
    card_alpha: float = 0.3  # EWMA weight per completed window
    warmup_batches: float = 20.0  # CUSUM suppressed until this many obs
    # NOTE: new fields must append at the TUPLE END (after sketch_impl):
    # checkpoints persist this config positionally (runtime.checkpoint
    # saves list(config)), so a mid-tuple insertion silently shifts
    # every later field on restore of an older snapshot.
    # Instant z needs a believable σ estimate, and tails take ~3x more
    # samples to learn than means — so single-batch z-scores stay gated
    # longer than the (drift-protected) CUSUM accumulators.
    z_warmup_batches: float = 60.0
    warmup_windows: float = 5.0
    eps: float = 1e-6
    # Page's CUSUM on standardized scores: catches sustained small
    # shifts a single-batch z can't (sparse errors, gradual creep).
    cusum_k: float = 0.5  # per-batch drift toward zero
    cusum_h: float = 5.0  # alarm threshold (latency↑ / error↑ lanes)
    cusum_cap: float = 50.0  # bound accumulation (bounded recovery time)
    err_slack: float = 0.01  # tolerated error-rate above baseline
    # Batch→delta sketch implementation: None auto-selects (the fused
    # Pallas kernel on TPU, XLA scatters elsewhere); "xla" / "pallas" /
    # "interpret" force a path (see ops.fused).
    sketch_impl: str | None = None
    # The rate↓ CUSUM lane runs a HIGHER threshold than lat/err.
    # Measured (runtime.qualbench, 600 quiet batches at uniform load):
    # every false alarm came from the rate-down accumulator —
    # per-service counts are multinomial-noisy, and with S parallel
    # CUSUMs h=5's per-lane ARL0 (~1k batches) fires every few minutes.
    # h=8 zeroes the measured FP rate and real throughput collapses
    # still detect in <1 s (kafkaQueueProblems TTD 0.75 s). lat/err
    # keep h=5: their scores only integrate on batches where the
    # service appears, so at the shop's sparse checkout cadence a
    # higher bar lets the baseline EWMA adapt to the fault before the
    # accumulator alarms (measured: paymentFailure never flags within
    # 300 s at err-h=8). Appended at the tuple end — see the NOTE above.
    cusum_h_rate: float = 8.0

    @property
    def num_windows(self) -> int:
        return len(self.windows_s)

    @property
    def cusum_thresholds(self) -> tuple[float, float, float]:
        """Per-lane alarm thresholds in cusum column order
        {lat↑, err↑, rate↓} — the single source both the device flag
        computation and the pipeline's flagd re-derive path use."""
        return (self.cusum_h, self.cusum_h, self.cusum_h_rate)

    @property
    def num_taus(self) -> int:
        return len(self.taus_s)


class DetectorState(NamedTuple):
    """All detector memory; a donated pytree of static-shaped arrays.

    Axis glossary: W#=tumbling windows, S=services, R=HLL registers,
    D×C=CMS rows×counters, T=EWMA timescales. The ``[W#, 2, ...]`` banks
    hold {0: current, 1: previous} per window — a sliding window as two
    tumbling halves, rotated by masked select inside the step.
    """

    hll_bank: jnp.ndarray  # int32[W#, 2, S, R]
    cms_bank: jnp.ndarray  # int32[W#, 2, D, C]
    span_total: jnp.ndarray  # float32[W#, 2] — spans per window bank
    lat_mean: jnp.ndarray  # float32[S, T]
    lat_var: jnp.ndarray  # float32[S, T]
    err_mean: jnp.ndarray  # float32[S, T]
    rate_mean: jnp.ndarray  # float32[S, T]
    rate_var: jnp.ndarray  # float32[S, T]
    card_mean: jnp.ndarray  # float32[S, W#]
    card_var: jnp.ndarray  # float32[S, W#]
    obs_batches: jnp.ndarray  # float32[S] — batches seen per service
    obs_windows: jnp.ndarray  # float32[S, W#] — completed windows seen
    cusum: jnp.ndarray  # float32[S, 3] — {lat↑, err↑, rate↓} accumulators
    step_idx: jnp.ndarray  # int32[] — steps taken


class DetectorReport(NamedTuple):
    """Per-step detection output (small; cheap to fetch to host)."""

    lat_z: jnp.ndarray  # float32[S, T]
    err_z: jnp.ndarray  # float32[S, T]
    rate_z: jnp.ndarray  # float32[S, T]
    card_z: jnp.ndarray  # float32[S, W#]
    card_est: jnp.ndarray  # float32[S, W#] — completed-window distinct count
    hh_ratio: jnp.ndarray  # float32[S, W#] — max attr share of window traffic
    svc_count: jnp.ndarray  # float32[S] — valid spans this batch
    cusum: jnp.ndarray  # float32[S, 3] — {lat↑, err↑, rate↓} accumulators
    flags: jnp.ndarray  # bool[S] — any signal over threshold


# Shape of each DetectorReport field as a function of config. Keyed by
# field NAME and resolved through DetectorReport._fields, so adding a
# report field without a shape entry raises KeyError at first use
# instead of silently shifting every later field's slot in the packed
# vector.
_BOOL_REPORT_FIELDS = {"flags"}  # carried as f32 on the packed wire

_REPORT_FIELD_SHAPES = {
    "lat_z": lambda c: (c.num_services, c.num_taus),
    "err_z": lambda c: (c.num_services, c.num_taus),
    "rate_z": lambda c: (c.num_services, c.num_taus),
    "card_z": lambda c: (c.num_services, c.num_windows),
    "card_est": lambda c: (c.num_services, c.num_windows),
    "hh_ratio": lambda c: (c.num_services, c.num_windows),
    "svc_count": lambda c: (c.num_services,),
    "cusum": lambda c: (c.num_services, 3),
    "flags": lambda c: (c.num_services,),  # bool → f32 on the wire
}


def _report_shapes(config: "DetectorConfig") -> list[tuple[int, ...]]:
    """Field shapes of DetectorReport, in declaration order."""
    return [_REPORT_FIELD_SHAPES[name](config) for name in DetectorReport._fields]


def report_pack(report: DetectorReport) -> jnp.ndarray:
    """Flatten the report to ONE float32 vector inside jit.

    A pytree ``device_get`` pays one transfer per leaf; packing on
    device makes the harvest a single transfer (the difference matters
    most where per-transfer latency dominates bandwidth).
    :func:`report_unpack` restores the
    structure host-side. Fields are handled by NAME (bool fields via
    ``_BOOL_REPORT_FIELDS``) so field order/appends can't silently
    scramble the layout."""
    leaves = [
        getattr(report, name).astype(jnp.float32)
        if name in _BOOL_REPORT_FIELDS
        else getattr(report, name)
        for name in DetectorReport._fields
    ]
    return jnp.concatenate([leaf.reshape(-1) for leaf in leaves])


def report_unpack(flat, config: "DetectorConfig") -> DetectorReport:
    """Host-side inverse of :func:`report_pack` (numpy fields)."""
    flat = np.asarray(flat)
    fields = []
    pos = 0
    for name, shape in zip(DetectorReport._fields, _report_shapes(config)):
        n = int(np.prod(shape))
        leaf = flat[pos:pos + n].reshape(shape)
        if name in _BOOL_REPORT_FIELDS:
            leaf = leaf > 0.5
        fields.append(leaf)
        pos += n
    if pos != flat.size:
        raise ValueError(
            f"packed report length {flat.size} != expected {pos} "
            "(DetectorReport layout drifted from _REPORT_FIELD_SHAPES?)"
        )
    return DetectorReport(*fields)


def detector_step_packed(config: "DetectorConfig", state: DetectorState, *args):
    """detector_step with the report pre-packed for single-fetch harvest."""
    new_state, report = detector_step(config, state, *args)
    return new_state, report_pack(report)


# One key's flag-time evidence (the provenance bundle's state block),
# as (name, shape) in wire order: the key's EWMA/CUSUM rows, the
# current-slot span totals and step, the key's current HLL registers
# and the current CMS counts at its candidates' columns. Float leaves
# ride the int32 wire by bitcast (exact), so the fetch is one transfer.
_EVIDENCE_F32 = frozenset({
    "lat_mean", "lat_var", "err_mean", "rate_mean", "rate_var",
    "card_mean", "cusum", "span_total",
})


def _evidence_layout(config: "DetectorConfig", k: int):
    t, nw = config.num_taus, config.num_windows
    return (
        ("lat_mean", (t,)), ("lat_var", (t,)), ("err_mean", (t,)),
        ("rate_mean", (t,)), ("rate_var", (t,)), ("card_mean", (nw,)),
        ("cusum", (3,)), ("span_total", (nw,)), ("step_idx", ()),
        ("hll_regs", (nw, 1 << config.hll_p)),
        ("cms_cells", (nw, config.cms_depth, k)),
    )


def evidence_gather(
    config: "DetectorConfig",
    state: DetectorState,
    key: jnp.ndarray,  # int32[] — the flagged key's row
    cols: jnp.ndarray,  # int32[D, K] — CMS columns of its candidates
) -> jnp.ndarray:
    """One key's flag-time evidence as ONE int32 vector; jit WITHOUT
    donation (it reads the live state between steps).

    ``cols`` is padded to the pipeline's candidate cap, so there is one
    compiled shape per config. :func:`evidence_unpack` restores the
    fields host-side."""
    depth = cols.shape[0]
    leaves = {
        "lat_mean": state.lat_mean[key],
        "lat_var": state.lat_var[key],
        "err_mean": state.err_mean[key],
        "rate_mean": state.rate_mean[key],
        "rate_var": state.rate_var[key],
        "card_mean": state.card_mean[key],
        "cusum": state.cusum[key],
        "span_total": state.span_total[:, 0],
        "step_idx": state.step_idx,
        "hll_regs": state.hll_bank[:, 0, key],
        "cms_cells": state.cms_bank[:, 0][
            :, jnp.arange(depth)[:, None], cols
        ],
    }
    return jnp.concatenate([
        (
            jax.lax.bitcast_convert_type(leaves[name], jnp.int32)
            if name in _EVIDENCE_F32
            else leaves[name]
        ).reshape(-1)
        for name, _shape in _evidence_layout(config, cols.shape[1])
    ])


def evidence_unpack(flat, config: "DetectorConfig", k: int) -> dict:
    """Host-side inverse of :func:`evidence_gather` (numpy fields)."""
    flat = np.asarray(flat)
    out: dict = {}
    pos = 0
    for name, shape in _evidence_layout(config, k):
        n = int(np.prod(shape))
        leaf = flat[pos:pos + n]
        if name in _EVIDENCE_F32:
            leaf = leaf.view(np.float32)
        out[name] = leaf.reshape(shape)
        pos += n
    if pos != flat.size:
        raise ValueError(
            f"evidence length {flat.size} != expected {pos}"
        )
    return out


def detector_init(config: DetectorConfig) -> DetectorState:
    nw, s, t = config.num_windows, config.num_services, config.num_taus
    return DetectorState(
        hll_bank=hll.hll_init(s, p=config.hll_p, leading=(nw, 2)),
        cms_bank=cms.cms_init(config.cms_depth, config.cms_width, leading=(nw, 2)),
        span_total=jnp.zeros((nw, 2), jnp.float32),
        lat_mean=jnp.zeros((s, t), jnp.float32),
        lat_var=jnp.zeros((s, t), jnp.float32),
        err_mean=jnp.zeros((s, t), jnp.float32),
        rate_mean=jnp.zeros((s, t), jnp.float32),
        rate_var=jnp.zeros((s, t), jnp.float32),
        card_mean=jnp.zeros((s, nw), jnp.float32),
        card_var=jnp.zeros((s, nw), jnp.float32),
        obs_batches=jnp.zeros((s,), jnp.float32),
        obs_windows=jnp.zeros((s, nw), jnp.float32),
        cusum=jnp.zeros((s, 3), jnp.float32),
        step_idx=jnp.zeros((), jnp.int32),
    )


def detector_step(
    config: DetectorConfig,
    state: DetectorState,
    svc: jnp.ndarray,  # int32[B]
    lat_us: jnp.ndarray,  # float32[B]
    is_error: jnp.ndarray,  # float32[B]
    trace_hi: jnp.ndarray,  # uint32[B]
    trace_lo: jnp.ndarray,  # uint32[B]
    attr_hi: jnp.ndarray,  # uint32[B]
    attr_lo: jnp.ndarray,  # uint32[B]
    valid: jnp.ndarray,  # bool[B]
    dt: jnp.ndarray,  # float32[] — seconds since previous batch
    rotate: jnp.ndarray,  # bool[W#] — window boundary crossed
    comm: Comm = NO_COMM,
) -> tuple[DetectorState, DetectorReport]:
    """One fully-fused detector update; jit with ``donate_argnums=1``.

    Order of operations matters and is fixed:
    1. *Harvest* completed windows: estimate cardinality of each current
       bank, then feed the card EWMA only where ``rotate`` is set (each
       completed window is exactly one observation).
    2. *Rotate* banks by masked select (prev ← cur, cur ← 0). A
       ``lax.cond`` per window would recompile-friendly too, but a select
       keeps the whole step a single straight-line fused program.
    3. *Absorb* the batch into every current bank and the EWMA heads.

    SPMD: the same function runs per-shard inside ``shard_map`` with a
    real ``comm``. State arrays then hold this shard's slice (service
    axis of HLL/EWMA, depth axis of CMS); batch arrays hold the local
    batch shard; four collectives (psum/pmax over the batch axis, pmin
    over the sketch axis) reconcile the shards. Service/row ids are
    global on the wire and localised here via ``comm.sketch_index`` —
    out-of-slice ids fall off through scatter-drop and one-hot miss, so
    no gather/compaction is ever needed.
    """
    # Local shard geometry, derived from the state arrays themselves.
    s_axis = state.lat_mean.shape[0]  # local service count
    d_local = state.cms_bank.shape[-2]  # local CMS depth rows
    shard = comm.sketch_index()
    svc = svc.astype(jnp.int32) - shard * s_axis  # global → local ids
    # Out-of-slice ids must become *positive* out-of-bounds (scatter's
    # drop mode drops those; negative ids would wrap numpy-style and
    # alias another service's registers).
    svc = jnp.where((svc >= 0) & (svc < s_axis), svc, s_axis)
    valid_f = valid.astype(jnp.float32)

    # ---- 1. harvest cardinality of windows that just completed -------
    cur_est = hll.hll_estimate(state.hll_bank[:, 0])  # [W#, S]
    card_x = cur_est.T  # [S, W#]
    rot_row = rotate[None, :]  # [1, W#]
    card_obs = rot_row & (card_x > 0.5)
    card_warm = state.obs_windows < config.warmup_windows
    cm, cv = state.card_mean, state.card_var
    card_delta = card_x - cm
    # Variance floor covers HLL estimation noise (~1.6% std at p=12,
    # 5% floor) plus an absolute term for near-empty windows.
    card_z = card_delta / jnp.sqrt(cv + (0.05 * cm) ** 2 + 10.0)
    card_z = jnp.where(card_obs & ~card_warm, card_z, 0.0)
    a_card = jnp.maximum(
        jnp.float32(config.card_alpha), 1.0 / (state.obs_windows + 1.0)
    )
    card_mean = jnp.where(card_obs, cm + a_card * card_delta, cm)
    card_var = jnp.where(
        card_obs, (1.0 - a_card) * (cv + a_card * card_delta * card_delta), cv
    )
    obs_windows = state.obs_windows + card_obs.astype(jnp.float32)

    # ---- 2. rotate tumbling banks ------------------------------------
    def rot_bank(bank: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
        # new cur = 0, new prev = old cur, where mask; else unchanged.
        rolled = jnp.stack([jnp.zeros_like(bank[:, 0]), bank[:, 0]], axis=1)
        mask_b = mask.reshape((-1,) + (1,) * (bank.ndim - 1))
        return jnp.where(mask_b, rolled, bank)

    hll_bank = rot_bank(state.hll_bank, rotate)
    cms_bank = rot_bank(state.cms_bank, rotate)
    span_total = rot_bank(state.span_total, rotate)

    # ---- 3a. absorb batch into sketch banks --------------------------
    # The batch is first reduced to one mergeable *delta sketch* (max
    # HLL rank per cell, count per CMS counter, moment stats per
    # service) — the fused Pallas kernel on TPU, XLA scatters elsewhere
    # (ops.fused). Deltas, not banks, then cross the batch-axis
    # collectives (windows× less ICI traffic) and fan into every
    # tumbling window with one broadcast max/add.
    # The latency head works in log space: RPC latency is heavy-tailed
    # multiplicative (a single gamma draw can sit 6σ out in linear
    # space), while log-latency is near-gaussian and a k× degradation
    # is a clean +ln(k) shift at every timescale.
    log_lat = jnp.log1p(jnp.maximum(lat_us, 0.0))
    # CMS rows are hash-independent, so the sketch axis shards the
    # depth dimension; this shard updates its own row slice with the
    # matching global row hashes.
    cidx_full = cms.cms_indices(
        attr_hi, attr_lo, config.cms_depth, config.cms_width
    )
    cidx = jax.lax.dynamic_slice_in_dim(cidx_full, shard * d_local, d_local, 0)
    impl = fused.resolve_impl(
        config.sketch_impl, batch=int(svc.shape[0]),
        # Shard-LOCAL geometry: the kernel sweeps this shard's
        # cells (s_axis services, d_local CMS rows), and the rate
        # model must price what actually runs.
        cms_depth=int(cidx.shape[0]), cms_width=config.cms_width,
        num_services=s_axis, hll_p=config.hll_p,
    )
    # ---- 3b. count-aware detection heads (fused with 3a) -------------
    # The EWMA/CUSUM head math lives in fused.head_update (formulas
    # unchanged — see its docstring for the count-aware z rationale).
    # On the single-chip path it FOLDS into the one-pass
    # sketch_batch_update program, consuming the stats accumulator in
    # VMEM — no delta round trip between sketch fold and head advance;
    # the mesh path applies the same function to its collective-merged
    # stats (deltas, not banks, must cross the batch axis).
    heads = fused.HeadState(
        lat_mean=state.lat_mean,
        lat_var=state.lat_var,
        err_mean=state.err_mean,
        rate_mean=state.rate_mean,
        rate_var=state.rate_var,
        cusum=state.cusum,
        obs_batches=state.obs_batches,
    )
    head_kw = dict(
        taus_s=tuple(config.taus_s),
        warmup_batches=config.warmup_batches,
        z_warmup_batches=config.z_warmup_batches,
        cusum_k=config.cusum_k,
        cusum_cap=config.cusum_cap,
        err_slack=config.err_slack,
    )
    # step 0 carries a meaningless dt (the window clock has no previous
    # tick), and a count divided by it would poison λ forever.
    step_pos = state.step_idx > 0
    if comm is NO_COMM:
        # Single chip: the one-pass spine update — the batch folds into
        # EVERY current window bank AND the EWMA/CUSUM heads inside one
        # program instead of materializing a delta and merging it as a
        # second step (fused.sketch_batch_update; bit-identical by the
        # integer monoids and the shared head_update, pinned by
        # tests/test_fused.py). The mesh path below cannot take this
        # shortcut: per-shard deltas must cross the batch-axis
        # collectives before any bank merge or head advance.
        hll_new, cms_new, stats, new_heads, (lat_z, err_z, rate_z) = (
            fused.sketch_batch_update(
                hll_bank[:, 0],
                cms_bank[:, 0],
                svc,
                log_lat,
                is_error,
                trace_hi,
                trace_lo,
                cidx,
                valid,
                num_services=s_axis,
                hll_p=config.hll_p,
                cms_width=config.cms_width,
                impl=impl,
                heads=heads,
                dt=dt,
                step_pos=step_pos,
                **head_kw,
            )
        )
        hll_bank = hll_bank.at[:, 0].set(hll_new)
        cms_bank = cms_bank.at[:, 0].set(cms_new)
        n_valid = jnp.sum(valid_f)
    else:
        delta = fused.sketch_batch_delta(
            svc,
            log_lat,
            is_error,
            trace_hi,
            trace_lo,
            cidx,
            valid,
            num_services=s_axis,
            hll_p=config.hll_p,
            cms_width=config.cms_width,
            impl=impl,
        )
        hll_delta = comm.pmax_batch(delta.hll)
        cms_delta = comm.psum_batch(delta.cms)
        # Float merge: always direct (order-stable f32) — see
        # Comm.psum_batch_f32; only integer monoids ride the ring.
        stats = comm.psum_batch_f32(delta.stats)
        hll_bank = hll_bank.at[:, 0].set(
            jnp.maximum(hll_bank[:, 0], hll_delta[None])
        )
        cms_bank = cms_bank.at[:, 0].set(cms_bank[:, 0] + cms_delta[None])
        n_valid = comm.psum_batch_f32(jnp.sum(valid_f))
        new_heads, (lat_z, err_z, rate_z) = fused.head_update(
            stats, heads, dt, step_pos, **head_kw
        )
    span_total = span_total.at[:, 0].add(n_valid)
    cnt = stats[0]
    lat_mean, lat_var = new_heads.lat_mean, new_heads.lat_var
    err_mean = new_heads.err_mean
    rate_mean, rate_var = new_heads.rate_mean, new_heads.rate_var
    obs_batches = new_heads.obs_batches

    # ---- 3c. heavy hitters: attr share of each current window --------
    # CANDIDATE SAMPLING: the per-span CMS lookup is random-access
    # gathers — measured 14 ms of the 26 ms step at B=512k (TPU gathers
    # serialize; 6.3M of them across 3 windows). A heavy hitter is, by
    # definition, frequent: any attr holding share ρ of a service's
    # spans appears in a strided 16k sample with probability
    # 1-(1-ρ)^16384 (≥0.1% share ⇒ certainty for all practical
    # purposes), so spans beyond HH_QUERY_CAP contribute candidates via
    # a fixed-stride subsample. The COUNTS stay exact — they come from
    # the full CMS table, which absorbed every span; only the candidate
    # set is sampled. Below the cap nothing changes.
    b_total = svc.shape[0]
    bq = min(b_total, HH_QUERY_CAP)
    if bq < b_total:
        # Evenly-distributed sample indices over the WHOLE batch,
        # computed by hh_sample_indices: (i·B)//BQ in HOST int64 — a
        # floor-division stride would leave the batch tail permanently
        # unsampled whenever B is not a multiple of the cap, and an
        # int32 device product i·B wraps negative from i=4096 at
        # B=512k, silently unsampling the middle half of the batch.
        q_idx = jnp.asarray(hh_sample_indices(b_total, bq))
        q_svc = svc[q_idx]
        q_valid = valid_f[q_idx]
        q_cidx = cidx[:, q_idx]
    else:
        q_svc, q_valid, q_cidx = svc, valid_f, cidx
    # Row-sharded CMS query: min over local rows, then min across the
    # sketch axis; batch shards each score their own spans, max-merged.
    counts = comm.pmin_sketch(
        jax.vmap(cms.cms_query, in_axes=(0, None))(cms_bank[:, 0], q_cidx)
    ).astype(jnp.float32)  # [W#, BQ]
    # Per-service max, chunked over the CANDIDATE set (≤ the cap): a
    # single dense [W#, BQ, S] one-hot product could still materialise
    # tens of MB, and a scatter-max serializes on duplicate service ids
    # (a span batch is nothing but duplicates). The scan sweeps the
    # candidates in fixed chunks — each step's [W#, chunk, S]
    # intermediate is a few MB of dense VPU work — and max-accumulates.
    nw = counts.shape[0]
    b_q = bq  # candidate count, NOT the batch total
    chunk = min(b_q, 8192)
    masked = counts * q_valid[None, :]
    hh_svc = q_svc
    pad = (-b_q) % chunk  # static
    if pad:
        # Pad to a chunk multiple: padding lanes carry svc == s_axis
        # (all-zero one-hot row) and zero counts — max identities.
        masked = jnp.pad(masked, ((0, 0), (0, pad)))
        hh_svc = jnp.pad(hh_svc, (0, pad), constant_values=s_axis)
    if chunk == b_q + pad:
        col = jax.lax.broadcasted_iota(jnp.int32, (chunk, s_axis), 1)
        onehot = (col == hh_svc[:, None]).astype(jnp.float32)
        local_max = jnp.max(masked[:, :, None] * onehot[None, :, :], axis=1)
    else:
        n_chunks = (b_q + pad) // chunk

        def hh_chunk(acc, xs):
            cnt_c, svc_c = xs  # [W#, chunk], [chunk]
            col = jax.lax.broadcasted_iota(jnp.int32, (chunk, s_axis), 1)
            onehot = (col == svc_c[:, None]).astype(jnp.float32)
            m = jnp.max(cnt_c[:, :, None] * onehot[None, :, :], axis=1)
            return jnp.maximum(acc, m), None

        local_max, _ = jax.lax.scan(
            hh_chunk,
            jnp.zeros((nw, s_axis), jnp.float32),
            (
                masked.reshape(nw, n_chunks, chunk).transpose(1, 0, 2),
                hh_svc.reshape(n_chunks, chunk),
            ),
        )
    per_svc_max = comm.pmax_batch(local_max)  # [W#, S]
    hh_ratio = (per_svc_max / jnp.maximum(span_total[:, 0], 1.0)[:, None]).T

    # ---- CUSUM layer: sustained small shifts --------------------------
    # Advanced inside fused.head_update alongside the EWMA heads (the
    # scores standardize against the slowest-τ baseline; sparse
    # services HOLD their accumulators — see head_update's docstring).
    cusum = new_heads.cusum

    # ---- flags -------------------------------------------------------
    thr = config.z_threshold
    # Per-lane CUSUM thresholds: {lat↑, err↑, rate↓} — the rate lane
    # runs higher (see cusum_h_rate's rationale in DetectorConfig).
    cusum_thr = jnp.asarray(config.cusum_thresholds, jnp.float32)
    flags = (
        jnp.any(jnp.abs(lat_z) > thr, axis=1)
        | jnp.any(jnp.abs(err_z) > thr, axis=1)
        | jnp.any(jnp.abs(rate_z) > thr, axis=1)
        | jnp.any(jnp.abs(card_z) > thr, axis=1)
        | jnp.any(cusum > cusum_thr[None, :], axis=1)
    )

    new_state = DetectorState(
        hll_bank=hll_bank,
        cms_bank=cms_bank,
        span_total=span_total,
        lat_mean=lat_mean,
        lat_var=lat_var,
        err_mean=err_mean,
        rate_mean=rate_mean,
        rate_var=rate_var,
        card_mean=card_mean,
        card_var=card_var,
        obs_batches=obs_batches,
        obs_windows=obs_windows,
        cusum=cusum,
        step_idx=state.step_idx + 1,
    )
    report = DetectorReport(
        lat_z=lat_z,
        err_z=err_z,
        rate_z=rate_z,
        card_z=card_z,
        card_est=card_x,
        hh_ratio=hh_ratio,
        svc_count=cnt,
        cusum=cusum,
        flags=flags,
    )
    return new_state, report


class AnomalyDetector:
    """Host-side driver: owns state, the compiled step, and the clock.

    Usage::

        det = AnomalyDetector(DetectorConfig())
        report = det.observe(tensor_batch, t_now)   # t in seconds

    The jitted step donates the previous state buffer, so steady-state
    ingest allocates nothing on device beyond the incoming batch.
    """

    def __init__(self, config: DetectorConfig | None = None):
        self.config = config or DetectorConfig()
        self.state = detector_init(self.config)
        self.clock = WindowClock(self.config.windows_s)
        self._step = jax.jit(
            partial(detector_step, self.config), donate_argnums=0
        )
        self._step_packed = jax.jit(
            partial(detector_step_packed, self.config), donate_argnums=0
        )
        # Named, unlike the step's bare partial (jit__unknown on the
        # device): a trace that reads the step by that name must not
        # count the gather as a step.
        self._evidence = jax.jit(update_wrapper(
            partial(evidence_gather, self.config), evidence_gather
        ))

    def gather_evidence(
        self, key: int, cols: np.ndarray, state: DetectorState | None = None
    ) -> jnp.ndarray:
        """Dispatch one key's :func:`evidence_gather` on ``state`` (the
        live state by default); returns the device vector unfetched.
        The live state is donated by the next step, so a caller on
        another thread dispatches under the pipeline's dispatch lock."""
        return self._evidence(
            self.state if state is None else state,
            np.int32(key), np.asarray(cols, np.int32),
        )

    def _args(self, batch: TensorBatch, t_now: float):
        dt, rotate = self.clock.tick(t_now)
        return (
            jnp.asarray(batch.svc),
            jnp.asarray(batch.lat_us),
            jnp.asarray(batch.is_error),
            jnp.asarray(batch.trace_hi),
            jnp.asarray(batch.trace_lo),
            jnp.asarray(batch.attr_hi),
            jnp.asarray(batch.attr_lo),
            jnp.asarray(batch.valid),
            jnp.float32(dt),
            jnp.asarray(rotate),
        )

    def observe(self, batch: TensorBatch, t_now: float) -> DetectorReport:
        self.state, report = self._step(self.state, *self._args(batch, t_now))
        return report

    def observe_packed(self, batch: TensorBatch, t_now: float) -> jnp.ndarray:
        """Like :meth:`observe` but the report comes back as one flat
        device vector — the low-latency harvest path
        (:func:`report_unpack` restores the structure host-side)."""
        self.state, flat = self._step_packed(self.state, *self._args(batch, t_now))
        return flat

    def flagged_services(self, report: DetectorReport, names: list[str]) -> list[str]:
        mask = np.asarray(report.flags)
        return [n for i, n in enumerate(names) if i < mask.shape[0] and mask[i]]
