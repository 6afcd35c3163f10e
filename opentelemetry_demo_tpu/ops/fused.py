"""Fused batch→delta sketch op: one pass, three sketches, no scatters.

The hot loop of the detector absorbs a span batch into HLL registers
(scatter-max), a Count-Min table (scatter-add), and per-service moment
stats (segment sum). This module collapses all three into one
delta-producing program (BASELINE config #4, "fused HLL+CMS+EWMA Pallas
kernel"):

- The batch's effect on each sketch is first reduced to a **delta
  sketch**: ``hll_delta[S,R]`` (max rank per cell), ``cms_delta[D,W]``
  (count per counter), ``stats[4,S]`` (count / Σlog-lat / Σlog-lat² /
  Σerr per service). Deltas are tiny monoid elements: the caller merges
  them into every tumbling-window bank with one broadcast max/add, and
  on a mesh they — not the banks — ride the ICI collectives.
- Inside the Pallas kernel the "scatter" is a dense one-hot
  compare-reduction: for each tile of sketch cells, compare the batch's
  cell ids against a lane iota and max/sum over the batch axis. That is
  embarrassingly parallel VPU work with perfect lane utilisation —
  the TPU answer to what CUDA builds do with HBM atomics (SURVEY.md §7
  hard part (b)) — and the whole working set (delta tiles + batch
  vectors) stays VMEM-resident.
- The segment stats ride the MXU as a ``[4,B] @ [B,S]`` one-hot matmul.

An ``impl="xla"`` reference path (the scatter formulation built from
``ops.hll`` / ``ops.cms`` / ``ops.ewma``) defines the semantics; the
Pallas path is property-tested against it (interpret mode on CPU, native
on TPU). Honest fetch-synchronized timing single-chip (S=32, p=12,
4×8192 CMS; r5, see the calibration table above ``expected_rates``):
the dense kernel owns the small-batch low-latency regime through
B≈16k (5.8M vs ~2.3M full-step at 8192); the XLA path wins from ~24k
up (47M at 65536, 105M at 512k, 123M plateau at 2M) — its CMS count
rides the scatter-free histogram engines in ``cms.cms_update_hist``
(the transposed-int8 MXU outer-product Pallas kernel at
tile-divisible geometries, sort+searchsorted elsewhere; TPU scatters
serialize on duplicate indices, and a CMS batch is nothing but
duplicates). ``resolve_impl`` auto-selects by batch size; the HYBRID
is deliberate — the dense formulation's O(B·cells) sweep is a ceiling
no layout removes (see the bound argument in the calibration comment
and PARITY.md), so BASELINE config #4's "fused kernel" answer at
large B is the histogram formulation, whose hot engine is itself a
Pallas kernel. The dense kernel's further wins are determinism (fixed
VPU/MXU schedule, no batch-order dependence) and keeping the whole
delta VMEM-resident.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import cms, ewma, hll

# Stable names in device traces and HLO metadata, so a trace reduction
# finds the step's parts after a refactor: the two Pallas kernels, and
# the named scopes of the XLA path's HLL, CMS and head phases.
KERNEL_DELTA = "sketch_delta"
KERNEL_UPDATE = "sketch_update"
SCOPE_HLL = "hll"
SCOPE_CMS = "cms"
SCOPE_HEAD = "head"


class SketchDelta(NamedTuple):
    """One batch's mergeable effect on the sketch bank."""

    hll: jnp.ndarray  # int32[S, R] — max HLL rank per (service, bucket)
    cms: jnp.ndarray  # int32[D, W] — count per CMS counter
    stats: jnp.ndarray  # float32[4, S] — cnt, Σlog-lat, Σlog-lat², Σerr


class HeadState(NamedTuple):
    """The EWMA/CUSUM detection-head memory one batch advances — the
    slice of ``models.detector.DetectorState`` the fused one-pass
    update owns when the head fold is enabled (NO_COMM path)."""

    lat_mean: jnp.ndarray  # float32[S, T]
    lat_var: jnp.ndarray  # float32[S, T]
    err_mean: jnp.ndarray  # float32[S, T]
    rate_mean: jnp.ndarray  # float32[S, T]
    rate_var: jnp.ndarray  # float32[S, T]
    cusum: jnp.ndarray  # float32[S, 3] — {lat↑, err↑, rate↓}
    obs_batches: jnp.ndarray  # float32[S]


def head_update(
    stats: jnp.ndarray,  # float32[4, S] — cnt, Σlog-lat, Σlog-lat², Σerr
    heads: HeadState,
    dt: jnp.ndarray,  # float32[] — seconds since previous batch
    step_pos: jnp.ndarray,  # bool[] — True past step 0 (rate gate)
    *,
    taus_s: tuple,
    warmup_batches: float,
    z_warmup_batches: float,
    cusum_k: float,
    cusum_cap: float,
    err_slack: float,
) -> tuple[HeadState, tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]]:
    """One batch's EWMA/CUSUM head advance: ``(heads', (lat_z, err_z,
    rate_z))`` — the 3b/CUSUM math of ``models.detector.detector_step``,
    hoisted HERE so the NO_COMM spine folds it into the one-pass
    ``sketch_batch_update`` program (the last delta→HBM round trip PR 9
    left) while the mesh path applies the SAME function to its
    collective-merged stats. Formulas are verbatim from the detector
    step — bit-identical by construction; tests/test_fused.py pins the
    folded path against this two-step form.

    Count-aware scaling (why each z looks the way it does):
    latency x̄ of n spans → z=(x̄-μ)/sqrt(σ²/n); error binomial;
    throughput Poisson with empirically learned burstiness — see the
    detector-step docstring for the full rationale.
    """
    # Per-τ smoothing weights, built from traced SCALARS (a
    # jnp.asarray(taus_s) constant would be a captured const inside
    # the Pallas kernel); elementwise-identical to 1-exp(-dt/taus).
    alphas = jnp.stack(
        [1.0 - jnp.exp(-dt / jnp.float32(t)) for t in taus_s]
    )  # [T]
    cnt, lat_sum, lat_sumsq, err_sum = stats
    # Column views are taken of FLOAT vectors and compared afterwards:
    # the v5e Pallas lowering refuses a bool [S] → [S,1] reshape.
    obs_col = heads.obs_batches[:, None]  # [S,1]
    obs2d = cnt[:, None] > 0  # [S,1]
    warm = obs_col < warmup_batches  # [S,1]
    z_warm = obs_col < z_warmup_batches  # [S,1]
    n = jnp.maximum(cnt, 1.0)[:, None]  # [S,1]
    # Bias-corrected smoothing (Adam-style debias via max, not divide).
    alphas = jnp.maximum(alphas, 1.0 / (obs_col + 1.0))  # [S,T]
    # Variance gets its own (slow) smoothing — the per-span variance is
    # a property of the service, not of the detection timescale.
    alpha_var = jnp.maximum(
        1.0 - jnp.exp(-dt / jnp.float32(max(taus_s))),
        1.0 / (obs_col + 1.0),
    )  # [S,1]

    # Latency: per-span mean μ and per-span variance σ² per timescale,
    # with a σ floor (≈15% latency noise in log space).
    mu = heads.lat_mean
    sigma2 = heads.lat_var
    floor2 = jnp.float32(0.15 * 0.15)
    xbar = (lat_sum / jnp.maximum(cnt, 1.0))[:, None]  # [S,1]
    lat_z = (xbar - mu) / jnp.sqrt(sigma2 / n + floor2)
    lat_z_cusum = jnp.where(obs2d & ~warm, lat_z, 0.0)
    lat_z = jnp.where(obs2d & ~z_warm, lat_z, 0.0)
    lat_mean = jnp.where(obs2d, mu + alphas * (xbar - mu), mu)
    # E[(x-μ)²] against the *updated* mean.
    v_obs = (
        (lat_sumsq / jnp.maximum(cnt, 1.0))[:, None]
        - 2.0 * lat_mean * xbar
        + lat_mean * lat_mean
    )
    lat_var = jnp.where(
        obs2d, sigma2 + alpha_var * (jnp.maximum(v_obs, 0.0) - sigma2), sigma2
    )

    # Error rate: EWMA of p, binomial z on this batch's error count.
    p = heads.err_mean
    err_cnt = err_sum[:, None]  # [S,1]
    err_z = (err_cnt - n * p) / jnp.sqrt(n * p * (1.0 - p) + 1.0)
    err_z = jnp.where(obs2d & ~z_warm, err_z, 0.0)
    phat = err_cnt / n
    err_mean = jnp.where(obs2d, p + alphas * (phat - p), p)

    # Throughput: EWMA of spans/sec; Poisson-floored variance with the
    # learned burstiness. step 0 carries a meaningless dt — gated out.
    lam = heads.rate_mean
    dt_c = jnp.maximum(dt, 1e-3)
    expected = lam * dt_c
    emp_var = heads.rate_var * dt_c * dt_c  # (spans/s)² → count²
    rate_obs = (obs2d | (obs_col > 0)) & step_pos
    rate_z = (cnt[:, None] - expected) / jnp.sqrt(
        jnp.maximum(expected, emp_var) + 1.0
    )
    rate_z_cusum = jnp.where(rate_obs & ~warm, rate_z, 0.0)
    rate_z = jnp.where(rate_obs & ~z_warm, rate_z, 0.0)
    rate_x = (cnt / jnp.maximum(dt, 1e-3))[:, None]
    rate_mean = jnp.where(rate_obs, lam + alphas * (rate_x - lam), lam)
    rate_var = jnp.where(
        rate_obs,
        heads.rate_var + alpha_var * ((rate_x - lam) ** 2 - heads.rate_var),
        heads.rate_var,
    )

    obs_batches = heads.obs_batches + (cnt > 0).astype(jnp.float32)

    # CUSUM layer: sustained small shifts, standardized scores against
    # the slowest-τ baseline; sparse services HOLD their accumulators.
    # Keep-dims [S,1] slices throughout: the v5e Pallas lowering refuses
    # a scalar index on a non-leading axis (``x[:, -1]`` lowers to a
    # dynamic_slice it does not implement); ``x[:, -1:]`` is a static
    # slice it accepts, and the elementwise math is unchanged.
    k = jnp.float32(cusum_k)
    active = obs2d & ~warm  # [S,1]
    s_lat = jnp.where(active, lat_z_cusum[:, -1:] - k, 0.0)
    p_ref = err_mean[:, -1:]
    err_sigma = jnp.sqrt(n * p_ref * (1.0 - p_ref) + 1.0)
    s_err = jnp.where(
        active,
        (err_cnt - n * (p_ref + err_slack)) / err_sigma - k,
        0.0,
    )
    s_rate = jnp.where(rate_obs & ~warm, -rate_z_cusum[:, -1:] - k, 0.0)
    scores = jnp.concatenate([s_lat, s_err, s_rate], axis=1)  # [S,3]
    cusum = jnp.clip(heads.cusum + scores, 0.0, cusum_cap)

    new_heads = HeadState(
        lat_mean=lat_mean,
        lat_var=lat_var,
        err_mean=err_mean,
        rate_mean=rate_mean,
        rate_var=rate_var,
        cusum=cusum,
        obs_batches=obs_batches,
    )
    return new_heads, (lat_z, err_z, rate_z)


def _cell_chunk(total_cells: int, batch: int, wide: bool = False) -> int:
    """Lane-chunk size: biggest power-of-two tile dividing the cell count.

    Two regimes, measured on v5e-1 (S=32, p=12, 4×8192 CMS):

    - ``wide`` (multi-tile grids, large B): chunks up to 2048 lanes.
      The kernel's cost is pure compare-reduce sweeps (O(B·cells)
      total), so its throughput is set by how much of each sweep runs
      per loop iteration — C=128 leaves ~1300 tiny sequential
      fori_loop steps per grid step and 1.7M spans/s; C=2048 cuts the
      loop overhead ~16× and reaches 7.6M spans/s (the dense-compare
      VPU roofline for this geometry), for [TB, 2048] int32 compare
      intermediates of 32 MiB inside the raised VMEM grant.
    - narrow (single-tile grids, small B — the low-latency pipeline
      regime): big chunks measurably HURT (1.67M → 1.02M at B=2048);
      without grid pipelining the wide intermediates only add VMEM
      pressure. Keep the [B, chunk] intermediate ≲4 MiB.
    """
    if wide:
        cap = max(128, (1 << 24) // max(batch, 1))
        limit = 2048
    else:
        cap = max(128, (1 << 20) // max(batch, 1))
        limit = 512
    c = 128
    while c * 2 <= min(limit, cap) and total_cells % (c * 2) == 0:
        c *= 2
    if total_cells % c:
        raise ValueError(f"cell count {total_cells} not divisible by {c}")
    return c


def _delta_kernel(
    flat_ref,  # int32[TB, 1] — svc*R + bucket (rank 0 ⇒ no-op)
    rank_ref,  # int32[TB, 1] — HLL rank, 0 for masked lanes
    cidx_ref,  # int32[TB, D] — CMS row indices
    weight_ref,  # int32[TB, 1] — CMS increment (0 for masked lanes)
    svc_ref,  # int32[TB, 1] — local service id, >=S for masked lanes
    feats_ref,  # float32[4, TB] — premasked [1, loglat, loglat², err]
    hll_ref,  # out int32[SR/C, C] — same block every grid step
    cms_ref,  # out int32[D, W] — same block every grid step
    stats_ref,  # out float32[4, S] — same block every grid step
    *,
    wide: bool,  # multi-tile grid → wide cell chunks (see _cell_chunk)
):
    """One grid step absorbs one batch tile into the delta.

    The grid runs sequentially over batch tiles (TPU grids iterate in
    order), each step revisiting the SAME output block: the first step
    initialises, later steps max/sum-accumulate. This keeps only a
    [TB, chunk] compare intermediate in VMEM regardless of total B —
    the scoped-VMEM ceiling that capped the single-block kernel at
    B=16384 no longer binds."""
    b = flat_ref.shape[0]
    n_hll, c_hll = hll_ref.shape
    d, w = cms_ref.shape
    s = stats_ref.shape[1]
    first = pl.program_id(0) == 0
    flat = flat_ref[:]  # [TB, 1]
    rank = rank_ref[:]

    # HLL delta: per cell tile, max rank over the batch where the flat
    # (service, bucket) id hits the lane's cell id.
    def hll_body(i, _):
        cell = i * c_hll + jax.lax.broadcasted_iota(jnp.int32, (1, c_hll), 1)
        contrib = jnp.where(flat == cell, rank, 0)  # [TB, C]
        tile_max = jnp.max(contrib, axis=0, keepdims=True)
        prev = jnp.where(first, 0, hll_ref[pl.ds(i, 1), :])
        hll_ref[pl.ds(i, 1), :] = jnp.maximum(prev, tile_max)
        return 0

    jax.lax.fori_loop(0, n_hll, hll_body, 0)

    # CMS delta: per row and cell tile, sum weights over the batch where
    # the row hash hits the lane's counter id.
    weight = weight_ref[:]  # [TB, 1] int32
    # 2*b: the grid pipeline double-buffers blocks, so budget the
    # [TB, chunk] intermediates as if two tiles were resident.
    c_cms = _cell_chunk(w, 2 * b, wide=wide)
    for di in range(d):  # depth is small and static — unrolled
        col = cidx_ref[:, pl.ds(di, 1)]  # [TB, 1]

        def cms_body(i, _, col=col, di=di):
            cell = i * c_cms + jax.lax.broadcasted_iota(
                jnp.int32, (1, c_cms), 1
            )
            contrib = jnp.where(col == cell, weight, 0)  # [TB, C]
            tile_sum = jnp.sum(contrib, axis=0, keepdims=True)
            prev = jnp.where(
                first, 0, cms_ref[pl.ds(di, 1), pl.ds(i * c_cms, c_cms)]
            )
            cms_ref[pl.ds(di, 1), pl.ds(i * c_cms, c_cms)] = prev + tile_sum
            return 0

        jax.lax.fori_loop(0, w // c_cms, cms_body, 0)

    # Segment stats: one-hot matmul on the MXU.
    cols = jax.lax.broadcasted_iota(jnp.int32, (b, s), 1)
    onehot = (cols == svc_ref[:]).astype(jnp.float32)  # [TB, S]
    tile_stats = jnp.dot(
        feats_ref[:], onehot, preferred_element_type=jnp.float32
    )
    prev = jnp.where(first, 0.0, stats_ref[:])
    stats_ref[:] = prev + tile_stats


def _update_kernel(
    *refs,
    wide: bool,
    n_windows: int,
    fold_heads: bool = False,
    head_statics: dict | None = None,
):
    """One grid step absorbs one batch tile DIRECTLY into every window
    bank — the single-pass form of :func:`_delta_kernel`.

    The delta kernel materializes a [S,R]/[D,W] delta that the caller
    then broadcast-merges into each of the W tumbling banks: one extra
    HBM round trip for the delta plus a separate merge computation. Here
    the accumulation is seeded from the INCOMING banks (first grid step)
    instead of zero, and each cell tile's batch contribution — computed
    once — is folded into all W banks while it is still VMEM-resident.
    Integer max/add monoids make this bit-identical to delta-then-merge.
    Only the single-chip path may use it: on a mesh the DELTA (not the
    merged bank) must cross the batch-axis collectives.

    Positional refs (``fold_heads=False``)::

        flat[TB,1] rank[TB,1] cidx[TB,D] weight[TB,1] svc[TB,1]
        feats[4,TB] hll_in cms_in → hll_out cms_out stats[4,S]

    With ``fold_heads=True`` the EWMA/CUSUM head state rides along —
    inputs gain ``lat_mean/lat_var/err_mean/rate_mean/rate_var[S,T]
    cusum[S,3] obs[1,S] params[1,2]`` (params = dt, step_pos) and
    outputs gain the advanced heads plus ``lat_z/err_z/rate_z[S,T]``.
    The head math (:func:`head_update`, shared verbatim with the xla
    impl and the mesh path) runs ONCE, on the LAST grid step, consuming
    the fully-accumulated stats straight from VMEM — the stats delta
    never round-trips to HBM between sketch fold and head advance,
    which is what makes the NO_COMM path truly one program.
    """
    if fold_heads:
        (flat_ref, rank_ref, cidx_ref, weight_ref, svc_ref, feats_ref,
         hll_in_ref, cms_in_ref, lat_mean_ref, lat_var_ref, err_mean_ref,
         rate_mean_ref, rate_var_ref, cusum_ref, obs_ref, params_ref,
         hll_ref, cms_ref, stats_ref, lat_mean_o, lat_var_o, err_mean_o,
         rate_mean_o, rate_var_o, cusum_o, obs_o, lat_z_o, err_z_o,
         rate_z_o) = refs
    else:
        (flat_ref, rank_ref, cidx_ref, weight_ref, svc_ref, feats_ref,
         hll_in_ref, cms_in_ref, hll_ref, cms_ref, stats_ref) = refs
    b = flat_ref.shape[0]
    rows_hll, c_hll = hll_ref.shape
    n_hll = rows_hll // n_windows
    rows_cms, w = cms_ref.shape
    d = rows_cms // n_windows
    s = stats_ref.shape[1]
    first = pl.program_id(0) == 0
    flat = flat_ref[:]  # [TB, 1]
    rank = rank_ref[:]

    # HLL: per cell tile, max rank over the batch — folded into every
    # window's bank row (the windows are row-stacked, stride n_hll).
    def hll_body(i, _):
        cell = i * c_hll + jax.lax.broadcasted_iota(jnp.int32, (1, c_hll), 1)
        contrib = jnp.where(flat == cell, rank, 0)  # [TB, C]
        tile_max = jnp.max(contrib, axis=0, keepdims=True)
        for wi in range(n_windows):
            row = wi * n_hll + i
            prev = jnp.where(
                first,
                hll_in_ref[pl.ds(row, 1), :],
                hll_ref[pl.ds(row, 1), :],
            )
            hll_ref[pl.ds(row, 1), :] = jnp.maximum(prev, tile_max)
        return 0

    jax.lax.fori_loop(0, n_hll, hll_body, 0)

    # CMS: per row and cell tile, sum weights over the batch — added
    # into every window's matching bank row.
    weight = weight_ref[:]  # [TB, 1] int32
    c_cms = _cell_chunk(w, 2 * b, wide=wide)
    for di in range(d):  # depth is small and static — unrolled
        col = cidx_ref[:, pl.ds(di, 1)]  # [TB, 1]

        def cms_body(i, _, col=col, di=di):
            cell = i * c_cms + jax.lax.broadcasted_iota(
                jnp.int32, (1, c_cms), 1
            )
            contrib = jnp.where(col == cell, weight, 0)  # [TB, C]
            tile_sum = jnp.sum(contrib, axis=0, keepdims=True)
            for wi in range(n_windows):
                row = wi * d + di
                prev = jnp.where(
                    first,
                    cms_in_ref[pl.ds(row, 1), pl.ds(i * c_cms, c_cms)],
                    cms_ref[pl.ds(row, 1), pl.ds(i * c_cms, c_cms)],
                )
                cms_ref[pl.ds(row, 1), pl.ds(i * c_cms, c_cms)] = (
                    prev + tile_sum
                )
            return 0

        jax.lax.fori_loop(0, w // c_cms, cms_body, 0)

    # Segment stats: one-hot matmul on the MXU (identical to the delta
    # kernel — stats feed the EWMA fold, which is not window-banked).
    cols = jax.lax.broadcasted_iota(jnp.int32, (b, s), 1)
    onehot = (cols == svc_ref[:]).astype(jnp.float32)  # [TB, S]
    tile_stats = jnp.dot(
        feats_ref[:], onehot, preferred_element_type=jnp.float32
    )
    new_stats = jnp.where(first, 0.0, stats_ref[:]) + tile_stats
    stats_ref[:] = new_stats

    if fold_heads:
        # EWMA/CUSUM head advance, ONCE, on the last grid step — the
        # accumulated stats are consumed from VMEM (new_stats), never
        # re-read from HBM. Same head_update the xla impl and the mesh
        # path run, so every impl is bit-identical by shared code.
        @pl.when(pl.program_id(0) == pl.num_programs(0) - 1)
        def _fold_heads():
            heads = HeadState(
                lat_mean=lat_mean_ref[:],
                lat_var=lat_var_ref[:],
                err_mean=err_mean_ref[:],
                rate_mean=rate_mean_ref[:],
                rate_var=rate_var_ref[:],
                cusum=cusum_ref[:],
                obs_batches=obs_ref[0, :],
            )
            dt = params_ref[0, 0]
            step_pos = params_ref[0, 1] > 0.5
            new_heads, (lat_z, err_z, rate_z) = head_update(
                new_stats, heads, dt, step_pos, **head_statics
            )
            lat_mean_o[:] = new_heads.lat_mean
            lat_var_o[:] = new_heads.lat_var
            err_mean_o[:] = new_heads.err_mean
            rate_mean_o[:] = new_heads.rate_mean
            rate_var_o[:] = new_heads.rate_var
            cusum_o[:] = new_heads.cusum
            obs_o[0, :] = new_heads.obs_batches
            lat_z_o[:] = lat_z
            err_z_o[:] = err_z
            rate_z_o[:] = rate_z


def _out_structs(
    shapes_dtypes: list[tuple[tuple[int, ...], jnp.dtype]],
    inputs: tuple,
) -> tuple:
    """ShapeDtypeStructs carrying the inputs' vma union. Under
    shard_map the per-shard result varies across every mesh axis any
    input varies across (batch-sharded lanes, sketch-localised ids);
    pallas_call can't infer that, so propagate the union."""
    vma = frozenset().union(*(jax.typeof(x).vma for x in inputs))
    return tuple(jax.ShapeDtypeStruct(s, d, vma=vma) for s, d in shapes_dtypes)


def _batch_tiling(b: int, batch_tile: int | None) -> tuple[int, int]:
    """(grid steps, tile rows) for the batch axis.

    Tile the batch axis so VMEM holds one tile, not the whole batch;
    the grid accumulates tiles into one delta/bank (see the kernels).
    4096 keeps the [TB, chunk] compare intermediates comfortably under
    the 16M scoped-VMEM limit at any total B (8192 tiles sat at
    16.04M — over by 40K — once the grid's double buffering counted).
    Picks the LARGEST divisor tile ≤ target (fewest grid steps), not a
    power-of-two shrink: every grid step re-sweeps all sketch cell
    tiles, so a degenerate tile (e.g. 16 for b=6000) would be a
    silent orders-of-magnitude cliff. Refuses instead of degrading.
    """
    target = min(b, batch_tile or 4096)
    nb = -(-b // target)  # ceil
    while nb <= b and b % nb:
        nb += 1
    tb = b // nb
    if tb < min(target, 256):
        hint = (
            f"pick a batch_tile that divides {b}"
            if batch_tile
            else "use a batch size that is a multiple of 4096 (or ≤ 4096)"
        )
        raise ValueError(
            f"batch size {b} has no usable tile divisor near {target} "
            f"for the pallas impl; {hint}"
        )
    return nb, tb


def _delta_pallas(
    flat: jnp.ndarray,
    rank: jnp.ndarray,
    cidx_t: jnp.ndarray,
    weight: jnp.ndarray,
    svc: jnp.ndarray,
    feats: jnp.ndarray,
    *,
    num_services: int,
    hll_regs: int,
    cms_depth: int,
    cms_width: int,
    interpret: bool = False,
    batch_tile: int | None = None,
) -> SketchDelta:
    b = flat.shape[0]
    nb, tb = _batch_tiling(b, batch_tile)
    sr = num_services * hll_regs
    wide = nb > 1  # multi-tile grid: pipelined sweeps want wide chunks
    c_hll = _cell_chunk(sr, 2 * tb, wide=wide)  # 2*: double-buffer headroom
    out_shape = _out_structs(
        [
            ((sr // c_hll, c_hll), jnp.int32),
            ((cms_depth, cms_width), jnp.int32),
            ((4, num_services), jnp.float32),
        ],
        (flat, rank, cidx_t, weight, svc, feats),
    )
    d = cidx_t.shape[1]

    def col_tile(i):  # [B, k] inputs: tile the batch (row) axis
        return (i, 0)

    def feats_tile(i):  # [4, B] input: tile the lane (col) axis
        return (0, i)

    def whole(i):  # outputs: same full block every grid step
        return (0, 0)

    hll_d, cms_d, stats = pl.pallas_call(
        functools.partial(_delta_kernel, wide=wide),
        name=KERNEL_DELTA,
        grid=(nb,),
        # The compiler's default scoped-VMEM budget (16 MiB) sits ~36 KiB
        # under what the grid pipeline requests at very large B; v5e has
        # 128 MiB physical VMEM, so grant headroom explicitly.
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        out_shape=out_shape,
        in_specs=[
            pl.BlockSpec((tb, 1), col_tile, memory_space=pltpu.VMEM),
            pl.BlockSpec((tb, 1), col_tile, memory_space=pltpu.VMEM),
            pl.BlockSpec((tb, d), col_tile, memory_space=pltpu.VMEM),
            pl.BlockSpec((tb, 1), col_tile, memory_space=pltpu.VMEM),
            pl.BlockSpec((tb, 1), col_tile, memory_space=pltpu.VMEM),
            pl.BlockSpec((4, tb), feats_tile, memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec((sr // c_hll, c_hll), whole, memory_space=pltpu.VMEM),
            pl.BlockSpec((cms_depth, cms_width), whole, memory_space=pltpu.VMEM),
            pl.BlockSpec((4, num_services), whole, memory_space=pltpu.VMEM),
        ),
        interpret=interpret,
    )(
        flat.reshape(b, 1),
        rank.reshape(b, 1),
        cidx_t,
        weight.reshape(b, 1),
        svc.reshape(b, 1),
        feats,
    )
    return SketchDelta(
        hll=hll_d.reshape(num_services, hll_regs), cms=cms_d, stats=stats
    )


def sketch_batch_delta(
    svc: jnp.ndarray,  # int32[B] — local service ids (may be out of range)
    log_lat: jnp.ndarray,  # float32[B]
    is_error: jnp.ndarray,  # float32[B]
    trace_hi: jnp.ndarray,  # uint32[B]
    trace_lo: jnp.ndarray,  # uint32[B]
    cidx: jnp.ndarray,  # int32[D, B] — CMS row indices (global hashes)
    valid: jnp.ndarray,  # bool[B]
    *,
    num_services: int,
    hll_p: int = hll.HLL_P,
    cms_width: int = cms.CMS_WIDTH,
    impl: str = "xla",  # "xla" | "pallas" | "interpret"
    batch_tile: int | None = None,  # pallas batch-grid tile (default 4096)
) -> SketchDelta:
    """Reduce one span batch to its mergeable sketch delta.

    Semantics (both impls):
    - HLL counts only lanes that are valid *and* in the local service
      slice ``[0, num_services)`` (out-of-slice ids belong to another
      shard on the sketch mesh axis).
    - CMS counts every valid lane (the table is global; service is
      folded into the key hash upstream).
    - stats rows are (count, Σlog-lat, Σlog-lat², Σerr) per service.
    """
    r = 1 << hll_p
    svc = svc.astype(jnp.int32)
    in_slice = (svc >= 0) & (svc < num_services)
    bucket, rank = hll.hll_indices(trace_hi, trace_lo, p=hll_p)
    rank = jnp.where(valid & in_slice, rank, 0)
    flat = jnp.where(in_slice, svc, 0) * r + bucket
    d = cidx.shape[0]

    if impl == "xla":
        with jax.named_scope(SCOPE_HLL):
            hll_d = hll.hll_update(
                jnp.zeros((num_services, r), jnp.int32),
                jnp.where(in_slice, svc, num_services),
                bucket,
                rank,
                valid,
            )
        # Unit weights → the scatter-free histogram. cms_update_hist
        # auto-selects its engine: at production geometries on TPU
        # (tile-divisible key counts) that is the MXU one-hot
        # outer-product Pallas kernel — so the "xla" impl embeds a
        # Pallas hist — with sort+searchsorted elsewhere; both are
        # bit-exact and ~2-4× over the duplicate-heavy scatter.
        with jax.named_scope(SCOPE_CMS):
            cms_d = cms.cms_update_hist(
                jnp.zeros((d, cms_width), jnp.int32), cidx, valid
            )
        with jax.named_scope(SCOPE_HEAD):
            cnt, lat_sum, lat_sumsq = ewma.segment_stats(
                log_lat, svc, num_services, valid=valid
            )
            _, err_sum, _ = ewma.segment_stats(
                is_error, svc, num_services, valid=valid
            )
            stats = jnp.stack([cnt, lat_sum, lat_sumsq, err_sum], axis=0)
        return SketchDelta(hll=hll_d, cms=cms_d, stats=stats)

    valid_f = valid.astype(jnp.float32)
    log_lat = log_lat.astype(jnp.float32) * valid_f
    feats = jnp.stack(
        [valid_f, log_lat, log_lat * log_lat, is_error.astype(jnp.float32) * valid_f],
        axis=0,
    )  # [4, B]
    return _delta_pallas(
        flat,
        rank,
        cidx.T,
        valid.astype(jnp.int32),
        jnp.where(valid & in_slice, svc, num_services),
        feats,
        num_services=num_services,
        hll_regs=r,
        cms_depth=d,
        cms_width=cms_width,
        interpret=(impl == "interpret"),
        batch_tile=batch_tile,
    )


def _update_pallas(
    flat: jnp.ndarray,
    rank: jnp.ndarray,
    cidx_t: jnp.ndarray,
    weight: jnp.ndarray,
    svc: jnp.ndarray,
    feats: jnp.ndarray,
    hll_cur: jnp.ndarray,  # int32[W, S, R]
    cms_cur: jnp.ndarray,  # int32[W, D, Wc]
    *,
    num_services: int,
    hll_regs: int,
    cms_depth: int,
    cms_width: int,
    interpret: bool = False,
    batch_tile: int | None = None,
    heads: HeadState | None = None,
    dt: jnp.ndarray | None = None,
    step_pos: jnp.ndarray | None = None,
    head_statics: dict | None = None,
):
    b = flat.shape[0]
    nb, tb = _batch_tiling(b, batch_tile)
    sr = num_services * hll_regs
    n_windows = hll_cur.shape[0]
    wide = nb > 1
    c_hll = _cell_chunk(sr, 2 * tb, wide=wide)
    # Row-stack the window banks into 2D blocks (same [rows, lanes]
    # shape discipline as the delta kernel — 3D blocks would force the
    # mosaic tiler onto an untested layout for no bandwidth gain).
    hll2 = hll_cur.reshape(n_windows * (sr // c_hll), c_hll)
    cms2 = cms_cur.reshape(n_windows * cms_depth, cms_width)
    fold = heads is not None
    out_dims: list[tuple[tuple[int, ...], jnp.dtype]] = [
        (hll2.shape, jnp.int32),
        (cms2.shape, jnp.int32),
        ((4, num_services), jnp.float32),
    ]
    head_ins: tuple = ()
    if fold:
        params = jnp.stack(
            [
                jnp.asarray(dt, jnp.float32),
                jnp.asarray(step_pos, jnp.float32),
            ]
        ).reshape(1, 2)
        head_ins = (
            heads.lat_mean, heads.lat_var, heads.err_mean,
            heads.rate_mean, heads.rate_var, heads.cusum,
            heads.obs_batches.reshape(1, num_services), params,
        )
        n_taus = heads.lat_mean.shape[1]
        out_dims += [
            ((num_services, n_taus), jnp.float32),  # lat_mean'
            ((num_services, n_taus), jnp.float32),  # lat_var'
            ((num_services, n_taus), jnp.float32),  # err_mean'
            ((num_services, n_taus), jnp.float32),  # rate_mean'
            ((num_services, n_taus), jnp.float32),  # rate_var'
            ((num_services, 3), jnp.float32),       # cusum'
            ((1, num_services), jnp.float32),       # obs_batches'
            ((num_services, n_taus), jnp.float32),  # lat_z
            ((num_services, n_taus), jnp.float32),  # err_z
            ((num_services, n_taus), jnp.float32),  # rate_z
        ]
    out_shape = _out_structs(
        out_dims,
        (flat, rank, cidx_t, weight, svc, feats, hll2, cms2) + head_ins,
    )
    d = cidx_t.shape[1]

    def col_tile(i):  # [B, k] inputs: tile the batch (row) axis
        return (i, 0)

    def feats_tile(i):  # [4, B] input: tile the lane (col) axis
        return (0, i)

    def whole(i):  # banks/heads/outputs: same full block every step
        return (0, 0)

    def whole_spec(shape):
        return pl.BlockSpec(shape, whole, memory_space=pltpu.VMEM)

    in_specs = [
        pl.BlockSpec((tb, 1), col_tile, memory_space=pltpu.VMEM),
        pl.BlockSpec((tb, 1), col_tile, memory_space=pltpu.VMEM),
        pl.BlockSpec((tb, d), col_tile, memory_space=pltpu.VMEM),
        pl.BlockSpec((tb, 1), col_tile, memory_space=pltpu.VMEM),
        pl.BlockSpec((tb, 1), col_tile, memory_space=pltpu.VMEM),
        pl.BlockSpec((4, tb), feats_tile, memory_space=pltpu.VMEM),
        whole_spec(hll2.shape),
        whole_spec(cms2.shape),
    ] + [whole_spec(tuple(x.shape)) for x in head_ins]
    out_specs = tuple(whole_spec(shape) for shape, _dtype in out_dims)

    got = pl.pallas_call(
        functools.partial(
            _update_kernel, wide=wide, n_windows=n_windows,
            fold_heads=fold, head_statics=head_statics,
        ),
        name=KERNEL_UPDATE,
        grid=(nb,),
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=100 * 1024 * 1024,
        ),
        out_shape=out_shape,
        in_specs=in_specs,
        out_specs=out_specs,
        interpret=interpret,
    )(
        flat.reshape(b, 1),
        rank.reshape(b, 1),
        cidx_t,
        weight.reshape(b, 1),
        svc.reshape(b, 1),
        feats,
        hll2,
        cms2,
        *head_ins,
    )
    hll_new = got[0].reshape(n_windows, num_services, hll_regs)
    cms_new = got[1].reshape(n_windows, cms_depth, cms_width)
    stats = got[2]
    if not fold:
        return hll_new, cms_new, stats
    new_heads = HeadState(
        lat_mean=got[3], lat_var=got[4], err_mean=got[5],
        rate_mean=got[6], rate_var=got[7], cusum=got[8],
        obs_batches=got[9].reshape(num_services),
    )
    return hll_new, cms_new, stats, new_heads, (got[10], got[11], got[12])


def sketch_batch_update(
    hll_cur: jnp.ndarray,  # int32[W, S, R] — current window banks
    cms_cur: jnp.ndarray,  # int32[W, D, Wc] — current window banks
    svc: jnp.ndarray,  # int32[B] — local service ids (may be out of range)
    log_lat: jnp.ndarray,  # float32[B]
    is_error: jnp.ndarray,  # float32[B]
    trace_hi: jnp.ndarray,  # uint32[B]
    trace_lo: jnp.ndarray,  # uint32[B]
    cidx: jnp.ndarray,  # int32[D, B] — CMS row indices (global hashes)
    valid: jnp.ndarray,  # bool[B]
    *,
    num_services: int,
    hll_p: int = hll.HLL_P,
    cms_width: int = cms.CMS_WIDTH,
    impl: str = "xla",  # "xla" | "pallas" | "interpret"
    batch_tile: int | None = None,
    heads: HeadState | None = None,
    dt: jnp.ndarray | None = None,
    step_pos: jnp.ndarray | None = None,
    # Head constants: REQUIRED whenever ``heads`` is passed (no
    # defaults here — they live in DetectorConfig, and a stale copy
    # would silently detune the folded path).
    taus_s: tuple | None = None,
    warmup_batches: float | None = None,
    z_warmup_batches: float | None = None,
    cusum_k: float | None = None,
    cusum_cap: float | None = None,
    err_slack: float | None = None,
):
    """One-pass batch absorption: ``(hll_banks', cms_banks', stats)``.

    The single-chip fast path of the ingest spine: instead of
    materializing a :class:`SketchDelta` and broadcast-merging it into
    every tumbling window bank as a second step, the batch's effect is
    folded into ALL ``W`` current banks inside one program — the Pallas
    kernel keeps banks + batch tile VMEM-resident and never writes the
    intermediate delta to HBM; the ``xla`` reference expresses the same
    fold as delta+merge in one traced scope (XLA fuses the broadcast
    into the delta's epilogue). Integer monoids (HLL max, CMS add) make
    every impl bit-identical to the two-step form — pinned by
    tests/test_fused.py.

    **Fused head update** (the r15 close of PR 9's last round trip):
    pass ``heads`` (+ ``dt``, ``step_pos`` and the head constants) and
    the EWMA/CUSUM head advance folds into the SAME program — the
    return grows to ``(hll', cms', stats, heads',
    (lat_z, err_z, rate_z))``. In the Pallas impl the head math runs on
    the last grid step against the VMEM-resident stats accumulator, so
    no stats delta round-trips to HBM between sketch fold and head
    advance; every impl shares :func:`head_update` verbatim, making the
    folded form bit-identical to calling it separately (pinned by
    tests/test_fused.py).

    NOT for the mesh path: under ``shard_map`` the per-shard DELTA must
    cross the batch-axis collectives before any bank merge, so
    ``detector_step`` uses this only when ``comm is NO_COMM`` (the mesh
    path applies :func:`head_update` to the psum-merged stats instead).
    """
    r = 1 << hll_p
    svc = svc.astype(jnp.int32)
    in_slice = (svc >= 0) & (svc < num_services)
    bucket, rank = hll.hll_indices(trace_hi, trace_lo, p=hll_p)
    rank = jnp.where(valid & in_slice, rank, 0)
    flat = jnp.where(in_slice, svc, 0) * r + bucket
    head_statics = None
    if heads is not None:
        required = dict(
            taus_s=taus_s, warmup_batches=warmup_batches,
            z_warmup_batches=z_warmup_batches, cusum_k=cusum_k,
            cusum_cap=cusum_cap, err_slack=err_slack, dt=dt,
            step_pos=step_pos,
        )
        missing = [k for k, v in required.items() if v is None]
        if missing:
            raise TypeError(
                f"sketch_batch_update(heads=...) requires {missing} "
                "(the head constants come from DetectorConfig — no "
                "defaults here)"
            )
        head_statics = dict(
            taus_s=tuple(taus_s),
            warmup_batches=warmup_batches,
            z_warmup_batches=z_warmup_batches,
            cusum_k=cusum_k,
            cusum_cap=cusum_cap,
            err_slack=err_slack,
        )

    if impl == "xla":
        delta = sketch_batch_delta(
            svc, log_lat, is_error, trace_hi, trace_lo, cidx, valid,
            num_services=num_services, hll_p=hll_p, cms_width=cms_width,
            impl="xla",
        )
        with jax.named_scope(SCOPE_HLL):
            hll_new = jnp.maximum(hll_cur, delta.hll[None])
        with jax.named_scope(SCOPE_CMS):
            cms_new = cms_cur + delta.cms[None]
        merged = (hll_new, cms_new, delta.stats)
        if heads is None:
            return merged
        with jax.named_scope(SCOPE_HEAD):
            new_heads, zs = head_update(
                delta.stats, heads, dt, step_pos, **head_statics
            )
        return merged + (new_heads, zs)

    valid_f = valid.astype(jnp.float32)
    log_lat = log_lat.astype(jnp.float32) * valid_f
    feats = jnp.stack(
        [
            valid_f,
            log_lat,
            log_lat * log_lat,
            is_error.astype(jnp.float32) * valid_f,
        ],
        axis=0,
    )  # [4, B]
    return _update_pallas(
        flat,
        rank,
        cidx.T,
        valid.astype(jnp.int32),
        jnp.where(valid & in_slice, svc, num_services),
        feats,
        hll_cur,
        cms_cur,
        num_services=num_services,
        hll_regs=r,
        cms_depth=cidx.shape[0],
        cms_width=cms_width,
        interpret=(impl == "interpret"),
        batch_tile=batch_tile,
        heads=heads,
        dt=dt,
        step_pos=step_pos,
        head_statics=head_statics,
    )


# --- impl auto-select: geometry-derived rate model -----------------------
#
# Calibration anchors, measured single-chip at the REFERENCE geometry
# (S=32, p=12, D=4, W=8192; fetch-synchronized slope timing of the FULL
# detector step, r5 after the transposed-int8 MXU histogram landed —
# its geometry gate now engages from B=2048, n_keys multiple of 8192):
#
#     B        pallas      xla        winner
#     2048     1.1M/s      0.6M/s     pallas (narrow chunks)
#     8192     5.8M/s      ~2.3M/s    pallas (wide chunks)
#     16384    6.2M/s      ~4.2M/s    pallas
#     32768    6.7M/s     ~12M/s      xla
#     65536    6.6M/s     47.2M/s     xla
#     524288   7.2M/s    104.8M/s     xla
#     2097152     —       123.1M/s    xla (plateau)
#
# These curves were fitted before this round through a remote device
# that is gone; none is verified on a local chip (PERF.md, open
# questions). Mid-size xla numbers (8k-32k) carried real run-to-run
# variance there (32768 measured 8-23M across trials); anchors are
# tight-floor medians, and _TIE_MARGIN absorbs the slack. The r4
# table's 42.7M@16384 did not reproduce after the r5 rework (r5's
# tight-floor runs put 16384 at ~4M either round).
#
# The router must not hard-code the conclusions of that table (r3 did:
# fixed crossovers at 8192/32768, stale the moment cms_width or hll_p
# changed). Instead it scales both sides by geometry:
#
# - The dense kernel's work is O(B·cells) compares BY CONSTRUCTION
#   (every batch tile sweeps every sketch cell tile), so its rate
#   scales as 1/cells — the one scaling law in this file that is
#   exact, not fitted; the B-shape comes from the measured curve
#   (_PALLAS_CURVE: narrow-chunk ramp through 4096, wide plateau
#   7.2M/s at cells_ref). That law is
#   also the kernel's CEILING: at the reference geometry no layout can
#   push the dense formulation past ~12M spans/s (B·cells compares at
#   the VPU's element rate), which is why the large-B regime belongs
#   to the histogram formulation by construction, not by tuning — see
#   PARITY.md "config #4".
# - The xla path's rate comes from the measured curves above
#   (log-interpolated in B, engine chosen by the REAL geometry gate),
#   derated by bins growth: its large-B cost is the CMS histogram,
#   whose work scales with the bin count. Bins below the reference cap
#   at the measured rate (never extrapolate faster than measured).

_REF_CELLS = 32 * (1 << 12) + 4 * 8192  # 163840
_REF_BINS = 4 * 8192
# Dense-kernel full-step curve at the reference geometry (the narrow→
# wide chunk transition sits at 8192, see _cell_chunk); rates scale as
# 1/cells, the kernel's exact O(B·cells) law. A flat narrow anchor
# (the r5-initial model) misrouted 4096-6144 to the slower xla path —
# the measured curve keeps routing monotone through the ramp.
_PALLAS_CURVE = (
    (2048, 1.14e6), (4096, 1.54e6), (8192, 5.8e6), (16384, 6.2e6),
    (65536, 6.6e6), (524288, 7.2e6),
)
# (batch, spans/s) at the reference geometry, per histogram engine.
_XLA_MXU_CURVE = (
    (2048, 0.62e6), (8192, 2.3e6), (16384, 4.2e6), (32768, 12.0e6),
    (65536, 47.2e6), (524288, 104.8e6), (2097152, 123.1e6),
)
_XLA_SORT_CURVE = ((2048, 0.63e6), (4096, 1.2e6), (8192, 1.7e6), (32768, 7.0e6))
# Prefer xla inside this band: the pallas side is its best-case plateau
# K, while the sort numbers are full-step measurements — at the pre-MXU
# ~32k tie (6.7 vs 7.0) the dense kernel's model slightly overshoots.
_TIE_MARGIN = 0.9


def _interp_rate(curve, batch: float) -> float:
    """Piecewise log-log interpolation, clamped at the curve's ends."""
    import math

    if batch <= curve[0][0]:
        return curve[0][1]
    if batch >= curve[-1][0]:
        return curve[-1][1]
    for (b0, r0), (b1, r1) in zip(curve, curve[1:]):
        if b0 <= batch <= b1:
            f = math.log(batch / b0) / math.log(b1 / b0)
            return r0 * (r1 / r0) ** f
    return curve[-1][1]  # unreachable


def expected_rates(
    batch: int,
    cms_depth: int = cms.CMS_DEPTH,
    cms_width: int = cms.CMS_WIDTH,
    num_services: int = 32,
    hll_p: int = hll.HLL_P,
) -> tuple[float, float]:
    """(pallas, xla) expected spans/s at this batch AND geometry."""
    cells = num_services * (1 << hll_p) + cms_depth * cms_width
    bins = cms_depth * cms_width
    pallas_rate = _interp_rate(_PALLAS_CURVE, batch) * (
        _REF_CELLS / max(cells, 1)
    )
    mxu = cms.mxu_hist_geometry_ok(bins, cms_depth * batch)
    if mxu:
        # Bins growth derates the MXU estimate only: the one-hot
        # contraction's FLOPs scale with the bin count. The sort
        # engine's cost is O(keys·log) — bins touch nothing but the
        # searchsorted log factor, so its curve stays as measured.
        xla_rate = _interp_rate(_XLA_MXU_CURVE, batch) * min(
            1.0, _REF_BINS / max(bins, 1)
        )
    else:
        xla_rate = _interp_rate(_XLA_SORT_CURVE, batch)
    return pallas_rate, xla_rate


def resolve_impl(
    requested: str | None,
    batch: int | None = None,
    cms_depth: int = cms.CMS_DEPTH,
    cms_width: int = cms.CMS_WIDTH,
    num_services: int = 32,
    hll_p: int = hll.HLL_P,
) -> str:
    """Map a config's ``sketch_impl`` field to a concrete impl name.

    ``None`` auto-selects by backend, batch size AND sketch geometry:
    the expected-rate model above picks whichever side wins at the
    configured (cells, bins, batch) — e.g. a large sketch (S=64, p=14)
    sinks the dense kernel's K/cells rate enough that xla wins at every
    batch, where the r3 fixed-crossover table would have silently kept
    pallas. CPU interpret mode is for tests, not production CPU runs.
    """
    if requested is None:
        if jax.default_backend() != "tpu":
            return "xla"
        if batch is None:
            return "pallas"  # no batch hint: the low-latency default
        pallas_rate, xla_rate = expected_rates(
            batch, cms_depth, cms_width, num_services, hll_p
        )
        return "xla" if xla_rate >= _TIE_MARGIN * pallas_rate else "pallas"
    if requested not in ("xla", "pallas", "interpret"):
        raise ValueError(f"unknown sketch impl {requested!r}")
    return requested
