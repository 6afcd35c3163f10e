"""Count-Min sketch on packed count tensors, TPU-first.

State layout: ``int32[..., D, W]`` — leading window axes, then ``D`` hash
rows × ``W`` counters. The sketch is *global* with the service id folded
into the key hash (keys are (service, attribute) pairs hashed together on
the host / in ``models.detector``): point queries always name a service,
so folding loses nothing and keeps the scatter one flat 1-D op instead of
a per-service loop — the shape XLA lowers best on TPU.

Row hashes use the Kirsch–Mitzenmacher construction ``g_i = lo + i·hi``
(two independent 32-bit hashes generate d pairwise-usable row hashes),
so the device never needs more than the one 64-bit key hash produced by
``ops.hashing``.

Monoid algebra: update = scatter-add, merge = elementwise add (``lax.psum``
over the batch mesh axis on multi-chip), query = min over rows.

Answers BASELINE config #2 ("Count-Min heavy-hitter attrs across all
services") — the reference system surfaces the same question as Grafana
top-k panels over spanmetrics
(/root/reference/src/grafana/provisioning/dashboards/demo/spanmetrics-dashboard.json).
"""

from __future__ import annotations

import jax.numpy as jnp

CMS_DEPTH = 4
# Width 8192: for counts over a 1-60s window of ~thousands of spans the
# over-estimate bound e·N/W is single-digit counts — negligible against the
# heavy-hitter thresholds we flag on. int32[4, 8192] = 128 KiB per window.
CMS_WIDTH = 8192


def cms_init(
    depth: int = CMS_DEPTH, width: int = CMS_WIDTH, leading: tuple[int, ...] = ()
) -> jnp.ndarray:
    """Zeroed count table ``int32[*leading, depth, width]``."""
    return jnp.zeros((*leading, depth, width), dtype=jnp.int32)


def cms_indices(
    hash_hi: jnp.ndarray,
    hash_lo: jnp.ndarray,
    depth: int = CMS_DEPTH,
    width: int = CMS_WIDTH,
) -> jnp.ndarray:
    """Row indices ``int32[depth, B]`` via Kirsch–Mitzenmacher.

    ``width`` must be a power of two so the modulo is a mask (VPU and-op,
    no integer division anywhere on device).
    """
    assert width & (width - 1) == 0, "CMS width must be a power of two"
    hi = hash_hi.astype(jnp.uint32)
    lo = hash_lo.astype(jnp.uint32)
    rows = []
    for i in range(depth):
        g = lo + jnp.uint32(i) * hi  # wrapping uint32 arithmetic
        rows.append((g & jnp.uint32(width - 1)).astype(jnp.int32))
    return jnp.stack(rows, axis=0)


def cms_update(
    table: jnp.ndarray,
    idx: jnp.ndarray,
    weight: jnp.ndarray | None = None,
    valid: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Scatter-add a batch into ``table[D, W]``.

    ``idx`` is ``[D, B]`` from :func:`cms_indices`. Invalid lanes add 0
    (the monoid identity) so batches stay fixed-width. One flat scatter of
    D·B elements.
    """
    d, w = table.shape[-2], table.shape[-1]
    b = idx.shape[-1]
    if weight is None:
        weight = jnp.ones((b,), dtype=table.dtype)
    weight = jnp.broadcast_to(weight.astype(table.dtype), (d, b))
    if valid is not None:
        weight = jnp.where(valid[None, :], weight, 0)
    row_offset = jnp.arange(d, dtype=jnp.int32)[:, None] * w
    flat_idx = (idx + row_offset).reshape(-1)
    flat = table.reshape(*table.shape[:-2], d * w)
    flat = flat.at[..., flat_idx].add(weight.reshape(-1), mode="drop")
    return flat.reshape(table.shape)


def cms_update_hist(
    table: jnp.ndarray,
    idx: jnp.ndarray,
    valid: jnp.ndarray | None = None,
    impl: str | None = None,
) -> jnp.ndarray:
    """Scatter-free unit-weight batch count.

    Semantically identical to :func:`cms_update` with ``weight=None``.
    TPU scatters serialize on duplicate indices, and a CMS batch is
    nothing but duplicates (B ≫ W), so the histogram is computed
    scatter-free. Two interchangeable engines (bit-exact, both tested):

    - ``"mxu"`` (TPU default when the table fits 16-bit keys and the
      batch tiles evenly): the one-hot OUTER-PRODUCT histogram — each
      flat key splits into (hi, lo) bytes, a Pallas kernel builds
      TRANSPOSED int8 one-hots ([HI, TB] and [256, TB], keys riding the
      LANE axis so the equality-compare broadcasts across sublanes —
      the cheap direction; the r4 row-major layout broadcast the key
      column across lanes, a relayout that dominated the kernel) and
      contracts them on the MXU with int8×int8→int32 accumulation into
      the [HI, 256] count matrix
      (``count[hi, lo] = Σ_b 1[hi_b=hi]·1[lo_b=lo]``). int32
      accumulation is exact for any key count below 2³¹ — the r4 f32
      engine's 2²⁴ cap is gone. Measured single-chip, D=4 W=8192
      B=512k, 200-rep slope: **~0.49 ms vs 3.3 ms** for the r4 bf16
      row-major kernel and 7.9 ms for the sort engine — BELOW the old
      bf16 MXU FLOP bound (~0.7 ms), because int8 runs the MXU at 2×
      the bf16 rate (new int8 bound ~0.35 ms; the remaining 1.4× is
      one-hot construction, now minor).
    - ``"sort"``: ``diff(searchsorted(sort(ids), edges))`` — the
      engine everywhere the kernel can't run (CPU tests, odd
      geometries), and itself ~2× over the scatter at large B.

    2-D tables only (the delta path); invalid lanes carry key ``d·w``,
    one past the counted range, and fall out of either engine.
    """
    d, w = table.shape
    row_offset = jnp.arange(d, dtype=jnp.int32)[:, None] * w
    flat_idx = idx + row_offset
    if valid is not None:
        # Invalid lanes take key d·w — one past the counted range: the
        # sort engine's edge sweep stops before it, and the mxu engine
        # folds it onto the last bin pre-kernel and subtracts the exact
        # sentinel count afterwards (see _hist_mxu's sentinel-FOLD note).
        flat_idx = jnp.where(valid[None, :], flat_idx, d * w)
    flat = flat_idx.reshape(-1)
    if impl is None:
        impl = "mxu" if _mxu_hist_usable(d * w, flat.shape[0]) else "sort"
    if impl == "mxu":
        counts = _hist_mxu(flat, d * w).astype(table.dtype)
    else:
        s = jnp.sort(flat)
        edges = jnp.arange(d * w + 1, dtype=s.dtype)
        cuts = jnp.searchsorted(s, edges)
        counts = (cuts[1:] - cuts[:-1]).astype(table.dtype)
    return table + counts.reshape(d, w)


_HIST_TILE = 8192  # keys per MXU-histogram grid step (VMEM-resident)


def mxu_hist_geometry_ok(n_bins: int, n_keys: int) -> bool:
    """Pure-geometry gate for the MXU histogram engine (no backend
    check — also used by ``fused.resolve_impl`` to predict whether the
    xla path will get the fast engine at a given batch size)."""
    return (
        # (hi, lo) byte split: bins must fit 16-bit keys (sentinels are
        # folded onto the last bin pre-kernel, so they need no slot of
        # their own) and fill whole 256-wide lo rows.
        n_bins <= 65536
        and n_bins % 256 == 0
        # the kernel tiles the key axis; a partial tile would need a
        # second masked pass — keys are D·B with B a power of two in
        # every real config, so just fall back otherwise.
        and n_keys > 0
        and n_keys % _HIST_TILE == 0
        # the MXU accumulates bin counts in int32, exact below 2^31;
        # counts are bounded by the key count, so gate on it and let
        # larger batches take the sort engine.
        and n_keys < (1 << 31)
    )


def _mxu_hist_usable(n_bins: int, n_keys: int) -> bool:
    import jax

    return jax.default_backend() == "tpu" and mxu_hist_geometry_ok(
        n_bins, n_keys
    )


# The MXU histogram kernel's stable name in device traces.
KERNEL_HIST = "cms_hist_mxu"


def _hist_mxu_kernel(keys_ref, out_ref):
    """One grid step: [1, TB] keys → TRANSPOSED int8 one-hots → MXU
    int8 contraction accumulated into the [HI, 256] int32 count block.

    Layout is the whole trick (r5): keys ride the LANE axis ([1, TB]
    row), so ``(k >> 8) == iota`` broadcasts the key vector across
    SUBLANES — the cheap broadcast direction. The r4 kernel held keys
    as a [TB, 1] column and broadcast across lanes, a per-element
    relayout that cost ~5× the matmul itself. int8 one-hots halve the
    VMEM footprint and run the MXU at 2× the bf16 rate with EXACT int32
    accumulation (no 2²⁴ cap). Keys arrive pre-clamped to [0, n_bins):
    sentinels are folded onto the last bin by the caller (see the
    sentinel-FOLD note in ``_hist_mxu``) and corrected after."""
    from jax import lax
    from jax.experimental import pallas as pl

    first = pl.program_id(0) == 0
    k = keys_ref[:]  # [1, TB] int32
    n_hi = out_ref.shape[0]
    iota_hi = lax.broadcasted_iota(jnp.int32, (n_hi, 1), 0)
    iota_lo = lax.broadcasted_iota(jnp.int32, (256, 1), 0)
    oh_hi = ((k >> 8) == iota_hi).astype(jnp.int8)  # [HI, TB]
    oh_lo = ((k & 255) == iota_lo).astype(jnp.int8)  # [256, TB]
    tile = lax.dot_general(
        oh_hi, oh_lo, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # [HI, 256]
    prev = jnp.where(first, 0, out_ref[:])
    out_ref[:] = prev + tile


def _hist_mxu(flat: jnp.ndarray, n_bins: int) -> jnp.ndarray:
    """Exact histogram of int32 keys in [0, n_bins] → counts[n_bins].

    Keys equal to ``n_bins`` (the invalid-lane sentinel) are clamped
    onto the last bin before the kernel and their exact count is
    subtracted afterwards — see the sentinel-FOLD note below. See
    cms_update_hist for engine selection."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = flat.shape[0]
    if n == 0 or n % _HIST_TILE:
        # The grid would silently truncate (or never write the output
        # block): a forced impl="mxu" at a non-tileable key count must
        # be an error, not wrong counts. Auto-select gates on this same
        # condition (_mxu_hist_usable).
        raise ValueError(
            f"mxu histogram needs a key count that is a nonzero "
            f"multiple of {_HIST_TILE}; got {n} (use impl='sort')"
        )
    if n_bins % 256:
        # Same must-be-an-error philosophy as the key-count guard: the
        # fold keeps exactly n_bins//256 hi rows, so a partial lo row
        # would silently drop keys past the last whole row (the pre-r4
        # +1-row variant tolerated this; the fold does not).
        raise ValueError(
            f"mxu histogram needs a bin count that is a multiple of "
            f"256; got {n_bins} (use impl='sort')"
        )
    if n >= 1 << 31:
        # int32 accumulation is exact only below 2^31 (counts are
        # bounded by the key count) — a forced impl="mxu" past that
        # must be an error, not silently wrapped counts, same
        # philosophy as the tile/bin guards above. Auto-select gates on
        # this condition too (mxu_hist_geometry_ok).
        raise ValueError(
            f"mxu histogram is int32-exact only below 2^31 keys; got {n} "
            f"(use impl='sort')"
        )
    # Sentinel FOLD (r4): the invalid-lane key ``n_bins`` used to ride
    # its own hi row, making HI = n_bins//256 + 1 — 129 at the
    # production table — and the MXU pads output rows to 128-row
    # tiles, so that single extra row DOUBLED the contraction passes
    # (measured: ~2x hist wall time). Clamp sentinels onto the last
    # real bin and subtract their exact count afterwards: HI stays a
    # whole number of MXU tiles and the result is bit-identical.
    sentinel_count = jnp.sum((flat >= n_bins).astype(jnp.int32))
    flat = jnp.minimum(flat, n_bins - 1)
    n_hi = n_bins // 256
    # Propagate the keys' varying-manual-axes under shard_map.
    out_struct = jax.ShapeDtypeStruct(
        (n_hi, 256), jnp.int32, vma=jax.typeof(flat).vma
    )

    # Keys as ONE [1, n] row, blocked along the lane axis: the block's
    # leading dim (1) equals the array's, satisfying the Pallas TPU
    # block-divisibility rule, and the kernel sees each tile lane-major
    # (the layout the transposed construction needs).
    counts2d = pl.pallas_call(
        _hist_mxu_kernel,
        name=KERNEL_HIST,
        grid=(n // _HIST_TILE,),
        # int8 one-hots: [HI, TB]+[256, TB] ≈ 3 MiB at TB=8k —
        # comfortably inside the default scoped-VMEM budget (the r4
        # bf16 row-major tiles needed a 96 MiB override).
        out_shape=out_struct,
        in_specs=[
            pl.BlockSpec(
                (1, _HIST_TILE), lambda i: (0, i),
                memory_space=pltpu.VMEM,
            )
        ],
        out_specs=pl.BlockSpec(
            (n_hi, 256), lambda i: (0, 0), memory_space=pltpu.VMEM
        ),
    )(flat.reshape(1, n))
    counts = counts2d.reshape(-1)
    return counts.at[n_bins - 1].add(-sentinel_count)


def cms_query(table: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Point-query counts for a batch: ``min`` over the D rows.

    Returns ``int32[..., B]`` for ``table[..., D, W]`` and ``idx[D, B]``.
    Gathers vectorise over leading window axes.
    """
    gathered = jnp.take_along_axis(
        table, jnp.broadcast_to(idx, (*table.shape[:-2], *idx.shape)), axis=-1
    )
    return jnp.min(gathered, axis=-2)


def cms_merge(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """CMS union: tables merge by elementwise addition (exact)."""
    return a + b


# -- host-side read helpers (the query plane) --------------------------
#
# The live query service (runtime.query) answers point queries from a
# SNAPSHOT of sketch state — host numpy arrays taken under the
# pipeline's dispatch lock on the primary, or the replication mirror on
# a read replica (which has no device at all). These numpy twins of
# cms_indices/cms_query exist so both roles run the IDENTICAL read
# path: same dtypes, same wrapping arithmetic, bit-identical answers
# from bit-identical state (the read-replica consistency bar
# tests/test_query.py asserts).


def cms_indices_np(
    hash_hi: "np.ndarray",
    hash_lo: "np.ndarray",
    depth: int = CMS_DEPTH,
    width: int = CMS_WIDTH,
) -> "np.ndarray":
    """Host twin of :func:`cms_indices`: ``int32[depth, B]`` rows via
    the same Kirsch–Mitzenmacher construction in wrapping uint32."""
    import numpy as np

    assert width & (width - 1) == 0, "CMS width must be a power of two"
    hi = hash_hi.astype(np.uint32)
    lo = hash_lo.astype(np.uint32)
    rows = []
    with np.errstate(over="ignore"):
        for i in range(depth):
            g = lo + np.uint32(i) * hi
            rows.append((g & np.uint32(width - 1)).astype(np.int32))
    return np.stack(rows, axis=0)


def cms_query_np(table: "np.ndarray", idx: "np.ndarray") -> "np.ndarray":
    """Host twin of :func:`cms_query`: min over the D rows of a host
    table snapshot. ``table[..., D, W]``, ``idx[D, B]`` →
    ``int32[..., B]``."""
    import numpy as np

    gathered = np.take_along_axis(
        table, np.broadcast_to(idx, (*table.shape[:-2], *idx.shape)), axis=-1
    )
    return np.min(gathered, axis=-2)
