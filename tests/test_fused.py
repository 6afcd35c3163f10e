"""Fused batch→delta op: Pallas (interpret) vs XLA scatter semantics.

The fused kernel (ops.fused, BASELINE config #4) must be a drop-in
replacement for the scatter formulation: identical HLL/CMS deltas
(integer state ⇒ bit-exact) and float-close segment stats, including
masked lanes and out-of-slice service ids (the SPMD localisation
contract). On CPU the kernel runs in interpret mode; on real TPU the
same tests hold natively (validated on v5e-1).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from opentelemetry_demo_tpu.models import (
    DetectorConfig,
    detector_init,
    detector_step,
)
from opentelemetry_demo_tpu.ops import cms, fused
from opentelemetry_demo_tpu.ops.hashing import splitmix64_np, split_hi_lo_np


def _batch(rng, b, num_services, cms_depth, cms_width, svc_lo=0, svc_hi=None):
    svc_hi = num_services if svc_hi is None else svc_hi
    t_hi, t_lo = split_hi_lo_np(
        splitmix64_np(rng.integers(0, 2**63, size=b, dtype=np.uint64))
    )
    a_hi, a_lo = split_hi_lo_np(
        splitmix64_np(rng.integers(0, 2**20, size=b, dtype=np.uint64))
    )
    cidx = cms.cms_indices(
        jnp.asarray(a_hi), jnp.asarray(a_lo), cms_depth, cms_width
    )
    return dict(
        svc=jnp.asarray(rng.integers(svc_lo, svc_hi, size=b), jnp.int32),
        log_lat=jnp.asarray(rng.gamma(2.0, 1.0, size=b), jnp.float32),
        is_error=jnp.asarray(rng.random(b) < 0.1, jnp.float32),
        trace_hi=jnp.asarray(t_hi),
        trace_lo=jnp.asarray(t_lo),
        cidx=cidx,
        valid=jnp.asarray(rng.random(b) < 0.9),
    )


def _assert_delta_equal(ref: fused.SketchDelta, got: fused.SketchDelta):
    np.testing.assert_array_equal(np.asarray(ref.hll), np.asarray(got.hll))
    np.testing.assert_array_equal(np.asarray(ref.cms), np.asarray(got.cms))
    np.testing.assert_allclose(
        np.asarray(ref.stats), np.asarray(got.stats), rtol=1e-5, atol=1e-4
    )


class TestSketchBatchDelta:
    @pytest.mark.parametrize(
        "b,s,p,d,w",
        [
            (256, 32, 8, 4, 1024),
            (128, 8, 10, 2, 512),  # odd geometry: few services, 2 rows
            (512, 32, 8, 4, 1024),
        ],
    )
    def test_pallas_matches_xla(self, rng, b, s, p, d, w):
        kw = dict(num_services=s, hll_p=p, cms_width=w)
        # svc range includes out-of-slice ids on both sides, mimicking a
        # sketch-sharded shard seeing global ids localised by subtraction.
        batch = _batch(rng, b, s, d, w, svc_lo=-3, svc_hi=s + 3)
        ref = fused.sketch_batch_delta(*batch.values(), impl="xla", **kw)
        got = fused.sketch_batch_delta(*batch.values(), impl="interpret", **kw)
        _assert_delta_equal(ref, got)

    @pytest.mark.parametrize("batch_tile", [64, 128, 256])
    def test_batch_grid_tiling_matches_single_block(self, rng, batch_tile):
        """The batch-grid accumulation path (B > tile → multi-step grid
        revisiting the same output block) is bit-identical to the XLA
        reference and to the single-block kernel."""
        b, s, p, d, w = 512, 16, 8, 4, 1024
        kw = dict(num_services=s, hll_p=p, cms_width=w)
        batch = _batch(rng, b, s, d, w, svc_lo=-3, svc_hi=s + 3)
        ref = fused.sketch_batch_delta(*batch.values(), impl="xla", **kw)
        tiled = fused.sketch_batch_delta(
            *batch.values(), impl="interpret", batch_tile=batch_tile, **kw
        )
        _assert_delta_equal(ref, tiled)

    def test_resolve_impl_batch_crossover(self, monkeypatch):
        """Auto-selection routes small batches to the dense kernel and
        the rest to the xla path, reproducing the r5 single-chip
        FULL-STEP measurements at the reference geometry (calibration
        table above fused.expected_rates: pallas 5.8M vs ~2.3M at
        8192 and 6.2M vs ~4.2M at 16384; xla from the ~24k crossover
        up, 47M at 65536)."""
        monkeypatch.setattr(fused.jax, "default_backend", lambda: "tpu")
        assert fused.resolve_impl(None, batch=2048) == "pallas"
        # The narrow-chunk ramp (4096-6144) must not misroute to xla:
        # routing stays monotone through the dense kernel's regime.
        assert fused.resolve_impl(None, batch=4096) == "pallas"
        assert fused.resolve_impl(None, batch=6144) == "pallas"
        assert fused.resolve_impl(None, batch=8192) == "pallas"
        assert fused.resolve_impl(None, batch=16384) == "pallas"
        assert fused.resolve_impl(None, batch=32768) == "xla"
        assert fused.resolve_impl(None, batch=65536) == "xla"
        assert fused.resolve_impl(None, batch=524288) == "xla"
        # Below the ~24k crossover the winner is the dense kernel
        # regardless of the histogram geometry gate; past it a
        # non-multiple batch drops the xla path onto the SLOWER sort
        # engine, whose ~32k tie the router still respects.
        assert fused.resolve_impl(None, batch=12000) == "pallas"
        assert fused.resolve_impl(None, batch=24576) == "xla"  # 3×8192
        assert fused.resolve_impl(None, batch=40000) == "xla"  # >32k tie
        assert fused.resolve_impl(None) == "pallas"  # no batch hint
        # Explicit requests are never overridden by the batch hint.
        assert fused.resolve_impl("pallas", batch=524288) == "pallas"
        monkeypatch.setattr(fused.jax, "default_backend", lambda: "cpu")
        assert fused.resolve_impl(None, batch=64) == "xla"

    def test_all_invalid_lanes_produce_empty_delta(self, rng):
        kw = dict(num_services=8, hll_p=8, cms_width=512)
        batch = _batch(rng, 64, 8, 4, 512)
        batch["valid"] = jnp.zeros(64, bool)
        got = fused.sketch_batch_delta(*batch.values(), impl="interpret", **kw)
        assert int(jnp.sum(got.hll)) == 0
        assert int(jnp.sum(got.cms)) == 0
        np.testing.assert_allclose(np.asarray(got.stats), 0.0)

    def test_delta_is_mergeable_monoid(self, rng):
        """delta(A ∪ B) == merge(delta(A), delta(B)) — the property that
        lets batch shards psum/pmax deltas instead of banks."""
        kw = dict(num_services=8, hll_p=8, cms_width=512)
        a = _batch(rng, 128, 8, 4, 512)
        b = _batch(rng, 128, 8, 4, 512)
        joint = {
            k: jnp.concatenate([a[k], b[k]], axis=-1) for k in a
        }
        da = fused.sketch_batch_delta(*a.values(), impl="interpret", **kw)
        db = fused.sketch_batch_delta(*b.values(), impl="interpret", **kw)
        dj = fused.sketch_batch_delta(*joint.values(), impl="xla", **kw)
        np.testing.assert_array_equal(
            np.asarray(jnp.maximum(da.hll, db.hll)), np.asarray(dj.hll)
        )
        np.testing.assert_array_equal(
            np.asarray(da.cms + db.cms), np.asarray(dj.cms)
        )
        np.testing.assert_allclose(
            np.asarray(da.stats + db.stats),
            np.asarray(dj.stats),
            rtol=1e-5,
            atol=1e-4,
        )

    def test_resolve_impl(self):
        assert fused.resolve_impl("xla") == "xla"
        assert fused.resolve_impl(None) in ("xla", "pallas")
        with pytest.raises(ValueError):
            fused.resolve_impl("cuda")


class TestSketchBatchUpdate:
    """The one-pass spine update (delta + fold into every window bank
    in one program) must be bit-identical to delta-then-merge — the
    integer-monoid contract detector_step's NO_COMM branch relies on."""

    def _banks(self, rng, nw, s, p, d, w):
        hll_cur = jnp.asarray(
            rng.integers(0, 20, size=(nw, s, 1 << p)), jnp.int32
        )
        cms_cur = jnp.asarray(
            rng.integers(0, 1000, size=(nw, d, w)), jnp.int32
        )
        return hll_cur, cms_cur

    @pytest.mark.parametrize("impl", ["xla", "interpret"])
    @pytest.mark.parametrize(
        "b,s,p,d,w", [(256, 32, 8, 4, 1024), (128, 8, 10, 2, 512)]
    )
    def test_update_matches_delta_then_merge(self, rng, impl, b, s, p, d, w):
        kw = dict(num_services=s, hll_p=p, cms_width=w)
        batch = _batch(rng, b, s, d, w, svc_lo=-3, svc_hi=s + 3)
        hll_cur, cms_cur = self._banks(rng, 3, s, p, d, w)
        delta = fused.sketch_batch_delta(*batch.values(), impl="xla", **kw)
        want_hll = jnp.maximum(hll_cur, delta.hll[None])
        want_cms = cms_cur + delta.cms[None]
        got_hll, got_cms, got_stats = fused.sketch_batch_update(
            hll_cur, cms_cur, *batch.values(), impl=impl, **kw
        )
        np.testing.assert_array_equal(np.asarray(want_hll), np.asarray(got_hll))
        np.testing.assert_array_equal(np.asarray(want_cms), np.asarray(got_cms))
        np.testing.assert_allclose(
            np.asarray(delta.stats), np.asarray(got_stats),
            rtol=1e-5, atol=1e-4,
        )

    @pytest.mark.parametrize("batch_tile", [64, 128])
    def test_update_batch_grid_tiling(self, rng, batch_tile):
        """Multi-step grids must seed the fold from the incoming banks
        exactly once (first step) and accumulate after — the same
        revisit-the-block discipline as the delta kernel."""
        b, s, p, d, w = 512, 16, 8, 4, 1024
        kw = dict(num_services=s, hll_p=p, cms_width=w)
        batch = _batch(rng, b, s, d, w, svc_lo=-3, svc_hi=s + 3)
        hll_cur, cms_cur = self._banks(rng, 3, s, p, d, w)
        ref_hll, ref_cms, ref_stats = fused.sketch_batch_update(
            hll_cur, cms_cur, *batch.values(), impl="xla", **kw
        )
        got_hll, got_cms, got_stats = fused.sketch_batch_update(
            hll_cur, cms_cur, *batch.values(), impl="interpret",
            batch_tile=batch_tile, **kw
        )
        np.testing.assert_array_equal(np.asarray(ref_hll), np.asarray(got_hll))
        np.testing.assert_array_equal(np.asarray(ref_cms), np.asarray(got_cms))
        np.testing.assert_allclose(
            np.asarray(ref_stats), np.asarray(got_stats),
            rtol=1e-5, atol=1e-4,
        )

    def test_all_invalid_lanes_leave_banks_untouched(self, rng):
        kw = dict(num_services=8, hll_p=8, cms_width=512)
        batch = _batch(rng, 64, 8, 4, 512)
        batch["valid"] = jnp.zeros(64, bool)
        hll_cur, cms_cur = self._banks(rng, 3, 8, 8, 4, 512)
        got_hll, got_cms, got_stats = fused.sketch_batch_update(
            hll_cur, cms_cur, *batch.values(), impl="interpret", **kw
        )
        np.testing.assert_array_equal(np.asarray(hll_cur), np.asarray(got_hll))
        np.testing.assert_array_equal(np.asarray(cms_cur), np.asarray(got_cms))
        np.testing.assert_allclose(np.asarray(got_stats), 0.0)


class TestFusedHeadUpdate:
    """The r15 head fold: sketch_batch_update with ``heads`` must be
    BIT-exact vs the two-step form (banks via sketch_batch_update, then
    fused.head_update on the returned stats) in every impl — the last
    delta round trip PR 9 left, now inside the one program."""

    HEAD_KW = dict(
        taus_s=(1.0, 10.0, 60.0), warmup_batches=20.0,
        z_warmup_batches=60.0, cusum_k=0.5, cusum_cap=50.0,
        err_slack=0.01,
    )

    def _heads(self, rng, s, t=3):
        return fused.HeadState(
            lat_mean=jnp.asarray(rng.gamma(2.0, 1.0, (s, t)), jnp.float32),
            lat_var=jnp.asarray(rng.gamma(1.0, 0.2, (s, t)), jnp.float32),
            err_mean=jnp.asarray(rng.random((s, t)) * 0.2, jnp.float32),
            rate_mean=jnp.asarray(rng.gamma(3.0, 10.0, (s, t)), jnp.float32),
            rate_var=jnp.asarray(rng.gamma(1.0, 5.0, (s, t)), jnp.float32),
            cusum=jnp.asarray(rng.random((s, 3)) * 3.0, jnp.float32),
            obs_batches=jnp.asarray(
                rng.integers(0, 100, s), jnp.float32
            ),
        )

    @pytest.mark.parametrize("impl", ["xla", "interpret"])
    @pytest.mark.parametrize("step_pos", [True, False])
    def test_folded_heads_bit_exact_vs_two_step(self, rng, impl, step_pos):
        # Both paths run under jax.jit — the regime detector_step
        # always runs in. (Eager op-by-op dispatch makes different
        # FMA-contraction choices than a traced computation, so an
        # unjitted comparison can differ by 1 ulp without either side
        # being wrong; under jit the expression graphs are identical
        # and so are the bits.)
        import jax

        b, s, p, d, w = 256, 32, 8, 4, 1024
        kw = dict(num_services=s, hll_p=p, cms_width=w)
        batch = _batch(rng, b, s, d, w, svc_lo=-3, svc_hi=s + 3)
        hll_cur = jnp.asarray(
            rng.integers(0, 20, size=(3, s, 1 << p)), jnp.int32
        )
        cms_cur = jnp.asarray(
            rng.integers(0, 1000, size=(3, d, w)), jnp.int32
        )
        heads = self._heads(rng, s)
        dt = jnp.float32(0.05)
        sp = jnp.asarray(step_pos)

        @jax.jit
        def two_step(heads):
            h, c, stats = fused.sketch_batch_update(
                hll_cur, cms_cur, *batch.values(), impl=impl, **kw
            )
            nh, zs = fused.head_update(
                stats, heads, dt, sp, **self.HEAD_KW
            )
            return h, c, stats, nh, zs

        @jax.jit
        def folded(heads):
            return fused.sketch_batch_update(
                hll_cur, cms_cur, *batch.values(), impl=impl,
                heads=heads, dt=dt, step_pos=sp, **self.HEAD_KW, **kw
            )

        ref_hll, ref_cms, ref_stats, ref_heads, ref_zs = two_step(heads)
        got_hll, got_cms, got_stats, got_heads, got_zs = folded(heads)
        np.testing.assert_array_equal(np.asarray(ref_hll), np.asarray(got_hll))
        np.testing.assert_array_equal(np.asarray(ref_cms), np.asarray(got_cms))
        np.testing.assert_array_equal(
            np.asarray(ref_stats), np.asarray(got_stats)
        )
        for name, x, y in zip(ref_heads._fields, ref_heads, got_heads):
            np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y), err_msg=name
            )
        for name, x, y in zip(("lat_z", "err_z", "rate_z"), ref_zs, got_zs):
            np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y), err_msg=name
            )

    @pytest.mark.parametrize("batch_tile", [64, 128])
    def test_folded_heads_multi_tile_grid(self, rng, batch_tile):
        """Multi-step grids run the head fold ONCE, on the last step,
        against the fully-accumulated stats: the folded form must be
        bit-exact vs two-step AT THE SAME TILING (tile count changes
        the f32 stats accumulation ORDER — a 1-ulp effect the existing
        delta-kernel tests already bound with allclose — so the pin
        here is folded-vs-two-step, not tiled-vs-untiled)."""
        import jax

        b, s, p, d, w = 512, 16, 8, 4, 1024
        kw = dict(num_services=s, hll_p=p, cms_width=w)
        batch = _batch(rng, b, s, d, w, svc_lo=-3, svc_hi=s + 3)
        hll_cur = jnp.asarray(
            rng.integers(0, 20, size=(3, s, 1 << p)), jnp.int32
        )
        cms_cur = jnp.asarray(
            rng.integers(0, 1000, size=(3, d, w)), jnp.int32
        )
        heads = self._heads(rng, s)
        dt = jnp.float32(0.05)
        sp = jnp.asarray(True)

        @jax.jit
        def two_step(heads):
            h, c, stats = fused.sketch_batch_update(
                hll_cur, cms_cur, *batch.values(), impl="interpret",
                batch_tile=batch_tile, **kw
            )
            nh, zs = fused.head_update(
                stats, heads, dt, sp, **self.HEAD_KW
            )
            return h, c, stats, nh, zs

        @jax.jit
        def folded(heads):
            return fused.sketch_batch_update(
                hll_cur, cms_cur, *batch.values(), impl="interpret",
                batch_tile=batch_tile, heads=heads, dt=dt, step_pos=sp,
                **self.HEAD_KW, **kw
            )

        ref = two_step(heads)
        got = folded(heads)
        np.testing.assert_array_equal(np.asarray(ref[0]), np.asarray(got[0]))
        np.testing.assert_array_equal(np.asarray(ref[1]), np.asarray(got[1]))
        np.testing.assert_array_equal(np.asarray(ref[2]), np.asarray(got[2]))
        for name, a, b_ in zip(ref[3]._fields, ref[3], got[3]):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b_), err_msg=name
            )
        for a, b_ in zip(ref[4], got[4]):  # z triples
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))

    @pytest.mark.parametrize("width", [4096, 8192])
    def test_folded_heads_at_ladder_widths(self, rng, width):
        """The adaptive ladder's wider dispatch widths at the DEFAULT
        tiling (4096: one tile; 8192: two tiles, wide cell chunks) run
        the same head fold: banks bit-equal to the xla impl, heads and
        z-scores bit-exact vs the two-step form."""
        import jax

        s, p, d, w = 32, 8, 4, 1024
        kw = dict(num_services=s, hll_p=p, cms_width=w)
        batch = _batch(rng, width, s, d, w, svc_lo=-3, svc_hi=s + 3)
        hll_cur = jnp.asarray(
            rng.integers(0, 20, size=(3, s, 1 << p)), jnp.int32
        )
        cms_cur = jnp.asarray(
            rng.integers(0, 1000, size=(3, d, w)), jnp.int32
        )
        heads = self._heads(rng, s)
        dt = jnp.float32(0.05)
        sp = jnp.asarray(True)

        @jax.jit
        def two_step(heads):
            h, c, stats = fused.sketch_batch_update(
                hll_cur, cms_cur, *batch.values(), impl="interpret", **kw
            )
            return h, c, stats, *fused.head_update(
                stats, heads, dt, sp, **self.HEAD_KW
            )

        def folded(heads, impl):
            return jax.jit(
                lambda heads: fused.sketch_batch_update(
                    hll_cur, cms_cur, *batch.values(), impl=impl,
                    heads=heads, dt=dt, step_pos=sp, **self.HEAD_KW, **kw
                )
            )(heads)

        ref = two_step(heads)
        got = folded(heads, "interpret")
        xla = folded(heads, "xla")
        for i in (0, 1):  # integer banks: every impl, bit for bit
            np.testing.assert_array_equal(np.asarray(ref[i]), np.asarray(got[i]))
            np.testing.assert_array_equal(np.asarray(xla[i]), np.asarray(got[i]))
        np.testing.assert_array_equal(np.asarray(ref[2]), np.asarray(got[2]))
        for name, a, b_ in zip(ref[3]._fields, ref[3], got[3]):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b_), err_msg=name
            )
        for a, b_ in zip(ref[4], got[4]):  # z triples
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))

    def test_no_stats_roundtrip_in_folded_jaxpr(self, rng):
        """Structural pin for 'no delta round-trips to HBM on the
        NO_COMM path': the folded pallas program contains exactly ONE
        pallas_call, and the head outputs come out of IT — there is no
        second kernel or post-kernel stats consumer producing them."""
        import jax

        b, s, p, d, w = 256, 32, 8, 4, 1024
        kw = dict(num_services=s, hll_p=p, cms_width=w)
        batch = _batch(rng, b, s, d, w)
        hll_cur = jnp.zeros((3, s, 1 << p), jnp.int32)
        cms_cur = jnp.zeros((3, d, w), jnp.int32)
        heads = self._heads(rng, s)

        def folded(*args):
            return fused.sketch_batch_update(
                hll_cur, cms_cur, *args, impl="interpret", heads=heads,
                dt=jnp.float32(0.05), step_pos=jnp.asarray(True),
                **self.HEAD_KW, **kw
            )

        jaxpr = jax.make_jaxpr(folded)(*batch.values())
        calls = [
            eqn for eqn in jaxpr.jaxpr.eqns if "pallas" in eqn.primitive.name
        ]
        assert len(calls) == 1, [e.primitive.name for e in jaxpr.jaxpr.eqns]
        # The single kernel emits banks + stats + 7 head arrays + 3 zs.
        assert len(calls[0].outvars) == 13


class TestDetectorWithFusedKernel:
    def test_detector_step_identical_across_impls(self, rng):
        """The full flagship step must not care which impl ran."""
        config = DetectorConfig(
            num_services=8, hll_p=8, cms_width=512, sketch_impl="xla"
        )
        config_pl = config._replace(sketch_impl="interpret")
        b = 256
        batch = _batch(rng, b, 8, config.cms_depth, config.cms_width)
        args = (
            batch["svc"],
            jnp.expm1(batch["log_lat"]),  # step takes raw latency µs
            batch["is_error"],
            batch["trace_hi"],
            batch["trace_lo"],
            batch["trace_hi"],  # reuse as attr hashes — fine for parity
            batch["trace_lo"],
            batch["valid"],
            jnp.float32(0.05),
            jnp.asarray([True, False, False]),
        )
        s1, r1 = detector_step(config, detector_init(config), *args)
        s2, r2 = detector_step(config_pl, detector_init(config_pl), *args)
        for name, x, y in zip(s1._fields, s1, s2):
            np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-5, err_msg=name
            )
        for name, x, y in zip(r1._fields, r1, r2):
            np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), rtol=1e-5, atol=1e-5, err_msg=name
            )


class TestGeometryAwareCrossover:
    """VERDICT r3 Weak #3: the router must re-derive the crossover at
    the CONFIGURED geometry, not apply the reference table blindly."""

    def test_big_sketch_shifts_crossover_to_xla(self, monkeypatch):
        """S=64, p=14 grows the dense kernel's swept cells ~6.6x, so
        its K/cells rate sinks below the xla curve at EVERY batch —
        the r3 fixed table would have silently kept pallas at 2k-8k."""
        monkeypatch.setattr(fused.jax, "default_backend", lambda: "tpu")
        geo = dict(num_services=64, hll_p=14)
        for batch in (2048, 4096, 8192, 16384, 65536):
            assert fused.resolve_impl(None, batch=batch, **geo) == "xla", batch
        # Rate model is the reason: pallas expected rate collapsed.
        p_ref, _ = fused.expected_rates(8192)
        p_big, x_big = fused.expected_rates(8192, **geo)
        assert p_big < p_ref / 5
        assert x_big >= p_big

    def test_tiny_sketch_keeps_pallas_longer(self, monkeypatch):
        """S=8, p=8, W=512: ~4k cells make the dense sweep nearly free —
        pallas stays preferred well past the reference crossover when
        the sort engine is the xla alternative."""
        monkeypatch.setattr(fused.jax, "default_backend", lambda: "tpu")
        geo = dict(num_services=8, hll_p=8, cms_width=512)
        # 12000*4 keys fail the MXU tile gate → sort engine → the tiny
        # sketch's dense sweep wins where the reference geometry would
        # already be near the sort tie.
        assert fused.resolve_impl(None, batch=12000, **geo) == "pallas"
        p_tiny, x_tiny = fused.expected_rates(12000, **geo)
        assert p_tiny > 10 * x_tiny

    def test_wide_cms_derates_xla_histogram(self):
        """Bins beyond the reference derate the xla estimate (its
        large-B cost is the histogram, work ∝ bins); bins below it cap
        at the measured curve (no faster-than-measured extrapolation)."""
        _, x_ref = fused.expected_rates(16384)
        # W=12288 keeps the MXU gate passing (bins 49152 < 2^16) while
        # growing bins 1.5x over the reference.
        _, x_wide = fused.expected_rates(16384, cms_width=12288)
        _, x_narrow = fused.expected_rates(16384, cms_width=2048)
        assert x_wide == pytest.approx(x_ref / 1.5)
        assert x_narrow == x_ref
        # Bins past the 16-bit key gate flip the engine itself: the
        # estimate becomes the sort curve (UNderated — sort cost barely
        # depends on bins). At 65536, where the MXU curve towers over
        # sort, the flip is a big visible drop; at mid sizes (r5: the
        # fixed-cost-dominated band) the two curves run close.
        _, x_huge = fused.expected_rates(16384, cms_width=32768)
        assert x_huge == pytest.approx(
            fused._interp_rate(fused._XLA_SORT_CURVE, 16384)
        )
        _, x_mxu_64k = fused.expected_rates(65536, cms_width=12288)
        _, x_sort_64k = fused.expected_rates(65536, cms_width=32768)
        assert x_sort_64k < x_mxu_64k / 2

    def test_wide_cms_sort_config_routes_to_xla(self, monkeypatch):
        """Wide-CMS configs whose bins fail the MXU gate still route to
        xla at large B (the old SORT_CROSSOVER rule's behavior, now
        derived): sort ~7M/s beats the bigger sketch's dense sweep."""
        monkeypatch.setattr(fused.jax, "default_backend", lambda: "tpu")
        assert fused.resolve_impl(None, batch=65536, cms_width=32768) == "xla"
