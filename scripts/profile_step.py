"""Component-level timing of detector_step to locate fixed per-step cost.

SLOPE timing with a device→host fetch terminating every region: fixed
per-region costs cancel and device compute cannot be hidden (the r3
bisection found a 14 ms CMS gather this way). Variants chain a donated state so XLA cannot
dead-code-eliminate the part under test — note the r3 lesson: a variant
whose CMS delta is unused gets the whole histogram sort DCE'd and reads
8 ms too fast.

Usage: python scripts/profile_step.py [B]   (default 524288; real TPU)
"""

import sys
import time
from functools import partial
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import numpy as np
import jax
import jax.numpy as jnp

from opentelemetry_demo_tpu.models import (
    DetectorConfig,
    detector_init,
    detector_step,
)
from opentelemetry_demo_tpu.ops import cms, fused
from bench import BASELINE_SPANS_PER_SEC, make_batch_pool

config = DetectorConfig()
B = int(sys.argv[1]) if len(sys.argv) > 1 else 524288
rng = np.random.default_rng(0)
pool = make_batch_pool(config, B, 2, rng)
dt = jnp.float32(B / BASELINE_SPANS_PER_SEC)
mask = jnp.asarray([False] * len(config.windows_s))


def slope(name, fn, iters=20):
    """Per-step seconds of a state-chained fn(state, *batch, dt, mask)."""
    jfn = jax.jit(fn, donate_argnums=0)

    def region(k, st):
        t0 = time.perf_counter()
        for i in range(k):
            st, _ = jfn(st, *pool[i % 2], dt, mask)
        _ = float(np.asarray(st.step_idx))  # fetch forces the chain
        return time.perf_counter() - t0, st

    st = detector_init(config)
    _, st = region(3, st)
    t1, st = region(iters, st)
    t2, st = region(3 * iters, st)
    per = (t2 - t1) / (2 * iters)
    print(f"{name:34s} {per*1e3:8.2f} ms   {B/per/1e6:7.1f}M spans/s")
    return per


full = slope("full step", partial(detector_step, config))


def make_delta(use_cms: bool):
    """Delta-only step variant; ``use_cms=False`` leaves the CMS delta
    unused so XLA DCE's its histogram sort — the gap between the two
    variants IS the sort's cost. ONE body builds both so they cannot
    silently measure different computations.

    impl is FORCED to "xla": a pallas_call is opaque to XLA, so
    dropping the cms output would NOT eliminate the CMS work inside
    the fused kernel and the subtraction would read ~0. (The dense
    kernel has no sort to isolate anyway — this decomposition is a
    property of the xla path.)
    """

    def fn(st, svc, lat_us, is_error, hi, lo, ahi, alo, valid, dt, mask):
        log_lat = jnp.log1p(jnp.maximum(lat_us, 0.0))
        cidx = cms.cms_indices(ahi, alo, config.cms_depth, config.cms_width)
        d = fused.sketch_batch_delta(
            svc.astype(jnp.int32), log_lat, is_error, hi, lo, cidx, valid,
            num_services=config.num_services, hll_p=config.hll_p,
            cms_width=config.cms_width, impl="xla",
        )
        st = st._replace(
            hll_bank=st.hll_bank.at[:, 0].set(
                jnp.maximum(st.hll_bank[:, 0], d.hll[None])
            ),
            obs_batches=st.obs_batches + d.stats[0],
            step_idx=st.step_idx + 1,
        )
        if use_cms:
            st = st._replace(
                cms_bank=st.cms_bank.at[:, 0].set(
                    st.cms_bank[:, 0] + d.cms[None]
                )
            )
        return st, None

    return fn


delta = slope("delta xla (hll+cms+stats, used)", make_delta(use_cms=True))
no_cms = slope("delta xla w/o cms hist (DCE'd)", make_delta(use_cms=False))
print(f"{'-> cms histogram sort (xla)':34s} {(delta - no_cms)*1e3:8.2f} ms")
print(f"{'-> rest (queries/heads/report)':34s} {(full - delta)*1e3:8.2f} ms")
