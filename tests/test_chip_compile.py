"""The main path's kernels compile for a described v5e chip.

Interpret-mode tests run the Pallas kernels through XLA on the CPU and
cannot see what the TPU compiler refuses (an unsupported primitive, an
unaligned slice, too much VMEM). Here the installed TPU compiler builds
them for one chip of a described, unattached ``v5e:2x2`` at the default
deployment geometry (S=32, p=12, D=4, W=8192) and the daemon's batch,
B=2048. Nothing runs. ``impl`` is passed explicitly: the auto-select
sees this process's CPU backend.

The topology is described inside a fixture, never at import time: only
one process may load the TPU library, and under pytest-xdist every
worker imports this file.
"""

from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from opentelemetry_demo_tpu.models import detector
from opentelemetry_demo_tpu.ops import cms, fused

B = 2048


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure to describe means skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    """Lower and compile ``fn`` for the described chip from argument
    shapes (pytrees allowed); the compiled program's HLO text."""
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        shapes,
    )
    return jax.jit(fn).lower(*args).compile().as_text()


def _spans(n):
    return [
        jax.ShapeDtypeStruct((n,), dt)
        for dt in (jnp.int32, jnp.float32, jnp.float32)
        + (jnp.uint32,) * 4
        + (jnp.bool_,)
    ]


def test_detector_step_pallas_compiles(one_chip):
    """The daemon's device step — one-pass bank update with the
    EWMA/CUSUM head fold — at the compose deployment's geometry."""
    config = detector.DetectorConfig(sketch_impl="pallas")
    state = jax.eval_shape(lambda: detector.detector_init(config))
    hlo = _compile(
        partial(detector.detector_step_packed, config),
        one_chip,
        state,
        *_spans(B),
        jax.ShapeDtypeStruct((), jnp.float32),
        jax.ShapeDtypeStruct((config.num_windows,), jnp.bool_),
    )
    assert "tpu_custom_call" in hlo


def test_sketch_batch_delta_pallas_compiles(one_chip):
    """The mesh path's per-shard delta kernel."""
    d, w = 4, 8192

    def delta(svc, log_lat, is_error, t_hi, t_lo, cidx, valid):
        return fused.sketch_batch_delta(
            svc, log_lat, is_error, t_hi, t_lo, cidx, valid,
            num_services=32, hll_p=12, cms_width=w, impl="pallas",
        )

    spans = _spans(B)
    hlo = _compile(
        delta,
        one_chip,
        spans[0], spans[1], spans[2], spans[3], spans[4],
        jax.ShapeDtypeStruct((d, B), jnp.int32),
        spans[7],
    )
    assert "tpu_custom_call" in hlo


def test_cms_hist_mxu_compiles(one_chip):
    """The xla impl's CMS count engine on TPU: the int8 MXU histogram
    kernel, at 2·8192 keys."""
    d, w = 4, 8192
    n = 2 * 8192 // d

    def hist(table, idx, valid):
        return cms.cms_update_hist(table, idx, valid, impl="mxu")

    hlo = _compile(
        hist,
        one_chip,
        jax.ShapeDtypeStruct((d, w), jnp.int32),
        jax.ShapeDtypeStruct((d, n), jnp.int32),
        jax.ShapeDtypeStruct((n,), jnp.bool_),
    )
    assert "tpu_custom_call" in hlo


def test_evidence_gather_compiles(one_chip):
    """The flag-time per-key evidence gather at the compose geometry,
    its candidate columns padded to the daemon's cap of 64."""
    config = detector.DetectorConfig()
    state = jax.eval_shape(lambda: detector.detector_init(config))
    _compile(
        partial(detector.evidence_gather, config),
        one_chip,
        state,
        jax.ShapeDtypeStruct((), jnp.int32),
        jax.ShapeDtypeStruct((config.cms_depth, 64), jnp.int32),
    )
