"""REAL multi-host validation: two jax processes, one hybrid mesh.

The hybrid (dcn × batch × sketch) mesh's multi-host branch — dcn axis
aligned to the process axis, delta merges crossing process boundaries —
previously ran only in single-host simulation. Here two OS processes
initialise ``jax.distributed`` (4 virtual CPU devices each), build the
8-device hybrid mesh, run the FULL sharded detector step with
cross-process collectives, and assert the report is bit-exact against a
single-device reference — the reference's multi-host analogue being
Kafka consumer groups scaled across hosts (SURVEY.md §2.3).
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    """Reserve a free port via bind(0) instead of guessing from a shared
    range: a random 20000-29999 pick can collide with the kafka tests'
    broker ports or unrelated ephemeral sockets under parallel runs."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]

_CHILD = r'''
import sys
import jax
jax.config.update("jax_platforms", "cpu")
pid = int(sys.argv[1])
port = sys.argv[2]
jax.distributed.initialize(
    coordinator_address=f"127.0.0.1:{port}", num_processes=2, process_id=pid
)
from functools import partial

import numpy as np
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.experimental import multihost_utils

from opentelemetry_demo_tpu.models import (
    DetectorConfig, detector_init, detector_step,
)
from opentelemetry_demo_tpu.parallel import make_hybrid_mesh, make_sharded_step
from opentelemetry_demo_tpu.runtime import SpanTensorizer

assert jax.process_count() == 2
assert jax.device_count() == 8

config = DetectorConfig(num_services=8, hll_p=6, cms_width=256, sketch_impl="xla")
B = 64
rng = np.random.default_rng(7)  # same seed both processes: global batch
n = B - 8
tz = SpanTensorizer(num_services=8, batch_size=B)
tb = tz.pack_arrays(
    svc=rng.integers(0, 8, n),
    lat_us=rng.gamma(4, 250, n).astype(np.float32),
    trace_id=rng.integers(0, 2**63, n, dtype=np.uint64),
    is_error=(rng.random(n) < 0.1).astype(np.float32),
    attr_key=rng.zipf(1.5, n).astype(np.uint64),
)
batch_np = [np.asarray(x) for x in (
    tb.svc, tb.lat_us, tb.is_error, tb.trace_hi, tb.trace_lo,
    tb.attr_hi, tb.attr_lo, tb.valid,
)]

mesh = make_hybrid_mesh(n_dcn=2, n_batch=2, n_sketch=2)
step, state = make_sharded_step(config, mesh)
bspec = NamedSharding(mesh, P(("dcn", "batch")))
rep = NamedSharding(mesh, P())

def globalize(x, sharding):
    return jax.make_array_from_callback(x.shape, sharding, lambda idx: x[idx])

gbatch = [globalize(x, bspec) for x in batch_np]
state, report = step(
    state, *gbatch,
    globalize(np.float32(0.01), rep),
    globalize(np.asarray([True, False, False]), rep),
)
lat_z = multihost_utils.process_allgather(report.lat_z, tiled=True)
hh = multihost_utils.process_allgather(report.hh_ratio, tiled=True)
card = multihost_utils.process_allgather(report.card_est, tiled=True)

# Single-device reference, computed independently in each process.
ref_step = jax.jit(partial(detector_step, config))
_, ref = ref_step(
    detector_init(config),
    *[jnp.asarray(x) for x in batch_np],
    jnp.float32(0.01),
    jnp.asarray([True, False, False]),
)
for got, want, name in (
    (lat_z, ref.lat_z, "lat_z"),
    (hh, ref.hh_ratio, "hh_ratio"),
    (card, ref.card_est, "card_est"),
):
    assert np.array_equal(np.asarray(got), np.asarray(want)), name
print(f"MULTIHOST_OK pid={pid} mesh={dict(mesh.shape)}", flush=True)
'''


def test_two_process_hybrid_mesh_bitexact():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    port = str(_free_port())
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _CHILD, str(i), port],
            env=env, cwd=REPO,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        for i in range(2)
    ]
    try:
        # Assert each child as it finishes: a fast failure (import
        # error, port bind) reports immediately instead of waiting out
        # the partner's coordinator timeout.
        for i, p in enumerate(procs):
            out, _ = p.communicate(timeout=300)
            assert p.returncode == 0, f"proc {i} failed:\n{out[-2000:]}"
            assert f"MULTIHOST_OK pid={i}" in out
    finally:
        # Never orphan a child: a hung collective or an early assert
        # would otherwise leave processes holding the port and CPU.
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            if p.poll() is None:
                try:
                    p.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    p.kill()
