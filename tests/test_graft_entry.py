"""Regression tests for the driver entry points (``__graft_entry__``).

These tests pin the dry run's contract: the body runs entirely on the
mesh's devices, and when the default backend is too small for the mesh
it re-execs in a pristine CPU subprocess.
"""

import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import __graft_entry__ as graft  # noqa: E402


def test_entry_compiles_and_runs():
    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)


def test_dryrun_in_process_on_cpu_mesh():
    # conftest gives this process an 8-device CPU backend, so the
    # in-process path (no fallback) is exercised here.
    assert len(jax.devices()) >= 8
    graft.dryrun_multichip(8)


def test_dryrun_body_rejects_short_device_list():
    with pytest.raises(ValueError, match="needs 8 devices"):
        graft._dryrun_body(8, jax.devices()[:1])


def test_dryrun_subprocess_path():
    # The driver topology: default backend can't host the mesh → the dry
    # run must re-exec in a clean JAX_PLATFORMS=cpu interpreter and pass.
    graft._dryrun_subprocess(8)
