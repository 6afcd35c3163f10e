"""Test harness: JAX on a virtual 8-device CPU mesh.

Tests run on the CPU: ``JAX_PLATFORMS=cpu`` is pinned before JAX
initialises a backend (and exported for any subprocess a test spawns),
and ``--xla_force_host_platform_device_count=8`` gives the sharding
layer an 8-device virtual mesh. What must be proven for the chip is
compiled for a described v5e in ``tests/test_chip_compile.py``.
"""

import contextlib
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"  # for any subprocesses tests spawn

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@contextlib.contextmanager
def _hold_harvest(pipe):
    """Hold a pipeline's report harvest: ``pump`` dispatches but
    fetches nothing, so reports pile up against the in-flight bound
    (the harvest-can't-keep-pace regime). Restores the harvest on
    exit — leave the block before ``drain()``/``close()``."""
    pipe._harvest_one = lambda keep=1: False
    try:
        yield pipe
    finally:
        del pipe._harvest_one


@pytest.fixture
def hold_harvest():
    return _hold_harvest


def missing_env_resource(resource: str) -> str | None:
    """Why ``resource`` is unavailable here, or None when present.

    The vocabulary behind the ``requires_env`` marker: each entry is
    an environment capability some tests legitimately need and CI
    legitimately lacks (the image ships no protoc). Unknown resources
    read as missing — a typo'd marker skips loudly instead of failing
    mysteriously."""
    if resource == "protoc":
        import shutil

        return None if shutil.which("protoc") else "protoc not on PATH"
    return f"unknown requires_env resource {resource!r}"


def pytest_collection_modifyitems(config, items):
    """Turn ``requires_env`` marks into explicit skips with the reason
    when the named resource is absent — known env gaps become clean
    skip signal instead of permanent red noise in tier-1."""
    for item in items:
        for mark in item.iter_markers("requires_env"):
            resource = mark.args[0] if mark.args else "<unnamed>"
            why = missing_env_resource(resource)
            if why is not None:
                item.add_marker(pytest.mark.skip(
                    reason=f"requires_env[{resource}]: {why}"
                ))
