"""Test configuration: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's `tests/python/unittest/common.py` fixtures: seeded
tests + a `default_context()` switch; multi-device collective tests use the
8 virtual host devices (the TPU-mesh stand-in, per the build contract).
"""
import os

# must be set before jax initializes
os.environ["JAX_PLATFORMS"] = "cpu"  # tests always run on the virtual CPU mesh
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_ENABLE_X64"] = "1"  # fp64 for numeric-gradient reference checks
# every test run (and every worker process a test spawns) compiles cold:
# the library would otherwise place JAX's persistent cache in the checkout
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

assert jax.config.jax_platforms == "cpu" and jax.config.jax_enable_x64
assert len(jax.devices()) == 8, "virtual 8-device CPU mesh not active"

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long multi-subprocess tests excluded from the "
        "tier-1 run (-m 'not slow'); tools/run_chaos.py --serving covers "
        "the same contracts as a gated artifact")


@pytest.fixture(autouse=True)
def _seeded():
    """Seed numpy + framework RNG per test (reference `with_seed()` decorator)."""
    np.random.seed(0)
    import incubator_mxnet_tpu as mx
    mx.random.seed(0)
    yield
