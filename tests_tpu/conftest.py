"""TPU-context test lane (reference `tests/python/gpu/test_operator_gpu.py`
pattern: rerun the operator battery on the accelerator and compare against
the CPU context).

This lane is run on purpose, through the chip tool, from the repo root:
`python -m pytest tests_tpu -q`.  Unlike `tests/` (which pins everything to
a virtual CPU mesh) it keeps the real platform — and without a TPU it is an
error at collection, not a green run of skips.
"""
import numpy as np
import pytest


def pytest_collection_modifyitems(config, items):
    import jax
    if jax.default_backend() != "tpu":
        raise pytest.UsageError(
            "tests_tpu/ is the chip lane and JAX came up on "
            f"{jax.default_backend()!r}: nothing here may pass on a CPU")


@pytest.fixture(autouse=True)
def _seeded():
    np.random.seed(0)
    import incubator_mxnet_tpu as mx
    mx.random.seed(0)
    yield
