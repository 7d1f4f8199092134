"""The gated delta rule's two Pallas kernels, compiled HERE for the chip the
benchmark runs on (a TPU v5e that is described, not attached), at the
published widths and the timed size: what Mosaic would refuse on the chip
(a slice off the tiling, a broadcast it cannot lay out, too much VMEM) it
refuses here, at no chip time.  Nothing runs, so nothing here says anything
about results or times (tests_tpu/test_delta_rule_kernel.py does, on the
chip).  The topology is described inside a fixture, never at import: only
the worker that is given this file loads the TPU's library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from incubator_mxnet_tpu.ops import delta_rule

B, T, HK, HV, D = 2, 4096, 16, 32, 128


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def uncached():
    """A compile for a described chip is written to JAX's persistent cache
    but cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _through_the_kernel(q, k, v, g, beta):
    """What `gated_delta_rule` runs on a TPU (here `default_backend()` is
    the CPU, so the driver is named)."""
    return delta_rule._sweep(q, k, v, g, beta, 64, "kernel")


@pytest.mark.parametrize("length,calls", [(T, 1), (T, 2), (192, 2)])
def test_kernels_compile_for_the_v5e(one_chip, uncached, length, calls):
    """Forward alone (one kernel), value and gradient (two); 4,096
    positions take two blocks of two chunks a grid step, 192 one of one
    (three chunks fill no block of two evenly)."""
    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    args = (shape(B, length, HK, D), shape(B, length, HK, D),
            shape(B, length, HV, D), shape(B, length, HV, dtype=jnp.float32),
            shape(B, length, HV, dtype=jnp.float32))
    fn = _through_the_kernel
    if calls == 2:
        fn = jax.grad(lambda *a: jnp.sum(
            _through_the_kernel(*a).astype(jnp.float32)),
            argnums=(0, 1, 2, 3, 4))
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == calls
