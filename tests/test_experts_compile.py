"""The routed experts' three Pallas kernels, compiled HERE for the chip the
benchmark runs on (a TPU v5e that is described, not attached), at the
published widths and the timed size (8,192 tokens, hidden 2,048,
intermediate 512, 16 of 512 experts held, 10 a token): what Mosaic would
refuse on the chip (a block off the tiling, an index map it cannot lower,
more VMEM than the chip has for an expert's matrices, their gradients and
the float32 sums) it refuses here, at no chip time.  Nothing runs, so
nothing here says anything about results or times
(tests_tpu/test_experts_kernel.py does, on the chip).  The topology is
described inside a fixture, never at import: only the worker that is given
this file loads the TPU's library.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from incubator_mxnet_tpu.ops import experts

N, C, INTER, NUM, HELD, TOPK = 8192, 2048, 512, 512, 16, 10


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


def _through_the_kernel(x2, router_weight, gate, up, down):
    """What `routed_experts` runs on a TPU (here `default_backend()` is the
    CPU, so the driver is named)."""
    cap, rows, block = experts.capacity(N, TOPK, NUM, HELD)
    assert (cap, rows, block) == (5120, 7168, 128)
    return experts._apply_fn(cap, rows, block, TOPK, 0, True, "kernel")(
        x2, router_weight, gate, up, down)[0]


@pytest.mark.parametrize("calls", [2, 4])
def test_kernels_compile_for_the_v5e(one_chip, uncached, calls):
    """Forward alone (the grouped product with nothing kept, and the
    combine), value and gradient (the product that keeps the
    pre-activations, its combine, the backward product, and the combine
    of the rows' gradients)."""
    def shape(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    args = (shape(N, C), shape(NUM, C), shape(HELD, INTER, C),
            shape(HELD, INTER, C), shape(HELD, C, INTER))
    fn = _through_the_kernel
    if calls == 4:
        fn = jax.value_and_grad(lambda *a: jnp.sum(
            _through_the_kernel(*a).astype(jnp.float32)),
            argnums=(0, 1, 2, 3, 4))
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == calls
