"""The routed experts' three Pallas kernels, compiled HERE for the chip the
benchmark runs on (a TPU v5e that is described, not attached), at the
published widths and the timed sizes of both configurations that hold them
(`qwen3_next_80b_a3b`: 8,192 tokens, hidden 2,048, intermediate 512, 16 of
512 experts held, 10 a token, a softmax router, one tile; `lfm2_24b_a2b`:
16,384 tokens, intermediate 1,536, 8 of 64 held, 4 a token, a sigmoid
router with a selection bias, the backward pass in two tiles of 768;
`sdar_30b_a3b_chat`: 16,384 rows, intermediate 768, 16 of 128 held, 8 a
row, a softmax router, one tile): what
Mosaic would refuse on the chip (a block off the tiling, an index map it cannot lower,
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

C = 2048
# tokens, intermediate, experts, held, a token, (capacity, rows, block)
# at the configuration's capacity factor (2 and 4 mean loads), the router,
# whether it takes a selection bias, (forward, backward) tiles
SIZES = {
    "qwen3_next_80b_a3b": (8192, 512, 512, 16, 10, (5120, 7168, 128),
                           experts.Router(), False, (512, 512)),
    "lfm2_24b_a2b": (16384, 1536, 64, 8, 4, (32768, 33792, 128),
                     experts.Router("sigmoid", True, 1e-6), True,
                     (1536, 768)),
    "sdar_30b_a3b_chat": (16384, 768, 128, 16, 8, (32768, 34816, 128),
                          experts.Router(), False, (768, 768)),
}


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


def _through_the_kernel(config):
    """What `routed_experts` runs on a TPU (here `default_backend()` is the
    CPU, so the driver is named), and the shapes of its arguments."""
    n, inter, num, held, top_k, plan, router, biased, tiles = SIZES[config]
    factor = {"lfm2_24b_a2b": 4.0}.get(config, 2.0)
    assert experts.capacity(n, top_k, num, held, factor) == plan
    assert experts._tiles(n, C, inter, plan[2], 2) == tiles
    apply = experts._apply_fn(*plan, top_k, 0, router, "kernel", tiles)
    shapes = [((n, C), jnp.bfloat16), ((num, C), jnp.bfloat16),
              ((held, inter, C), jnp.bfloat16),
              ((held, inter, C), jnp.bfloat16),
              ((held, C, inter), jnp.bfloat16)] + \
        ([((num,), jnp.float32)] if biased else [])
    return (lambda *args: apply(*args)[0]), shapes


@pytest.mark.parametrize("calls", [2, 4])
@pytest.mark.parametrize("config", sorted(SIZES))
def test_kernels_compile_for_the_v5e(one_chip, uncached, config, calls):
    """Forward alone (the grouped product with nothing kept, and the
    combine), value and gradient (the product that keeps the
    pre-activations, its combine, the backward product, and the combine
    of the rows' gradients)."""
    fn, shapes = _through_the_kernel(config)
    args = [jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
            for dims, dtype in shapes]
    if calls == 4:
        forward = fn
        fn = jax.value_and_grad(lambda *a: jnp.sum(
            forward(*a).astype(jnp.float32)), argnums=(0, 1, 2, 3, 4))
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.as_text().count("tpu_custom_call") == calls
