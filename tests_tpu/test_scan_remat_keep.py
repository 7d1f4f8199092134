"""What a re-materialised scanned layer keeps, in the programs the CHIP's
compiler makes at the cells' shapes (`sdar_30b_a3b_chat`: 2 x 8,192 rows,
four scanned layers of attention under the block-diffusion mask and routed
experts; `qwen3_next_80b_a3b`: 2 x 4,096 positions, three scanned delta-rule
layers and an inlined softmax layer).  The graph's value and gradient
through `symbol.graph_eval_fn` with its scan plan -- what
`fused.FusedTrainStep` wraps its optimizer around -- is compiled from
shapes, bfloat16 parameters as the cells declare them, and never run.

The kernels are Mosaic custom calls whose `op_name` carries the kernel's
name and, where the backward scan's body computes the layer again,
`rematted_computation`: the forward kernel of a kind that names what it
made (`ops.registry.scan_kept`) is there once a scanned run, in the forward
scan, and not among what is computed again.
"""
import importlib
import json
import os
import re

import jax
import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import obs
from incubator_mxnet_tpu.analysis.graph_passes import scan_plan
from incubator_mxnet_tpu.symbol.symbol import graph_eval_fn

CONFIGS = os.path.join(os.path.dirname(__file__), os.pardir, "benchmark",
                       "configs")


def _compiled(config):
    """The compiled value-and-gradient program's text, and what the
    counters read while it was traced."""
    with open(os.path.join(CONFIGS, config + ".json")) as f:
        cfg = json.load(f)
    program = importlib.import_module(f"benchmark.configs.{config}_program")
    symbol = program.build_symbol(mx, cfg)
    data, label = program.input_descs(cfg, 2)
    arg_shapes, _, aux_shapes = symbol.infer_shape(data=data,
                                                   softmax_label=label)
    args = tuple(
        jax.ShapeDtypeStruct(shape, jnp.float32 if name in (
            "data", "softmax_label") else jnp.bfloat16)
        for name, shape in zip(symbol.list_arguments(), arg_shapes))
    aux = tuple(jax.ShapeDtypeStruct(s, jnp.float32) for s in aux_shapes)
    fn, _, _, _ = graph_eval_fn(symbol, True, scan=scan_plan(symbol))

    def step(args, aux, key):
        (outs, new_aux), vjp = jax.vjp(lambda a: fn(a, aux, key), args)
        grads, = vjp((tuple(jnp.ones_like(o) for o in outs),
                      tuple(jnp.zeros_like(a) for a in new_aux)))
        return new_aux, grads
    counters = [obs.counter("scan.remat." + c) for c in ("kept",
                                                         "kept_bytes")]
    before = [c.value for c in counters]
    text = jax.jit(step).lower(
        args, aux, jax.ShapeDtypeStruct((2,), jnp.uint32)).compile().as_text()
    return text, [c.value - b for c, b in zip(counters, before)]


def _kernel_calls(text, kernel):
    """The `op_name` of every Mosaic custom call of `kernel`."""
    return [m.group(1) for m in re.finditer(
        r'custom_call_target="tpu_custom_call"[^\n]*?op_name="([^"]*)"', text)
        if "/%s/" % kernel in m.group(1)]


def test_flash_forward_runs_once_a_scanned_run():
    text, (kept, kept_bytes) = _compiled("sdar_30b_a3b_chat")
    forward = _kernel_calls(text, "flash_attention_fwd")
    backward = _kernel_calls(text, "flash_attention_bwd")
    assert len(forward) == 1 and len(backward) == 1, (forward, backward)
    assert "rematted_computation" not in forward[0]
    # o (bfloat16) and the log-sum-exp (float32) of 2 x 8,192 rows x 32
    # heads of 128, over the four layers
    assert kept == 2
    assert kept_bytes == 4 * 2 * 8192 * 32 * (128 * 2 + 4) == 545259520
    # the experts name nothing: their forward kernel is in both bodies
    assert sum("rematted_computation" in name for name in
               _kernel_calls(text, "routed_experts_fwd")) == 1


def test_delta_rule_forward_sweep_as_the_chip_decided():
    """ISSUE 37, item 4: the delta rule's declaration stands or goes by
    `qwen3_next_train_hostfed` on the chip; this holds the program to what
    was decided (PERF.md section 6, PR 37)."""
    text, (kept, kept_bytes) = _compiled("qwen3_next_80b_a3b")
    forward = _kernel_calls(text, "gated_delta_rule_fwd")
    assert len(_kernel_calls(text, "gated_delta_rule_bwd")) == 1
    assert len(forward) == 1 and "rematted_computation" not in forward[0]
    # o bfloat16, a float32 state a chunk of 64 and a float32 inverse a
    # block of two chunks, 32 value heads of 128 x 128, over three layers
    o = 2 * 4096 * 32 * 128 * 2
    states = 2 * 32 * 64 * 128 * 128 * 4
    tinvs = 2 * 32 * 32 * 128 * 128 * 4
    assert kept == 3 and kept_bytes == 3 * (o + states + tinvs) == 1409286144
    # the softmax layer stands inlined: its forward kernel runs once anyway
    flash = _kernel_calls(text, "flash_attention_fwd")
    assert len(flash) == 1 and "rematted_computation" not in flash[0]
