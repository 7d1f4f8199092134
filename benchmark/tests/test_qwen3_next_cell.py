"""What PR 30 adds to the benchmark, without a chip: the hand counts behind
`mfu_pct.tok` and the two roofline shares of `qwen3_next_80b_a3b`, the
configuration file against the published row, the readers of the new
per-layer metrics on a hand-made split, and a sabotage of its own -- the
delta rule's decay left out of the program -- that `correct` must catch.
(The cell's whole run at the `tiny` size, its planted faults and its control
are cases of test_rehearsal.py and test_faults.py, which take every cell of
BENCHMARK.json.)"""
import json
import os
import subprocess
import sys

import pytest

import tiny
from benchmark.harness import cells, flops, peaks

CELL = "qwen3_next_train_hostfed"
CONFIG = "qwen3_next_80b_a3b"
# config.json of Qwen/Qwen3-Next-80B-A3B-Instruct, as the model-configs
# catalog holds it
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512,
    "num_experts_per_tok": 10, "num_hidden_layers": 48,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}


def _cell(tiny_size=False):
    return cells.Cell(cells.benchmark_json(), CELL, tiny=tiny_size)


def test_configuration_keeps_every_published_width():
    bench = cells.benchmark_json()
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    with open(os.path.join(cells.ROOT, entry["file"])) as f:
        cfg = json.load(f)
    changed = {k for k, v in PUBLISHED.items() if cfg.get(k) != v}
    assert changed == set(entry["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert cfg["published"] == {k: PUBLISHED[k] for k in entry["reduced"]}
    # a whole period, at least 8 experts a layer, an eighth of the rows
    assert cfg["num_hidden_layers"] == cfg["full_attention_interval"] == 4
    held = cfg["experts_held"]
    assert held["count"] == cfg["num_experts"] == 16 and held["of"] == 512
    assert held["chips_per_layer"] * held["count"] == held["of"]
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert entry["source"] == cfg["source"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "train_hostfed_tokens_b2"
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200


def test_flops_per_token_by_hand():
    cell = _cell()
    c = cell.adapter.flops_per_sample(cell.cfg, flops)
    # a delta-rule layer: 2048 x (12288 + 64) + 4096 x 2048 in projections,
    # 8192 x 4 in the convolution, 3 x 128 x 128 x 32 in the recurrence
    gdn = 2048 * 12352 + 4096 * 2048 + 8192 * 4 + 3 * 128 * 128 * 32
    # the softmax layer: 2048 x (8192 + 2 x 512) + 4096 x 2048, and scores
    # and values over a mean causal length of 2048.5 for 16 heads of 256
    attn = 2048 * 9216 + 4096 * 2048 + 2 * 16 * 256 * 4097 // 2
    # router 512, shared expert 3 x 512 and its gate, 10 x 16 / 512 routed
    moe = 2048 * 512 + 3 * 2048 * 512 + 2048 + 3 * 2048 * 512 * 10 * 16 // 512
    assert c.forward_macs == 3 * gdn + attn + 4 * moe + 2048 * 18992 \
        == 209_530_880
    assert c.train_flops == 6 * 209_530_880          # 1.257 GFLOP a token
    assert c.param_bytes_f32 // 4 == 424_321_024     # 424 M parameters


def test_kernel_work_by_hand():
    cell = _cell()
    work = cell.adapter.kernel_work(cell.cfg, 8192)
    ops, nbytes = work["GatedDeltaRule"]
    assert ops == 8192 * 3 * 6 * 3 * 128 * 128 * 32
    # per token and layer: q, k (2048 each) and v (4096) in bfloat16, g and
    # beta (32 each) in float32, read in both passes and their gradients
    # written; o (4096, bfloat16) written, its gradient read
    ins = 2 * 8192 + 4 * 64
    assert nbytes == 8192 * 3 * (3 * ins + 2 * 2 * 4096)
    ops, nbytes = work["RoutedExperts"]
    rows = 8192 * 10 * 16 / 512
    assert ops == 4 * 6 * rows * 3 * 2048 * 512
    assert nbytes == 4 * (5 * rows * 2 * 2048 + 3 * 2 * 16 * 3 * 2048 * 512)
    # far under the step: neither share can reach 100 % unless its kind's
    # measured time falls under 2.0 and 1.8 ms a step
    v5e = peaks.of("TPU v5 lite")
    for kind, least_ms in (("GatedDeltaRule", 1.99), ("RoutedExperts", 1.74)):
        ops, nbytes = work[kind]
        least = max(ops / v5e["flops_bf16"], nbytes / v5e["hbm_bytes_per_s"])
        assert abs(least * 1e3 - least_ms) < 0.01


def test_new_metrics_are_declared_for_the_new_cell_alone():
    bench = cells.benchmark_json()
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name, unit in (("gdn_device_ms.tok", "ms"),
                       ("attn_device_ms.tok", "ms"),
                       ("moe_device_ms.tok", "ms"),
                       ("gdn_roofline_pct.tok", "%"),
                       ("moe_grouped_roofline_pct.tok", "%"),
                       ("moe_load_max_over_mean.tok", "ratio")):
        m = declared[name]
        assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
        assert m["unit"] == unit
    assert declared["program_build_s"]["workloads"][:2] == [
        "resnet50_train_hostfed", "lstm_ptb_train"]
    reported = {m["name"] for m in _cell().per_layer}
    assert {"mfu_pct.tok", "step_device_ms.tok", "device_idle_pct.tok",
            "program_build_s", "gdn_roofline_pct.tok"} <= reported
    assert not any(n.endswith(".img") for n in reported)


def test_kind_readers_on_a_hand_made_split():
    """2 steps; GatedDeltaRule 6 ms forward and 10 ms backward in all:
    8 ms a step; against kernel_work's least time for 8,192 tokens."""
    cell = _cell()
    ms = 1e6
    split = {"by_kind": {("GatedDeltaRule", "fwd"): 6 * ms,
                         ("GatedDeltaRule", "bwd"): 10 * ms,
                         ("RoutedExperts", "bwd"): 4 * ms,
                         ("FullyConnected", "fwd"): 50 * ms}}
    ctx = {"scope_join": split, "steps": 2, "cfg": cell.cfg,
           "traffic": cell.traffic, "peaks": peaks.of("TPU v5 lite"),
           "spans": [{"name": "moe.load",
                      "args": {"max": 900.0, "mean": 300.0}}]}
    assert cells.reader("gdn_device_ms.tok")(ctx) == 8.0
    assert cells.reader("moe_device_ms.tok")(ctx) == 2.0
    assert cells.reader("attn_device_ms.tok")(ctx) is None
    share = cells.reader("gdn_roofline_pct.tok")(ctx)
    assert abs(share - 100 * 1.9896 / 8.0) < 0.01
    assert abs(cells.reader("moe_grouped_roofline_pct.tok")(ctx)
               - 100 * 1.7310 / 2.0) < 0.01
    assert cells.reader("moe_load_max_over_mean.tok")(ctx) == 3.0
    # a program without the scopes, the span or the chip: nothing, no raise
    bare = {"scope_join": None, "steps": 2, "cfg": cell.cfg,
            "traffic": cell.traffic, "peaks": None, "spans": []}
    for name in ("gdn_device_ms.tok", "gdn_roofline_pct.tok",
                 "moe_grouped_roofline_pct.tok",
                 "moe_load_max_over_mean.tok"):
        assert cells.reader(name)(bare) is None


_CHILD = """
import json, sys, time
t0 = time.perf_counter()
sys.path.insert(0, {root!r})
import jax.numpy as jnp
from incubator_mxnet_tpu.ops import registry
from benchmark.harness import runner
gates = registry.get("GatedDeltaGates")
sound = gates.fn


def no_decay(params, a, b, a_log, dt_bias):
    g, beta = sound(params, a, b, a_log, dt_bias)
    return jnp.zeros_like(g), beta


gates.fn = no_decay
sys.exit(runner.run({cell!r}, 7, 1.0, False, t0, tiny=True))
"""


def test_delta_rule_without_its_decay_is_not_correct():
    """The program's gated delta rule with alpha = 1 everywhere (g = 0): a
    plain delta rule, every other number of the run sound."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false")
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD.format(root=tiny.ROOT, cell=CELL)],
        env=env, cwd=tiny.ROOT, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is False, result["compared"]
    over = [n for n, c in result["compared"].items()
            if c["limit"] is not None and c["value"] > c["limit"]]
    assert over and not set(over) & {"unfused", "fallbacks",
                                     "compiles_in_window"}, over
