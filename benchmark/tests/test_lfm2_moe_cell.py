"""What PR 34 adds to the benchmark, without a chip: the configuration file
against the published row, the hand counts behind `mfu_pct.tok` and the two
roofline shares of `lfm2_24b_a2b`, the three new readers on a hand-made
split, and a sabotage of its own -- the gated short convolution with its
output gate C left out of the program -- that `correct` must catch.  (The
cell's whole run at the `tiny` size, its planted faults and its control are
cases of test_rehearsal.py and test_faults.py, which take every cell of
BENCHMARK.json.)"""
import json
import os
import subprocess
import sys

import tiny
from benchmark.harness import cells, flops, peaks

CELL = "lfm2_moe_train_hostfed"
CONFIG = "lfm2_24b_a2b"
_PERIOD = ["full_attention", "conv", "conv", "conv"]
# config.json of LiquidAI/LFM2-24B-A2B, as the model-configs catalog holds it
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776,
    "layer_types": ["conv", "conv"] + _PERIOD * 9 + ["full_attention",
                                                      "conv"],
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536}
REDUCED = {"num_hidden_layers", "num_dense_layers", "layer_types",
           "num_experts", "vocab_size"}


def _cell(tiny_size=False):
    return cells.Cell(cells.benchmark_json(), CELL, tiny=tiny_size)


def test_configuration_keeps_every_published_width():
    bench = cells.benchmark_json()
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    with open(os.path.join(cells.ROOT, entry["file"])) as f:
        cfg = json.load(f)
    assert len(PUBLISHED["layer_types"]) == 40
    changed = {k for k, v in PUBLISHED.items() if cfg.get(k) != v}
    assert changed == set(entry["reduced"]) == REDUCED
    # each reduced key stated with its published value
    assert cfg["published"] == {k: PUBLISHED[k] for k in entry["reduced"]}
    # published layers 1 to 5: one leading dense layer (they count once) and
    # one whole period of routed layers, at least four of them
    assert cfg["layer_types"] == PUBLISHED["layer_types"][1:6] == \
        ["conv"] + _PERIOD
    assert cfg["num_hidden_layers"] == 5 and cfg["num_dense_layers"] == 1
    assert cfg["num_hidden_layers"] - cfg["num_dense_layers"] >= 4
    held = cfg["experts_held"]
    assert held["count"] == cfg["num_experts"] == 8 and held["of"] == 64
    assert held["chips_per_layer"] * held["count"] == held["of"]
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert entry["source"] == cfg["source"] and cfg["name"] == CONFIG
    assert cfg["tie_word_embeddings"] is True
    for key in ("optimizer", "learning_rate", "tie_word_embeddings",
                "expert_bias", "weights", "data", "seq_len"):
        assert cfg["assumed"][key], key
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "train_hostfed_tokens_b2"
    assert cfg["rate"]["per_row"] == cfg["seq_len"]
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200


def test_every_limit_lies_between_its_two_readings_and_holds_the_loss():
    """Each held number's limit lies between the program's largest reading
    and the least of the control and faults that count; `loss_gap`, the one
    number that holds the loss at the block's end, passes the program's
    largest reading and fails its upper reading (the float8 control's since
    lr 1e-7; a state left unchanged's at 1e-6), through `judge`."""
    from benchmark.harness import compare
    with open(os.path.join(cells.BENCH_DIR, "limits", CELL + ".json")) as f:
        doc = json.load(f)
    limits, readings = doc["limits"], doc["set_from"]
    assert "loss_gap" in limits and "loss_gap" not in readings["not_compared"]
    for name, limit in limits.items():
        assert readings[name]["lower"] < limit < readings[name]["upper"], name
    sound = {n: (readings[n]["lower"], "hand") for n in limits}
    assert compare.judge(sound, limits)[0]
    loss_off = dict(sound, loss_gap=(readings["loss_gap"]["upper"], "step 7"))
    ok, compared = compare.judge(loss_off, limits)
    assert not ok
    assert [n for n, c in compared.items() if c["value"] > c["limit"]] == \
        ["loss_gap"]


def test_flops_per_token_by_hand():
    cell = _cell()
    assert cell.cfg["seq_len"] == 8192
    c = cell.adapter.flops_per_sample(cell.cfg, flops)
    # a conv layer's mixer: 2048 x 6144 in, 2048 x 2048 out, 3 taps and two
    # gates an entry
    conv = 2048 * 6144 + 2048 * 2048 + 2048 * 5
    # the softmax layer: q and o 2048 x 2048, k and v 2048 x 512, and scores
    # and values over a mean causal length of 4096.5 for 32 heads of 64
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 32 * 64 * 8193 // 2
    dense = 3 * 2048 * 11776
    # router 64, and 4 x 8 / 64 of an expert of width 1536 a token
    moe = 2048 * 64 + 3 * 2048 * 1536 * 4 * 8 // 64
    assert c.forward_macs == 4 * conv + attn + dense + 4 * moe + \
        2048 * 8192 == 202_942_464
    assert c.train_flops == 6 * 202_942_464          # 1.218 GFLOP a token
    # 469 M parameters, the tied matrix once
    params = 4 * (2048 * 6144 + 2048 * 2048 + 2048 * 3) + \
        2 * 2048 * 2048 + 2 * 2048 * 512 + dense + \
        4 * (2048 * 64 + 8 * 3 * 2048 * 1536) + 2048 * 8192
    assert c.param_bytes_f32 // 4 == params == 469_262_336


def test_kernel_work_by_hand():
    cell = _cell()
    work = cell.adapter.kernel_work(cell.cfg, 16384)
    ops, nbytes = work["RoutedExperts"]
    rows = 16384 * 4 * 8 / 64              # 8,192 rows a layer
    assert rows == 8192
    assert ops == 4 * 6 * rows * 3 * 2048 * 1536
    assert nbytes == 4 * (5 * rows * 2 * 2048 + 3 * 2 * 8 * 3 * 2048 * 1536)
    ops, nbytes = work["GatedShortConv"]
    # per token and layer, bfloat16: forward reads 3 x 2048 and writes 2048;
    # backward reads 3 x 2048 and the output's gradient, writes 3 x 2048
    assert nbytes == 4 * 16384 * 2 * 2048 * 11
    assert ops == 4 * 16384 * 3 * 2 * 2048 * 5
    # far under the step: neither share can reach 100 % unless its kind's
    # measured time falls under 9.4 and 3.6 ms a step; the experts are bound
    # by the products at this width, the convolution by bytes
    v5e = peaks.of("TPU v5 lite")
    for kind, least_ms in (("RoutedExperts", 9.42), ("GatedShortConv", 3.61)):
        ops, nbytes = work[kind]
        least = max(ops / v5e["flops_bf16"], nbytes / v5e["hbm_bytes_per_s"])
        assert abs(least * 1e3 - least_ms) < 0.01
    ops, nbytes = work["RoutedExperts"]
    assert ops / v5e["flops_bf16"] > nbytes / v5e["hbm_bytes_per_s"]
    ops, nbytes = work["GatedShortConv"]
    assert ops / v5e["flops_bf16"] < nbytes / v5e["hbm_bytes_per_s"]


def test_new_metrics_are_declared_for_the_new_cell_alone():
    bench = cells.benchmark_json()
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name, unit in (("conv_device_ms.tok", "ms"),
                       ("conv_roofline_pct.tok", "%"),
                       ("fc_device_ms.tok", "ms")):
        m = declared[name]
        # of the cells the benchmark had, none: a later cell is appended
        assert m["workloads"][0] == CELL and m["moves"] == "tokens_per_s"
        assert m["unit"] == unit and m["source"] == "device_trace"
    # what the benchmark had stands in front of what this PR appended
    cells_before = ["resnet50_train_hostfed", "lstm_ptb_train",
                    "qwen3_next_train_hostfed"]
    assert [w["name"] for w in bench["workloads"]][:4] == \
        cells_before + [CELL]
    for name in ("mfu_pct.tok", "step_device_ms.tok", "attn_device_ms.tok",
                 "moe_device_ms.tok", "moe_grouped_roofline_pct.tok",
                 "moe_load_max_over_mean.tok"):
        listed = declared[name]["workloads"]
        assert listed.index(CELL) == listed.index(cells_before[2]) + 1, name
    for name in ("gdn_device_ms.tok", "gdn_roofline_pct.tok"):
        assert CELL not in declared[name]["workloads"]
    tokens = next(m for m in bench["end_to_end"]
                  if m["name"] == "tokens_per_s")
    assert CELL in tokens["workloads"]
    reported = {m["name"] for m in _cell().per_layer}
    assert {"mfu_pct.tok", "program_build_s", "conv_roofline_pct.tok",
            "fc_device_ms.tok"} <= reported
    assert "gdn_device_ms.tok" not in reported
    assert not any(n.endswith(".img") for n in reported)


def test_kind_readers_on_a_hand_made_split():
    """2 steps; GatedShortConv 6 ms forward and 10 ms backward in all: 8 ms
    a step, against kernel_work's least time for 16,384 tokens."""
    cell = _cell()
    ms = 1e6
    split = {"by_kind": {("GatedShortConv", "fwd"): 6 * ms,
                         ("GatedShortConv", "bwd"): 10 * ms,
                         ("RoutedExperts", "bwd"): 40 * ms,
                         ("FullyConnected", "fwd"): 50 * ms,
                         ("FullyConnected", "bwd"): 150 * ms}}
    ctx = {"scope_join": split, "steps": 2, "cfg": cell.cfg,
           "traffic": cell.traffic, "peaks": peaks.of("TPU v5 lite"),
           "spans": []}
    assert cells.reader("conv_device_ms.tok")(ctx) == 8.0
    assert cells.reader("fc_device_ms.tok")(ctx) == 100.0
    assert cells.reader("moe_device_ms.tok")(ctx) == 20.0
    assert abs(cells.reader("conv_roofline_pct.tok")(ctx)
               - 100 * 3.6054 / 8.0) < 0.01
    assert abs(cells.reader("moe_grouped_roofline_pct.tok")(ctx)
               - 100 * 9.4184 / 20.0) < 0.01
    # a program without the scopes or the chip: nothing, no raise
    bare = {"scope_join": None, "steps": 2, "cfg": cell.cfg,
            "traffic": cell.traffic, "peaks": None, "spans": []}
    for name in ("conv_device_ms.tok", "conv_roofline_pct.tok",
                 "fc_device_ms.tok"):
        assert cells.reader(name)(bare) is None


_CHILD = """
import json, sys, time
t0 = time.perf_counter()
sys.path.insert(0, {root!r})
import jax.numpy as jnp
from incubator_mxnet_tpu.ops import lm_ops, registry
from benchmark.harness import runner
conv = registry.get("GatedShortConv")


def no_output_gate(params, bcx, weight):
    c = weight.shape[0]
    return lm_ops.causal_conv1d(bcx[..., :c] * bcx[..., 2 * c:], weight)


conv.fn = no_output_gate
sys.exit(runner.run({cell!r}, 7, 1.0, False, t0, tiny=True))
"""


def test_convolution_without_its_output_gate_is_not_correct():
    """The program's gated short convolution with C = 1 everywhere: a gated
    input and a plain convolution, every other number of the run sound."""
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
