"""What the looped LM's cell adds to the benchmark, without a chip: the
configuration file against the published row, the hand counts behind
`mfu_pct.tok` and the two roofline shares of `ouro_2_6b`, the three new
readers on hand-made splits and maps, and the limits file.  (The cell's
whole run at the `tiny` size, its planted faults and its control are cases
of test_rehearsal.py and test_faults.py, which take every cell of
BENCHMARK.json.)"""
import json
import os

from benchmark.harness import cells, flops, peaks

CELL = "ouro_loop_train_hostfed"
CONFIG = "ouro_2_6b"
# config.json of ByteDance/Ouro-2.6B, as the model-configs catalog holds it
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
    "max_position_embeddings": 65536, "max_window_layers": 48,
    "model_type": "ouro", "num_attention_heads": 16,
    "num_hidden_layers": 48, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False, "vocab_size": 49152}


def _cell(tiny_size=False):
    return cells.Cell(cells.benchmark_json(), CELL, tiny=tiny_size)


def test_configuration_keeps_every_published_width():
    bench = cells.benchmark_json()
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    with open(os.path.join(cells.ROOT, entry["file"])) as f:
        cfg = json.load(f)
    changed = {k for k, v in PUBLISHED.items() if cfg.get(k, "absent") != v}
    assert changed == set(entry["reduced"]) == {"num_hidden_layers"}
    assert cfg["published"] == {"num_hidden_layers": 48}
    # period 1, no dense layer in front: the guide's floor of four layers
    assert cfg["num_hidden_layers"] == 4
    assert entry["source"] == cfg["source"] and cfg["name"] == CONFIG
    for key in ("num_hidden_layers", "exit_entropy_weight", "exit_gate",
                "sandwich_norms", "carried_state", "objective_per_position",
                "weights", "optimizer", "learning_rate", "seq_len",
                "fused_step_block", "data"):
        assert cfg["assumed"][key], key
    assert cfg["seq_len"] == 4096 and cfg["fused_step_block"] == 2
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "train_hostfed_tokens_b2"
    assert cfg["rate"]["per_row"] == cfg["seq_len"]
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200


def test_flops_per_token_by_hand():
    cell = _cell()
    c = cell.adapter.flops_per_sample(cell.cfg, flops)
    # a layer application: q, k, v, o 2048 x 2048, the SwiGLU's three
    # products 2048 x 5632: 51.4 M multiply-adds
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert layer == 51_380_224
    # scores and values over (L + 1) / 2 keys on average, 16 heads of 128
    attn = 2 * 16 * 128 * 4097 / 2
    head = 49152 * 2048 + 2048
    assert c.forward_macs == 16 * (layer + attn) + 4 * head
    # 2.718 GFLOP a token forward, 8.157 trained
    assert abs(2 * c.forward_macs / 1e9 - 2.718) < 1e-3
    assert c.train_flops == 6 * c.forward_macs
    # 407 M parameters: four layers, embedding, head, gate
    assert c.param_bytes_f32 // 4 == 4 * layer + 2 * 49152 * 2048 + 2048


def test_kernel_work_by_hand():
    cell = _cell()
    work = cell.adapter.kernel_work(cell.cfg, 8192)
    ops, nbytes = work["BlockwiseAttention"]
    entries = 2 * 4096 * 4097 / 2
    assert ops == 16 * entries * 16 * 128 * 2 * 6
    assert nbytes == 16 * 8192 * 2 * (4 * 2048 + 4 * 2048)
    ops, nbytes = work["LoopExitOutput"]
    # four heads: 6.60 TFLOP forward, 13.2 backward
    assert ops == 4 * 8192 * 49152 * 2048 * 2 * 3
    v5e = peaks.of("TPU v5 lite")
    least = max(ops / v5e["flops_bf16"], nbytes / v5e["hbm_bytes_per_s"])
    assert abs(least * 1e3 - 100.46) < 0.01, least * 1e3
    assert ops / v5e["flops_bf16"] > nbytes / v5e["hbm_bytes_per_s"]


def test_new_metrics_are_declared_for_the_new_cell():
    bench = cells.benchmark_json()
    declared = {m["name"]: m for m in bench["per_layer"]}
    new = [("loop_device_ms.tok", "ms", "device_trace", "fused step"),
           ("loop_head_device_ms.tok", "ms", "device_trace", "fused step"),
           ("loop_head_roofline_pct.tok", "%", "device_trace", "kernels")]
    # appended after what the benchmark had (later cells may follow)
    names = [m["name"] for m in bench["per_layer"]]
    assert names.index("loop_device_ms.tok") > names.index("epoch_end_ms.tok")
    for name, unit, source, layer in new:
        m = declared[name]
        assert m["workloads"][0] == CELL and m["moves"] == "tokens_per_s"
        assert (m["unit"], m["source"], m["layer"]) == (unit, source, layer)
    cells_now = [w["name"] for w in bench["workloads"]]
    assert cells_now.index(CELL) > cells_now.index("sdar_moe_bd_train_hostfed")
    for name in ("mfu_pct.tok", "step_device_ms.tok", "attn_device_ms.tok",
                 "attn_roofline_pct.tok", "attn_tiles_skipped_pct.tok",
                 "fc_device_ms.tok",
                 "program_build_s", "setup_import_s", "epoch_end_ms.tok"):
        assert CELL in declared[name]["workloads"], name
    for name in ("gdn_device_ms.tok", "moe_device_ms.tok",
                 "conv_device_ms.tok", "noise_device_ms.tok"):
        assert CELL not in declared[name]["workloads"]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["tokens_per_s"]["workloads"]
    reported = {m["name"] for m in _cell().per_layer}
    assert {n for n, *_ in new} | {"mfu_pct.tok", "attn_device_ms.tok",
                                   "fc_device_ms.tok"} <= reported


def test_head_readers_on_a_hand_made_split():
    """2 steps; LoopExitOutput 150 ms forward and 250 ms backward in all:
    200 ms a step against kernel_work's least 100.46 ms."""
    cell = _cell()
    ms = 1e6
    split = {"by_kind": {("LoopExitOutput", "fwd"): 150 * ms,
                         ("LoopExitOutput", "bwd"): 250 * ms,
                         ("BlockwiseAttention", "bwd"): 9 * ms}}
    ctx = {"scope_join": split, "steps": 2, "cfg": cell.cfg,
           "traffic": cell.traffic, "peaks": peaks.of("TPU v5 lite"),
           "spans": []}
    assert cells.reader("loop_head_device_ms.tok")(ctx) == 200.0
    assert abs(cells.reader("loop_head_roofline_pct.tok")(ctx)
               - 100 * 100.46 / 200.0) < 0.01
    bare = dict(ctx, scope_join=None, peaks=None)
    assert cells.reader("loop_head_device_ms.tok")(bare) is None
    assert cells.reader("loop_head_roofline_pct.tok")(bare) is None
    # another configuration's adapter has no such kind: nothing, no raise
    other = cells.Cell(cells.benchmark_json(), "sdar_moe_bd_train_hostfed")
    assert cells.reader("loop_head_roofline_pct.tok")(
        dict(ctx, cfg=other.cfg, traffic=other.traffic)) is None


def test_loop_reader_on_a_hand_made_map(monkeypatch):
    """The instructions whose scope names a loop, summed, a step; a map
    without the field (the parent's) and a program without a loop read
    nothing."""
    import incubator_mxnet_tpu as mx
    maps = {"train": {
        "fusion.1": {"phase": "fwd", "op": "FullyConnected", "node": "a",
                     "loop": "lm_loop1", "mixed": False},
        "fusion.2": {"phase": "bwd", "op": "BlockwiseAttention", "node": "b",
                     "loop": "lm_loop1", "mixed": False},
        "fusion.3": {"phase": "bwd", "op": "LoopExitOutput", "node": "c",
                     "loop": None, "mixed": False}}}
    monkeypatch.setattr(mx.compile, "op_scopes",
                        lambda label=None: maps.get(label, {}))
    by_op = {"fusion.1": 30e6, "fusion.2": 50e6, "fusion.3": 20e6,
             "copy.9": 1e6}
    ctx = {"trace": {"per_chip": [{"busy_ns": 101e6, "by_op": by_op}]},
           "steps": 4, "programs": [{"label": "train"}]}
    read = cells.reader("loop_device_ms.tok")
    assert read(ctx) == 20.0
    for entry in maps["train"].values():
        entry.pop("loop")
    assert read(ctx) is None
    assert read(dict(ctx, trace=None)) is None


def test_every_limit_lies_between_its_two_readings():
    from benchmark.harness import compare
    with open(os.path.join(cells.BENCH_DIR, "limits", CELL + ".json")) as f:
        doc = json.load(f)
    limits, readings = doc["limits"], doc["set_from"]
    # dw_gap and mom_gap hold the worst leaf, the exit gate's weight
    assert {"out0_gap", "dw_gap", "dw_wide_gap", "dw_med_gap", "mom_gap",
            "mom_med_gap", "aux_med_gap"} <= set(limits)
    for name, limit in limits.items():
        assert readings[name]["lower"] < limit < readings[name]["upper"], name
    sound = {n: (readings[n]["lower"], "hand") for n in limits}
    assert compare.judge(sound, limits)[0]
