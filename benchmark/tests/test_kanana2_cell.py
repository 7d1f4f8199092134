"""What the latent-attention cell adds to the benchmark, without a chip: the
configuration file against the published row, the hand counts behind
`mfu_pct.tok` and `attn_roofline_pct.tok` of `kanana2_30b_a3b`, the
`mla_device_ms` reader on hand-made maps, and the limits file.  (The cell's
whole run at the `tiny` size, its planted faults and its control are cases
of test_rehearsal.py and test_faults.py, which take every cell of
BENCHMARK.json.)"""
import json
import os

from benchmark.harness import cells, flops, peaks

CELL = "kanana2_mla_train_hostfed"
CONFIG = "kanana2_30b_a3b"
# config.json of kakaocorp/kanana-2-30b-a3b-instruct-2601, as the
# model-configs catalog holds it
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 48,
    "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
    "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 128256}


def _cell(tiny_size=False):
    return cells.Cell(cells.benchmark_json(), CELL, tiny=tiny_size)


def test_configuration_keeps_every_published_width():
    bench = cells.benchmark_json()
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    with open(os.path.join(cells.ROOT, entry["file"])) as f:
        cfg = json.load(f)
    changed = {k for k, v in PUBLISHED.items() if cfg.get(k, "absent") != v}
    assert changed == set(entry["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "n_routed_experts": 128, "vocab_size": 128256}
    # one dense layer and four routed ones; 8 of 128 experts, 16 chips a
    # layer; an eighth of the vocabulary
    assert cfg["num_hidden_layers"] == 5
    assert cfg["experts_held"] == {"offset": 0, "count": 8, "of": 128,
                                   "chips_per_layer": 16}
    assert cfg["n_routed_experts"] == cfg["experts_held"]["count"]
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert entry["source"] == cfg["source"] and cfg["name"] == CONFIG
    for key in ("n_routed_experts", "num_hidden_layers", "vocab_size",
                "seq_len", "rope_layout", "shared_experts", "expert_bias",
                "weights", "routing_seed", "optimizer", "learning_rate",
                "fused_step_block", "data"):
        assert cfg["assumed"][key], key
    assert cfg["seq_len"] == 8192 and cfg["rate"]["per_row"] == 8192
    assert cfg["fused_step_block"] == 4
    assert cfg["optimizer"]["multi_precision"] and \
        cfg["param_dtype"] == "bfloat16"
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "train_hostfed_tokens_b2"
    assert "75%" in cell["why"] and "1.5%" in cell["why"]
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200


def test_parameters_by_hand():
    """425 M parameters: 26.35 M of MLA a layer, the dense layer's SwiGLU,
    four routed layers' router, shared experts and 8 experts held, and the
    embedding and the head over an eighth of the vocabulary."""
    cell = _cell()
    c = cell.adapter.flops_per_sample(cell.cfg, flops)
    mla = 2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048
    assert mla == 26_345_472
    dense = mla + 3 * 2048 * 6144
    routed = mla + 2048 * 128 + 3 * 2048 * 1536 + 8 * 3 * 2048 * 768
    vocab = 2 * 16032 * 2048
    assert c.param_bytes_f32 // 4 == dense + 4 * routed + vocab
    assert abs((dense + 4 * routed + vocab) / 1e6 - 425.0) < 1.0


def test_flops_per_token_by_hand():
    cell = _cell()
    c = cell.adapter.flops_per_sample(cell.cfg, flops)
    mla = 26_345_472
    # scores 192 deep and values 128 wide over (L + 1) / 2 keys, 32 heads
    core = 32 * (192 + 128) * 8193 / 2
    assert abs(core / 1e6 - 41.9) < 0.05
    dense = mla + core + 3 * 2048 * 6144
    routed = mla + core + 2048 * 128 + 3 * 2048 * 1536 + \
        6 * 8 / 128 * 3 * 2048 * 768
    head = 2048 * 16032
    assert c.forward_macs == dense + 4 * routed + head
    # 458 M multiply-adds a token, 2.75 GFLOP trained; the MLA three
    # quarters of them, its attention core 46%, the routed experts 1.5%
    assert abs(c.forward_macs / 1e6 - 457.93) < 0.01
    assert c.train_flops == 6 * c.forward_macs
    assert abs(5 * (mla + core) / c.forward_macs - 0.746) < 0.005
    assert abs(5 * core / c.forward_macs - 0.458) < 0.005
    assert abs(4 * 6 * 8 / 128 * 3 * 2048 * 768 / c.forward_macs - 0.0155) \
        < 0.0005


def test_kernel_work_by_hand():
    cell = _cell()
    work = cell.adapter.kernel_work(cell.cfg, 16384)
    ops, nbytes = work["BlockwiseAttention"]
    entries = 2 * 8192 * 8193 / 2
    # the unpadded depths: 192 for the scores, 128 for the values
    assert ops == 5 * entries * 32 * (192 + 128) * 2 * 3
    assert nbytes == 5 * 16384 * 2 * 4 * 32 * (192 + 128)
    v5e = peaks.of("TPU v5 lite")
    least = max(ops / v5e["flops_bf16"], nbytes / v5e["hbm_bytes_per_s"])
    assert ops / v5e["flops_bf16"] > nbytes / v5e["hbm_bytes_per_s"]
    # 105 ms a step at the bf16 peak: the attention core, trained
    assert abs(least * 1e3 - 104.66) < 0.01, least * 1e3
    ops, nbytes = work["RoutedExperts"]
    rows = 16384 * 6 * 8 / 128
    assert ops == 4 * 6 * rows * 3 * 2048 * 768
    assert nbytes == 4 * (5 * rows * 2 * 2048 + 3 * 2 * 8 * 3 * 2048 * 768)


def test_metrics_are_declared_for_the_new_cell():
    bench = cells.benchmark_json()
    declared = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-1] == "mla_device_ms.tok"
    m = declared["mla_device_ms.tok"]
    assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
    assert (m["unit"], m["source"], m["layer"]) == \
        ("ms", "device_trace", "fused step")
    cells_now = [w["name"] for w in bench["workloads"]]
    assert cells_now[-1] == CELL
    for name in ("mfu_pct.tok", "step_device_ms.tok", "attn_device_ms.tok",
                 "attn_roofline_pct.tok", "fc_device_ms.tok",
                 "moe_device_ms.tok", "moe_grouped_roofline_pct.tok",
                 "moe_load_max_over_mean.tok", "program_build_s",
                 "setup_import_s", "setup_epoch_end_s", "epoch_end_ms.tok",
                 "ring_stall_pct.tok", "device_idle_pct.tok"):
        assert declared[name]["workloads"][-1] == CELL, name
    for name in ("gdn_device_ms.tok", "conv_device_ms.tok",
                 "noise_device_ms.tok", "loop_device_ms.tok"):
        assert CELL not in declared[name]["workloads"]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["tokens_per_s"]["workloads"][-1] == CELL
    assert "workloads" not in e2e["setup_s"]
    reported = {m["name"] for m in _cell().per_layer}
    assert {"mla_device_ms.tok", "mfu_pct.tok", "attn_roofline_pct.tok",
            "moe_grouped_roofline_pct.tok"} <= reported


def test_attention_roofline_on_a_hand_made_split():
    """2 steps; BlockwiseAttention 150 ms forward and 275 ms backward in all:
    212.5 ms a step against kernel_work's least 104.66 ms."""
    cell = _cell()
    ms = 1e6
    split = {"by_kind": {("BlockwiseAttention", "fwd"): 150 * ms,
                         ("BlockwiseAttention", "bwd"): 275 * ms,
                         ("RoutedExperts", "bwd"): 9 * ms}}
    ctx = {"scope_join": split, "steps": 2, "cfg": cell.cfg,
           "traffic": cell.traffic, "peaks": peaks.of("TPU v5 lite"),
           "spans": []}
    assert cells.reader("attn_device_ms.tok")(ctx) == 212.5
    v5e = peaks.of("TPU v5 lite")
    least = cell.adapter.kernel_work(cell.cfg, 16384)[
        "BlockwiseAttention"][0] / v5e["flops_bf16"]
    assert abs(cells.reader("attn_roofline_pct.tok")(ctx)
               - 100 * least * 1e3 / 212.5) < 1e-9


def test_mla_reader_on_a_hand_made_map(monkeypatch):
    """The instructions whose node is one the mixer names ``mla_*``,
    summed, a step: forward, backward, inside a scanned body alike; a map
    whose nodes carry no such name (another configuration's, or the
    parent's program) reads nothing."""
    import incubator_mxnet_tpu as mx
    maps = {"train": {
        "fusion.1": {"phase": "fwd", "op": "FullyConnected",
                     "node": "mla_q_proj", "loop": None, "mixed": False},
        "fusion.2": {"phase": "bwd", "op": "BlockwiseAttention",
                     "node": "mla_attention", "loop": None, "mixed": False},
        "fusion.3": {"phase": "bwd", "op": "FullyConnected", "node": "fwd",
                     "loop": None, "mixed": False},
        "fusion.4": {"phase": "fwd", "op": "RMSNorm", "node": "mla_kv_norm",
                     "loop": None, "mixed": False},
        "copy.5": {"phase": "other", "op": None, "node": None,
                   "loop": None, "mixed": False}}}
    monkeypatch.setattr(mx.compile, "op_scopes",
                        lambda label=None: maps.get(label, {}))
    by_op = {"fusion.1": 30e6, "fusion.2": 50e6, "fusion.3": 20e6,
             "fusion.4": 4e6, "copy.5": 1e6, "copy.9": 1e6}
    ctx = {"trace": {"per_chip": [{"busy_ns": 106e6, "by_op": by_op}]},
           "steps": 4, "programs": [{"label": "train"}]}
    read = cells.reader("mla_device_ms.tok")
    assert read(ctx) == 21.0
    for entry in maps["train"].values():
        if entry["node"] and entry["node"].startswith("mla_"):
            entry["node"] = "attention"
    assert read(ctx) is None
    assert read(dict(ctx, trace=None)) is None
    assert read(dict(ctx, steps=0)) is None


def test_every_limit_lies_between_its_two_readings():
    from benchmark.harness import compare
    with open(os.path.join(cells.BENCH_DIR, "limits", CELL + ".json")) as f:
        doc = json.load(f)
    limits, readings = doc["limits"], doc["set_from"]
    assert {"loss_gap", "out0_gap", "dw_wide_gap", "dw_med_gap",
            "mom_med_gap"} <= set(limits)
    for name, limit in limits.items():
        assert readings[name]["lower"] < limit < readings[name]["upper"], name
    sound = {n: (readings[n]["lower"], "hand") for n in limits}
    assert compare.judge(sound, limits)[0]
