"""What PR 36 adds to the benchmark, without a chip: the configuration file
against the published row, the hand counts behind `mfu_pct.tok` and the two
roofline shares of `sdar_30b_a3b_chat`, the three new readers on a
hand-made split and hand-made counters, the limits file, and the cell's own
fault -- the reference run with the causal mask over the 2L rows in place
of the block-diffusion one -- which `out0_gap` must catch under the limits
file.  (The cell's whole run at the `tiny` size, its planted faults and its
control are cases of test_rehearsal.py and test_faults.py, which take every
cell of BENCHMARK.json.)"""
import json
import os

import tiny
from benchmark.harness import cells, flops, peaks

CELL = "sdar_moe_bd_train_hostfed"
CONFIG = "sdar_30b_a3b_chat"
# config.json of JetLM/SDAR-30B-A3B-Chat, as the model-configs catalog
# holds it
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}
REDUCED = {"num_hidden_layers", "num_experts", "vocab_size"}


def _cell(tiny_size=False):
    return cells.Cell(cells.benchmark_json(), CELL, tiny=tiny_size)


def test_configuration_keeps_every_published_width():
    bench = cells.benchmark_json()
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    with open(os.path.join(cells.ROOT, entry["file"])) as f:
        cfg = json.load(f)
    changed = {k for k, v in PUBLISHED.items() if cfg.get(k, "absent") != v}
    assert changed == set(entry["reduced"]) == REDUCED
    assert cfg["published"] == {k: PUBLISHED[k] for k in entry["reduced"]}
    # period 1 and no dense layer in front: four consecutive layers
    assert cfg["num_hidden_layers"] == 4
    held = cfg["experts_held"]
    assert held["count"] == cfg["num_experts"] == 16 and held["of"] == 128
    assert held["chips_per_layer"] * held["count"] == held["of"]
    assert cfg["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert entry["source"] == cfg["source"] and cfg["name"] == CONFIG
    for key in ("block_length", "noise_interval", "noise_seed", "mask_token",
                "seq_len", "optimizer", "learning_rate", "weights",
                "routing_seed", "capacity_factor", "data"):
        assert cfg["assumed"][key], key
    assert cfg["block_length"] == 4 and cfg["noise_interval"] == [0.001, 1.0]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "train_hostfed_tokens_b2"
    # the rate counts CLEAN tokens
    assert cfg["rate"]["per_row"] == cfg["seq_len"] == 4096
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200


def test_every_limit_lies_between_its_two_readings():
    from benchmark.harness import compare
    with open(os.path.join(cells.BENCH_DIR, "limits", CELL + ".json")) as f:
        doc = json.load(f)
    limits, readings = doc["limits"], doc["set_from"]
    assert {"out0_gap", "dw_wide_gap", "dw_med_gap", "mom_med_gap"} <= \
        set(limits)
    for name, limit in limits.items():
        assert readings[name]["lower"] < limit < readings[name]["upper"], name
    sound = {n: (readings[n]["lower"], "hand") for n in limits}
    assert compare.judge(sound, limits)[0]


def test_flops_per_clean_token_by_hand():
    cell = _cell()
    c = cell.adapter.flops_per_sample(cell.cfg, flops)
    # a layer's products for the TWO rows of a clean token: q and o 2048 x
    # 4096, k and v 2048 x 512, the router's 128 outputs
    proj = 2 * (2 * 2048 * 4096 + 2 * 2048 * 512 + 2048 * 128)
    # scores and values over the mask's L + B = 4,100 entries a clean token
    # for 32 heads of 128
    attn = 2 * 32 * 128 * 4100
    # 8 x 16 / 128 = 1 assignment a row to an expert of width 768 held here
    moe = 2 * 3 * 2048 * 768
    assert cell.adapter.mask_entries(cell.cfg) == 4096 * 4100
    assert c.forward_macs == 4 * (proj + attn + moe) + 2048 * 18992 == \
        364_085_248
    assert c.train_flops == 6 * 364_085_248        # 2.185 GFLOP a clean token
    # 456 M parameters: the embedding with the mask token's row
    params = 4 * (2 * 2048 * 4096 + 2 * 2048 * 512 + 2048 * 128 +
                  16 * 3 * 2048 * 768) + 2048 * 18992 + 2048 * 18993
    assert c.param_bytes_f32 // 4 == params == 456_329_216


def test_kernel_work_by_hand():
    cell = _cell()
    work = cell.adapter.kernel_work(cell.cfg, 8192)
    ops, nbytes = work["RoutedExperts"]
    rows = 2 * 8192 * 8 * 16 / 128          # 16,384 rows a layer
    assert rows == 16384
    assert ops == 4 * 6 * rows * 3 * 2048 * 768
    assert nbytes == 4 * (5 * rows * 2 * 2048 + 3 * 2 * 16 * 3 * 2048 * 768)
    ops, nbytes = work["BlockwiseAttention"]
    # two sequences; a quarter of the square's entries; 6 products of 128
    entries = 4096 * 4096 + 4096 * 4
    assert entries / (8192 * 8192) < 0.2503
    assert ops == 4 * 2 * entries * 32 * 128 * 2 * 6
    # q, do, o, dq: 32 heads of 128; k, v, dk, dv: 4 heads; 16,384 rows
    assert nbytes == 4 * 16384 * 2 * (4 * 4096 + 4 * 512)
    v5e = peaks.of("TPU v5 lite")
    for kind, least_ms in (("RoutedExperts", 9.42), ("BlockwiseAttention",
                                                     33.52)):
        ops, nbytes = work[kind]
        least = max(ops / v5e["flops_bf16"], nbytes / v5e["hbm_bytes_per_s"])
        assert abs(least * 1e3 - least_ms) < 0.01, least * 1e3
        # both bound by their products
        assert ops / v5e["flops_bf16"] > nbytes / v5e["hbm_bytes_per_s"]


def test_new_metrics_are_declared_for_the_new_cell_alone():
    bench = cells.benchmark_json()
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name, unit, source, layer in (
            ("attn_roofline_pct.tok", "%", "device_trace", "kernels"),
            ("attn_tiles_skipped_pct.tok", "%", "program_counter",
             "kernels"),
            ("noise_device_ms.tok", "ms", "device_trace", "fused step")):
        m = declared[name]
        assert m["workloads"][0] == CELL and m["moves"] == "tokens_per_s"
        assert (m["unit"], m["source"], m["layer"]) == (unit, source, layer)
    # what the benchmark had stands in front of what this PR appended
    before = ["resnet50_train_hostfed", "lstm_ptb_train",
              "qwen3_next_train_hostfed", "lfm2_moe_train_hostfed"]
    assert [w["name"] for w in bench["workloads"]][:5] == before + [CELL]
    assert [m["name"] for m in bench["per_layer"]][-3:] == [
        "attn_roofline_pct.tok", "attn_tiles_skipped_pct.tok",
        "noise_device_ms.tok"]
    for name in ("mfu_pct.tok", "step_device_ms.tok", "attn_device_ms.tok",
                 "moe_device_ms.tok", "moe_grouped_roofline_pct.tok",
                 "moe_load_max_over_mean.tok", "fc_device_ms.tok",
                 "program_build_s"):
        assert declared[name]["workloads"][-1] == CELL, name
    for name in ("gdn_device_ms.tok", "gdn_roofline_pct.tok",
                 "conv_device_ms.tok", "conv_roofline_pct.tok"):
        assert CELL not in declared[name]["workloads"]
    reported = {m["name"] for m in _cell().per_layer}
    assert {"mfu_pct.tok", "attn_roofline_pct.tok",
            "attn_tiles_skipped_pct.tok", "noise_device_ms.tok"} <= reported
    assert not any(n.endswith(".img") for n in reported)


def test_new_readers_on_a_hand_made_split_and_counters():
    """2 steps; BlockwiseAttention 40 ms forward and 100 ms backward in
    all: 70 ms a step, against kernel_work's least time for 8,192 clean
    tokens; the noise 1 ms in all."""
    import incubator_mxnet_tpu as mx
    cell = _cell()
    ms = 1e6
    split = {"by_kind": {("BlockwiseAttention", "fwd"): 40 * ms,
                         ("BlockwiseAttention", "bwd"): 100 * ms,
                         ("BlockDiffusionNoise", "fwd"): 1 * ms}}
    ctx = {"scope_join": split, "steps": 2, "cfg": cell.cfg,
           "traffic": cell.traffic, "peaks": peaks.of("TPU v5 lite"),
           "spans": []}
    assert cells.reader("noise_device_ms.tok")(ctx) == 0.5
    assert abs(cells.reader("attn_roofline_pct.tok")(ctx)
               - 100 * 33.5204 / 70.0) < 0.01
    # a program without the scopes or the chip, and another configuration's
    # adapter (no such kind in its kernel_work): nothing, no raise
    bare = {"scope_join": None, "steps": 2, "cfg": cell.cfg,
            "traffic": cell.traffic, "peaks": None, "spans": []}
    assert cells.reader("attn_roofline_pct.tok")(bare) is None
    assert cells.reader("noise_device_ms.tok")(bare) is None
    other = cells.Cell(cells.benchmark_json(), "lfm2_moe_train_hostfed")
    assert cells.reader("attn_roofline_pct.tok")(
        dict(ctx, cfg=other.cfg, traffic=other.traffic)) is None
    # the counters: 30 lane tiles run whole, 10 masked, 88 skipped
    names = ("run", "masked", "skipped")
    counters = [mx.obs.counter("ops.attention.tiles." + n) for n in names]
    was = [c.value for c in counters]
    read = cells.reader("attn_tiles_skipped_pct.tok")
    if not sum(was):
        # no kernel call yet in this process: 0 if XLA's form was lowered
        # (another test's), nothing on a program that lowered neither
        xla, kernel = (mx.obs.counter("ops.attention.lowered." + n).value
                       for n in ("xla", "kernel"))
        assert read({}) == (0.0 if xla and not kernel else None)
    for c, by in zip(counters, (30, 10, 88)):
        c.inc(by)
    now = [c.value for c in counters]
    assert abs(read({}) - 100.0 * now[2] / sum(now)) < 1e-9


def test_reference_with_the_causal_mask_fails_out0_gap():
    """The cell's own fault (the harness hands the reference `numerics`
    and `rows` only): the reference's `visible` replaced by the causal mask
    over the 2L rows, read against the sound reference as control.py reads
    a fault, under the limits file."""
    import jax.numpy as jnp
    from benchmark.harness import compare, traffic
    cell = _cell(tiny_size=True)
    batch = cell.traffic["batch_per_chip"] * 4
    pool = traffic.make_pool(cell.traffic, cell.cfg, cell.adapter, batch, 11)
    key, k = compare.program_key(11), cell.cfg["fused_step_block"]
    sound = compare.run_reference(cell.reference, cell.cfg, key, pool, k)
    ok, compared = compare.judge(compare.numbers(sound, sound), cell.limits)
    assert ok, compared
    cell.reference.visible = lambda rows, length, block: \
        rows[:, None] >= jnp.arange(2 * length)[None, :]
    causal = compare.run_reference(cell.reference, cell.cfg, key, pool, k)
    ok, compared = compare.judge(compare.numbers(causal, sound), cell.limits)
    assert not ok
    assert compared["out0_gap"]["value"] > compared["out0_gap"]["limit"], \
        compared["out0_gap"]
