"""The SDAR mixture-of-experts family on the training path -- block-diffusion
training over the doubled sequence -- at a small size on the CPU, against
the plain float32 reference the benchmark keeps
(benchmark/configs/sdar_30b_a3b_chat_reference.py, loaded by path: it
imports nothing of the program).  Seeded random weights throughout.

Tolerances.  Everything here runs in float32 on the CPU, where a matrix
product is a true float32 product; program and reference differ in the ORDER
of their sums only (a blocked softmax over the mask's key slices against a
whole masked one, grouped rows against a dense mask over the experts).  The
helpers and their tolerance are tests/test_qwen3_next.py's: 2e-5 relative
to the largest entry covers a few hundred float32 roundings (6e-8 each);
gradients get five times that, having passed through both passes.  The
noise's draws are compared bit for bit: integer work, look-ups in tables
made on the host and one comparison on either side.  A planted fault (another mask, no weight,
other positions) must read at least a hundred times the sound gap.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.llm import SdarMoeConfig, sdar_moe_symbol
from incubator_mxnet_tpu.ops import registry
from incubator_mxnet_tpu.parallel import ExpertShare

from test_qwen3_next import ROOT, cells, _close, _op, _rand

CELL = "sdar_moe_bd_train_hostfed"

REF = cells.load_module(os.path.join(
    cells.BENCH_DIR, "configs", "sdar_30b_a3b_chat_reference.py"))


def _tiny_cell():
    return cells.Cell(cells.benchmark_json(), CELL, tiny=True)


# -- the noise ---------------------------------------------------------------------

_NOISE = {"block_length": 4, "noise_interval": [0.001, 1.0], "vocab_size": 50}


def _ids(seed, batch=3, length=24, vocab=50):
    return jax.random.randint(jax.random.PRNGKey(seed), (batch, length), 0,
                              vocab)


def _noise_op(ids, seed, train=False, **params):
    op = registry.get("BlockDiffusionNoise")
    params = op.canonicalize_params(dict(
        {"block_length": 4, "mask_token": 50, "seed": seed}, **params))
    params["_train"] = train
    return op.fn(params, ids.astype(jnp.float32), jnp.zeros((3,)),
                 jax.random.PRNGKey(7))


@pytest.mark.parametrize("seed,block,length", [(11, 4, 24), (12, 4, 24),
                                               (11, 8, 24), (11, 5, 23)])
def test_noise_operator_is_the_references_draw_bit_for_bit(seed, block,
                                                           length):
    """The same masks, the same weights to the last bit (a block length
    that does not divide the sequence among them: its last block is
    short), in both directions of jit."""
    ids = _ids(3, length=length)
    cfg = dict(_NOISE, block_length=block, noise_seed=seed)
    want = jax.jit(lambda x: REF.noise(x, cfg))(ids)
    got = _noise_op(ids, seed, block_length=block)
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g, np.float64),
                              np.asarray(w, np.float64))
    noisy, m, weight = (np.asarray(a) for a in got)
    assert set(np.unique(m)) == {0.0, 1.0} and 0 < m.mean() < 1
    assert np.all(noisy[m == 1] == 50) and \
        np.all(noisy[m == 0] == np.asarray(ids)[m == 0])
    # one level a block: the masked rows of a block weigh the same, 1 / t
    # with t on [0.001, 1]
    assert np.all(weight[m == 0] == 0) and np.all(weight[m == 1] >= 1.0)
    for row_m, row_w in zip(m, weight):
        for b in range(0, length, block):
            w = row_w[b:b + block][row_m[b:b + block] == 1]
            assert len(set(w.tolist())) <= 1


def test_noise_differs_between_rows_and_seeds_and_follows_the_tokens():
    ids = _ids(4)
    _, m, _ = _noise_op(ids, 11)
    assert not np.array_equal(m[0], m[1])
    assert not np.array_equal(m, _noise_op(ids, 12)[1])
    # a function of the row's tokens: the same row draws the same, wherever
    # it stands in the batch; one token changed draws another
    _, swapped, _ = _noise_op(ids[::-1], 11)
    assert np.array_equal(swapped[::-1], m)
    other = ids.at[0, 5].set((ids[0, 5] + 1) % 50)
    _, moved, _ = _noise_op(other, 11)
    assert not np.array_equal(moved[0], m[0])
    assert np.array_equal(moved[1:], m[1:])


def test_noise_without_a_seed_draws_from_the_random_resource():
    """No `seed`: the graph's key decides, as `Dropout`'s; through the
    executor two steps draw differently, with a seed they draw alike."""
    ids = _ids(5)
    op = registry.get("BlockDiffusionNoise")
    assert op.needs_rng
    params = op.canonicalize_params({"block_length": 4, "mask_token": 50})
    masks = [np.asarray(op.fn(dict(params), ids.astype(jnp.float32),
                              jnp.zeros((3,)), jax.random.PRNGKey(k))[1])
             for k in (1, 1, 2)]
    assert np.array_equal(masks[0], masks[1])
    assert not np.array_equal(masks[0], masks[2])
    for seed, same in ((None, False), (9, True)):
        noise = mx.sym.BlockDiffusionNoise(
            mx.sym.Variable("data"), name="noise", block_length=4,
            mask_token=50, seed=seed)
        exe = noise[1].simple_bind(mx.cpu(), data=ids.shape,
                                   noise_stats=(3,))
        exe.arg_dict["data"][:] = np.asarray(ids, np.float32)
        steps = [exe.forward(is_train=True)[0].asnumpy() for _ in range(2)]
        assert np.array_equal(steps[0], steps[1]) == same
    lowered = mx.obs.counter("ops.diffusion_noise.lowered.random")
    before = lowered.value
    jax.make_jaxpr(lambda x, k: op.fn(dict(params), x, jnp.zeros((3,)), k))(
        ids.astype(jnp.float32), jax.random.PRNGKey(0))
    assert lowered.value == before + 1


def test_noise_counts_in_training_alone_and_refuses_bad_parameters():
    ids = _ids(6)
    out = _noise_op(ids, 11, train=True)
    assert len(out) == 4
    noisy, m, weight, stats = out
    _close(stats, [ids.size, float(m.sum()), float(weight.sum())], 1e-6)
    assert len(_noise_op(ids, 11)) == 3
    op = registry.get("BlockDiffusionNoise")
    assert op.num_aux({}) == 1 and op.num_outputs({}) == 3
    for bad in ({"low": 0.0001}, {"low": 0.6, "high": 0.5}, {"high": 1.5},
                {"block_length": 0}):
        with pytest.raises(mx.MXNetError, match="BlockDiffusionNoise"):
            _noise_op(ids, 11, **bad)
    note = op.counters([{"stats": np.array([96.0, 40.0, 120.5]),
                         "params": {}}])
    assert note["span"] == "diffusion.noise"
    assert note["args"]["rows"] == 96 and note["args"]["masked"] == 40
    assert note["counters"] == {"diffusion.rows": 96, "diffusion.masked": 40,
                                "diffusion.weight_sum": 120.5}


# -- the positions and the mask ------------------------------------------------------

def test_rotary_embedding_takes_the_positions_period():
    """`copies=2`: each half of the time axis at positions 0..T/2 - 1, what
    the reference's `positions` gives; absent, as it was."""
    x = _rand(1, 2, 16, 3, 8)
    plain = _op("RotaryEmbedding", rotary_dim=8, base=1e6)
    twice = _op("RotaryEmbedding", rotary_dim=8, base=1e6, copies=2)
    _close(twice(x), jnp.concatenate([plain(x[:, :8]), plain(x[:, 8:])], 1),
           1e-6)
    _close(twice(x), REF._rotary(x, 1e6, REF.positions(8)))
    assert np.array_equal(
        np.asarray(_op("RotaryEmbedding", rotary_dim=8, base=1e6,
                       copies=1)(x)), np.asarray(plain(x)))
    assert float(jnp.abs(twice(x) - plain(x)).max()) > 0.1
    with pytest.raises(mx.MXNetError, match="copies"):
        _op("RotaryEmbedding", rotary_dim=8, copies=3)(x)


def test_the_references_mask_is_the_four_lines():
    length, block = 12, 4
    see = np.asarray(REF.visible(jnp.arange(2 * length), length, block))
    b = np.arange(length) // block
    for i in range(length):
        for j in range(length):
            assert see[i, j] == (b[j] == b[i])
            assert see[i, length + j] == (b[j] < b[i])
            assert see[length + i, length + j] == (b[j] <= b[i])
            assert not see[length + i, j]
    # L^2 + L B of the (2L)^2 entries
    assert see.sum() == length * length + length * block


# -- the routed layer's shares -------------------------------------------------------

E, TOPK, C, I, N = 16, 4, 24, 12, 40
_ROUTER = {"num_experts_per_tok": TOPK, "norm_topk_prob": True}


def _moe_leaves(seed=20):
    return {"moe.router.w": _rand(seed, E, C, scale=0.5),
            "moe.gate.w": _rand(seed + 1, E, I, C, scale=0.3),
            "moe.up.w": _rand(seed + 2, E, I, C, scale=0.3),
            "moe.down.w": _rand(seed + 3, E, C, I, scale=0.3)}


def _program_routed(p, x, share):
    op = registry.get("RoutedExperts")
    params = op.canonicalize_params(dict(
        top_k=TOPK, **share.op_params()))
    params["_train"] = True
    held = slice(share.offset, share.offset + share.count)
    return op.fn(params, x, p["moe.router.w"], p["moe.gate.w"][held],
                 p["moe.up.w"][held], p["moe.down.w"][held],
                 jnp.zeros((share.count,)), jnp.zeros((2,)))


@pytest.mark.parametrize("count", [2, 16])
def test_shares_add_up_to_the_uncut_layer(count):
    """Over all disjoint shares of E / count experts (the configuration's 8
    shares of 2 among them) the partial outputs add up to the reference's
    layer that holds every expert: nothing is computed by every share alike
    (no shared expert), and the softmax router renormalises over the
    chosen wherever they live."""
    p, x = _moe_leaves(), _rand(41, 2, N // 2, C)
    whole, whole_load = REF.moe(p, x, _ROUTER, "float32", held=(0, E))
    shares = [ExpertShare.of_chip(E, E // count, i)
              for i in range(E // count)]
    assert len(shares) == (8 if count == 2 else 1)
    parts = [_program_routed(p, x, s) for s in shares]
    _close(sum(part[0] for part in parts), whole)
    _close(jnp.concatenate([part[1] for part in parts]), whole_load, 0)
    assert float(whole_load.sum()) == N * TOPK
    for part, s in zip(parts, shares):
        cut = dict(p, **{n: p[n][s.offset:s.offset + s.count] for n in
                         ("moe.gate.w", "moe.up.w", "moe.down.w")})
        _close(part[0], REF.moe(cut, x, _ROUTER, "float32",
                                held=(s.offset, s.count))[0])


# -- the model through Module.fit -----------------------------------------------------

@pytest.fixture(scope="module")
def fitted():
    """The benchmark's own set-up at the `tiny` size: ONE module driven
    through `Module.fit` for a block of K = 8 fused steps with the guardian
    on, and the plain reference's 8 steps from the same seed."""
    from benchmark.harness import compare, runner
    from incubator_mxnet_tpu.obs import trace as obs_trace
    cell = _tiny_cell()
    obs_trace.enable()
    obs_trace.reset()
    program = runner.Program(cell, 2147483777)
    spans = obs_trace.buffered()
    reference = compare.run_reference(cell.reference, cell.cfg, program.key,
                                      program.pool, program.k)
    return cell, program, reference, spans


def test_fit_block_matches_the_reference(fitted):
    from benchmark.harness import compare
    cell, program, reference, _ = fitted
    assert program.unfused == 0 and program.k == 8
    fs = program.mod._fused_step
    assert fs is not None and not fs.broken
    # the four layers, and nothing else, as one scanned run
    assert [run[1] for run in fs.scan_runs] == [4]
    assert program.mod._guardian is not None
    nums = compare.numbers(program.prog, reference)
    # float32 on both sides: the gaps are roundings, 8 steps deep
    for name in ("loss_gap", "loss0_gap", "out0_gap", "dw_gap", "mom_gap",
                 "aux_gap"):
        assert nums[name][0] < 1e-5, (name, nums[name])
    assert reference["loss"][-1] < reference["loss"][0]     # it trains
    # every leaf moved, the mask token's row among the embedding's
    assert min(reference["dw"].values()) > 0
    assert set(program.prog["dw"]) == set(reference["dw"])
    # the counts: loads of the four layers and the noise's
    assert set(program.prog["aux"]) == {"noise.stats"} | {
        f"l{i}.moe.load" for i in range(4)}


def test_every_gradient_leaf_against_the_objectives_gradient(fitted):
    """One forward and backward pass of the bound symbol: outputs, and per
    leaf the gradient the weighted head sends back (summed over the rows)
    against `jax.grad` of the reference's objective (a mean over them)."""
    cell, program, _, _ = fitted
    cfg, ref, adapter = cell.cfg, cell.reference, cell.adapter
    params, aux = ref.init_params(program.key, cfg)
    data, label = (jnp.asarray(a) for a in program.pool[0])
    symbol = adapter.build_symbol(mx, cfg)
    exe = symbol.simple_bind(mx.cpu(), data=data.shape,
                             softmax_label=label.shape)
    names = symbol.list_arguments() + symbol.list_auxiliary_states()
    for n, a in adapter.to_program({**params, **aux}, cfg, names).items():
        (exe.arg_dict if n in exe.arg_dict else exe.aux_dict)[n][:] = \
            np.asarray(a)
    exe.arg_dict["data"][:] = np.asarray(data)
    exe.arg_dict["softmax_label"][:] = np.asarray(label)
    out = exe.forward(is_train=True)[0].asnumpy()
    exe.backward()
    _close(out, ref.outputs(params, aux, data, cfg))
    want = jax.grad(lambda p: ref.loss_fn(p, aux, data, label, cfg)[0])(
        params)
    got = adapter.from_program(
        {n: g.asnumpy() for n, g in exe.grad_dict.items()
         if g is not None and n not in ("data", "softmax_label")}, cfg)
    assert set(got) == set(want)
    for leaf, g in want.items():
        _close(got[leaf], g * data.size, 1e-4)
    # the label reaches no gradient: another one, the same gradients
    exe.arg_dict["softmax_label"][:] = np.asarray(data)
    exe.forward(is_train=True)
    exe.backward()
    again = adapter.from_program(
        {n: g.asnumpy() for n, g in exe.grad_dict.items()
         if g is not None and n not in ("data", "softmax_label")}, cfg)
    for leaf in want:
        assert np.array_equal(again[leaf], got[leaf]), leaf


def _causal(rows, length, block):
    return rows[:, None] >= jnp.arange(2 * length)[None, :]


def _unweighted(x, cfg):
    noisy, m, weight = _SOUND_NOISE(x, cfg)
    return noisy, m, jnp.ones_like(weight)


_SOUND_NOISE = REF.noise


@pytest.mark.parametrize("fault,patch", [
    ("the causal mask over the 2L rows", {"visible": _causal}),
    ("the weight left out", {"noise": _unweighted}),
    ("the two copies at positions 0..2L-1",
     {"positions": lambda length: jnp.arange(2 * length)})])
def test_a_planted_fault_fails_a_number(fitted, monkeypatch, fault, patch):
    """The reference with one piece of the objective changed, against the
    sound program: at least one number reads a hundred times the sound
    float32 gap; another mask and other positions show in the first step's
    outputs, the weight (which the outputs do not hold) in the gradients."""
    from benchmark.harness import compare
    cell, program, sound, _ = fitted
    for name, fn in patch.items():
        monkeypatch.setattr(cell.reference, name, fn)
    broken = compare.run_reference(cell.reference, cell.cfg, program.key,
                                   program.pool, program.k)
    nums = compare.numbers(program.prog, broken)
    over = {n for n in ("out0_gap", "loss_gap", "dw_gap", "dw_med_gap",
                        "mom_med_gap") if nums[n][0] > 1e-3}
    assert over, nums
    assert ("out0_gap" in over) == ("noise" not in patch), (fault, nums)
    if "noise" in patch:
        assert {"dw_med_gap", "mom_med_gap"} <= over, nums


def test_spans_of_the_epochs_end(fitted):
    cell, program, reference, spans = fitted
    tokens = 8 * cell.traffic["batch_per_chip"] * cell.cfg["seq_len"]
    (load,) = [s for s in spans if s["name"] == "moe.load"]
    # the experts see both copies' rows
    assert load["args"]["tokens"] == 2 * tokens
    assert load["args"]["layers"] == 4 and load["args"]["dropped"] == 0
    assert load["args"]["scoring"] == "softmax"
    (noise,) = [s for s in spans if s["name"] == "diffusion.noise"]
    args = noise["args"]
    assert args["rows"] == tokens and 0 < args["masked"] < tokens
    assert args["weight_sum"] > args["masked"]
    values = mx.obs.metrics.registry().collect()
    assert values["diffusion.rows"] >= tokens


def test_routing_seed_fixes_the_embedding_and_the_routers_alone():
    """With `routing_seed` (the cell's configuration) the embedding and the
    routers are the same under every key and every other matrix follows
    the key; without it (the tiny size) all follow the key."""
    cfg = dict(_tiny_cell().cfg)
    assert cfg["routing_seed"] is None
    routed = ("embed.w", "moe.router.w")

    def draws(cfg):
        return [REF.init_params(jax.random.PRNGKey(k), cfg)[0]
                for k in (1, 2)]
    a, b = draws(dict(cfg, routing_seed=5))
    for name in a:
        if "norm" not in name:          # norm weights are ones
            assert bool(jnp.array_equal(a[name], b[name])) == \
                name.endswith(routed), name
    a, b = draws(cfg)
    assert not any(bool(jnp.array_equal(a[n], b[n])) for n in a
                   if n.endswith(routed))
    c = draws(dict(cfg, routing_seed=6))[0]
    assert not bool(jnp.array_equal(c["embed.w"], a["embed.w"]))


def test_config_from_the_published_keys():
    cell = cells.Cell(cells.benchmark_json(), CELL)
    cfg = SdarMoeConfig.from_dict(cell.cfg)
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim,
            cfg.num_key_value_heads) == (2048, 32, 128, 4)
    assert cfg.experts_held == ExpertShare(128, 0, 16)
    assert cfg.num_experts == 128 and cfg.num_experts_per_tok == 8
    assert cfg.mask_token_id == cfg.vocab_size == 18992
    assert cfg.attention_mask() == {"mask": "block_diffusion",
                                    "block_length": 4}
    assert cfg.noise_interval == (0.001, 1.0) and cfg.noise_seed is not None
    # a dense layer among the sparse ones has no path here
    with pytest.raises(mx.MXNetError, match="mlp_only_layers"):
        SdarMoeConfig(mlp_only_layers=[0])
    with pytest.raises(mx.MXNetError, match="decoder_sparse_step"):
        SdarMoeConfig(decoder_sparse_step=2)


def test_declared_bfloat16_parameters_and_fresh_initialisation():
    cfg = SdarMoeConfig(param_dtype="bfloat16", vocab_size=32)
    mod = mx.mod.Module(sdar_moe_symbol(cfg), context=mx.cpu(),
                        label_names=("softmax_label",))
    mod.bind(data_shapes=[("data", (2, 16))],
             label_shapes=[("softmax_label", (2, 16))])
    exe = mod._exec_group.execs[0]
    assert exe.arg_dict["lm_embed_weight"].shape == (33, 64)
    for name in ("lm_embed_weight", "lm_head_weight",
                 "lm_layer0_attn_q_norm_gamma",
                 "lm_layer2_moe_experts_down_weight"):
        assert str(exe.arg_dict[name].dtype) == "bfloat16", name
    for name in ("lm_noise_stats", "lm_layer3_moe_load"):
        assert str(exe.aux_dict[name].dtype) == "float32", name
    mod.init_params(mx.init.Normal(0.02))
    args, aux = mod.get_params()
    for name in ("lm_layer0_norm1_gamma", "lm_layer1_attn_k_norm_gamma",
                 "lm_final_norm_gamma"):
        assert float(args[name].asnumpy().min()) == 1.0, name
    assert float(np.abs(aux["lm_noise_stats"].asnumpy()).max()) == 0.0


_FRESH = """
import json, sys
import numpy as np
import incubator_mxnet_tpu as mx
sym = mx.sym.load(sys.argv[1])
loaded = "incubator_mxnet_tpu.llm.sdar" in sys.modules
exe = sym.simple_bind(mx.cpu(), data=(2, 16), softmax_label=(2, 16))
rng = np.random.default_rng(0)
for name, arr in exe.arg_dict.items():
    if name in ("data", "softmax_label"):
        arr[:] = rng.integers(0, 32, arr.shape)
    else:
        arr[:] = 0.05 * rng.standard_normal(arr.shape)
out = exe.forward(is_train=False)[0].asnumpy()
nodes = json.loads(sym.tojson())["nodes"]
attn = next(n for n in nodes if n["op"] == "BlockwiseAttention")
print(json.dumps({"shape": list(out.shape), "rowsum": float(out.sum(-1).mean()),
                  "ops": sorted({n["op"] for n in nodes}),
                  "mask": attn["attrs"]["mask"],
                  "aux": len(sym.list_auxiliary_states()), "llm": loaded}))
"""


def test_saved_symbol_loads_in_a_fresh_process(tmp_path):
    path = str(tmp_path / "sdar-symbol.json")
    sdar_moe_symbol(SdarMoeConfig(vocab_size=32, noise_seed=3)).save(path)
    proc = subprocess.run(
        [sys.executable, "-c", _FRESH, path], capture_output=True, text=True,
        timeout=600, cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    # the noisy copy's rows alone reach the head
    assert got["shape"] == [32, 32] and abs(got["rowsum"] - 1.0) < 1e-4
    assert {"BlockDiffusionNoise", "RMSNorm", "RotaryEmbedding",
            "RoutedExperts", "BlockwiseAttention", "FullyConnected",
            "Embedding", "SoftmaxOutput"} <= set(got["ops"])
    assert got["mask"] == "block_diffusion" and not got["llm"]
    assert got["aux"] == 9       # four layers' load and dropped, the noise's
