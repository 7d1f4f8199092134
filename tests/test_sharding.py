"""mxshard static SPMD sharding analysis (ISSUE-18 acceptance).

Gates: megatron rule coverage is checked STATICALLY (every TransformerLM
matrix param matches exactly one rule, with zero trace work); a dropped
rule is a `rule-coverage` ERROR carrying the exact param name; a forced
producer/consumer spec mismatch is a `hidden-reshard` WARN naming both
nodes and the statically computed bytes, and both seeded defects exit
nonzero through the `mxlint --shard-report --fail-on` CLI contract; the
static dp ICI plan is BYTE-EXACT against measured `KVStore.stats()`
under dp=4 and dp=2,tp=2; the committed COST_BUDGETS "sharding" section
passes on HEAD and fails on a seeded regression; the bench program set
and examples/ produce zero non-hint findings (no false positives); plus
`parse_spec` error messages naming the offending token and grammar.
"""
import glob
import importlib.util
import json
import os

import pytest

from jax.sharding import PartitionSpec as P

import incubator_mxnet_tpu as mx          # noqa: F401  (device census)
from incubator_mxnet_tpu import sym
from incubator_mxnet_tpu.analysis import budgets as mxbudgets
from incubator_mxnet_tpu.analysis import sharding as mxshard
from incubator_mxnet_tpu.base import MXNetError
from incubator_mxnet_tpu.parallel.mesh import parse_spec
from incubator_mxnet_tpu.parallel.tensor_parallel import ShardingRules

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUDGETS_PATH = os.path.join(REPO, "COST_BUDGETS.json")


def _cli():
    spec = importlib.util.spec_from_file_location(
        "_mxlint_cli_shard", os.path.join(REPO, "tools", "mxlint.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _lm_params():
    symb, shapes, dtypes = mxshard.lm_bench_symbol()
    arg_shapes, _, _ = symb.infer_shape(**shapes)
    step = set(shapes)
    return symb, dtypes, shapes, {
        n: tuple(s) for n, s in zip(symb.list_arguments(), arg_shapes)
        if n not in step}


# ---------------------------------------------------------------------------
# mesh spec parsing (the error-message contract)
# ---------------------------------------------------------------------------

def test_parse_spec_roundtrip():
    assert parse_spec("dp=4,tp=2") == {"dp": 4, "tp": 2}
    assert list(parse_spec("pp=2,dp=4")) == ["pp", "dp"]  # order kept


@pytest.mark.parametrize("bad,token,reason", [
    ("dp:4", "'dp:4'", "missing '='"),
    ("dp=four", "'dp=four'", "not an integer"),
    ("dp=4,=2", "'=2'", "empty axis name"),
    ("dp=0", "'dp=0'", "positive"),
    ("dp=2,dp=4", "'dp=4'", "twice"),
])
def test_parse_spec_error_names_token_and_grammar(bad, token, reason):
    with pytest.raises(MXNetError) as ei:
        parse_spec(bad)
    msg = str(ei.value)
    assert "bad token " + token in msg, msg      # the offending token
    assert reason in msg                         # why it is bad
    assert "mesh spec grammar" in msg            # the accepted grammar
    assert "'dp=4,tp=2'" in msg                  # with a worked example


# ---------------------------------------------------------------------------
# rule coverage: the static twin of test_llm's dynamic megatron check
# ---------------------------------------------------------------------------

def test_megatron_rules_cover_every_lm_matrix_param_exactly_once():
    _, _, _, params = _lm_params()
    rules = ShardingRules.megatron(tp_axis="tp")
    matrices = 0
    for name, shape in sorted(params.items()):
        nmatch = sum(1 for prog, _ in rules.rules if prog.search(name))
        if len(shape) >= 2:
            assert nmatch == 1, (name, nmatch)   # exactly one rule
            matrices += 1
        else:                                    # bias/gamma/beta may
            assert nmatch <= 1                   # fall to the default
    # embed + (qkv, out_proj, fc1, fc2) x 2 blocks
    assert matrices == 1 + 4 * 2
    rep = mxshard.check_rule_coverage(params, rules)
    assert [f for f in rep if f.code == "rule-coverage"] == []


def test_dropped_megatron_rule_is_error_with_exact_param_name():
    symb, dtypes, shapes, _ = _lm_params()
    dropped = ShardingRules([              # row-parallel rule DROPPED
        (r"(qkv|query|key|value|gate|up|fc1|ffn_in).*weight",
         P("tp", None)),
        (r"embed.*weight", P("tp", None)),
        (r"bias", P()),
    ])
    rep = mxshard.analyze_sharding(symb, shapes=shapes, dtypes=dtypes,
                                   mesh="dp=2,tp=2", rules=dropped)
    errs = [f for f in rep.findings if f.code == "rule-coverage"]
    assert errs and all(f.severity == "error" for f in errs)
    flagged = {f.node for f in errs}
    for name in ("lm_block0_out_proj_weight", "lm_block1_fc2_weight"):
        assert name in flagged
        assert any(name in f.message for f in errs)


def test_ambiguous_rule_match_is_error_listing_patterns():
    rules = ShardingRules([(r"fc1.*weight", P("tp", None)),
                           (r"weight", P(None, "tp"))])
    rep = mxshard.check_rule_coverage({"blk_fc1_weight": (64, 32)}, rules)
    errs = [f for f in rep if f.code == "rule-coverage"]
    assert len(errs) == 1
    assert "2 sharding rules" in errs[0].message
    assert "fc1.*weight" in errs[0].message


def test_rule_set_not_applicable_to_model_is_silent():
    # a convnet under megatron rules is not a coverage gap
    rep = mxshard.check_rule_coverage(
        {"conv0_weight": (16, 3, 3, 3), "fc0_weight": (32, 4096)},
        ShardingRules.megatron(tp_axis="tp"))
    assert len(rep) == 0


# ---------------------------------------------------------------------------
# propagation: megatron algebra on the LM bench symbol
# ---------------------------------------------------------------------------

def test_lm_megatron_propagation_collectives_and_peak_hbm():
    symb, dtypes, shapes, _ = _lm_params()
    rep = mxshard.analyze_sharding(
        symb, shapes=shapes, dtypes=dtypes, mesh="dp=2,tp=2",
        rules=ShardingRules.megatron(tp_axis="tp"))
    # row-parallel psums: embedding + (out_proj + fc2) per block
    psums = [c for c in rep.collectives
             if c["kind"] == "psum" and c["axis"] == "tp"]
    assert len(psums) == 1 + 2 * 2
    # clean model: no warnings/errors, every op modeled
    assert [f for f in rep.findings
            if f.severity in ("error", "warn")] == []
    assert rep.fallback_ops == {}
    # sharding genuinely shrinks the per-device footprint
    assert rep.per_device_peak_hbm_bytes < rep.replicated_peak_hbm_bytes
    assert rep.ici_bytes_per_step > 0


def test_forced_spec_mismatch_hidden_reshard_names_nodes_and_bytes():
    data = sym.Variable("data")
    h = sym.FullyConnected(data, num_hidden=2048, name="blk_qkv",
                           no_bias=True)          # col-parallel under
    out = sym.LayerNorm(h, name="blk_ln")         # megatron: last dim tp
    rep = mxshard.analyze_sharding(
        out, shapes={"data": (256, 2048)}, mesh="dp=2,tp=2",
        rules=ShardingRules.megatron(tp_axis="tp"))
    hr = [f for f in rep.findings if f.code == "hidden-reshard"]
    assert len(hr) >= 1
    f = hr[0]
    assert f.severity == "warn"
    assert "blk_qkv" in f.message and "blk_ln" in f.message  # both nodes
    assert str(256 * 2048 * 4) in f.message       # static bytes
    # classified: dp survives on dim 0 while tp gathers -> all-to-all
    assert "all-to-all" in f.message


def test_hidden_reshard_gated_by_min_mb():
    data = sym.Variable("data")
    h = sym.FullyConnected(data, num_hidden=64, name="blk_qkv",
                           no_bias=True)          # 2 KB edge: recorded,
    out = sym.LayerNorm(h, name="blk_ln")         # never a finding
    rep = mxshard.analyze_sharding(
        out, shapes={"data": (8, 64)}, mesh="dp=2,tp=2",
        rules=ShardingRules.megatron(tp_axis="tp"))
    assert [f for f in rep.findings if f.code == "hidden-reshard"] == []
    assert any(r["kind"] in ("all-gather", "all-to-all")
               for r in rep.reshards)


def test_implicit_replication_flagged_and_gated_by_min_mb():
    data = sym.Variable("data")
    out = sym.FullyConnected(data, num_hidden=512, name="plain",
                             no_bias=True)        # weight 512x1024 = 2MB
    kw = dict(shapes={"data": (8, 1024)}, mesh="dp=2,tp=2", rules=None)
    rep = mxshard.analyze_sharding(out, **kw)
    hits = [f for f in rep.findings if f.code == "implicit-replication"]
    assert any(f.node == "plain_weight" for f in hits)
    assert all(f.severity == "warn" for f in hits)
    # raising the floor past the tensor silences it
    rep = mxshard.analyze_sharding(out, min_mb=4.0, **kw)
    assert [f for f in rep.findings
            if f.code == "implicit-replication"] == []


def test_unknown_op_falls_back_replicated_and_is_recorded():
    data = sym.Variable("data")
    out = sym.tile(data, reps=(1, 2), name="tile0")
    rep = mxshard.analyze_sharding(out, shapes={"data": (8, 64)},
                                   mesh="dp=2")
    assert rep.fallback_ops.get("tile") == 1
    assert any(f.code == "shard-fallback" for f in rep.findings)


# ---------------------------------------------------------------------------
# zero false positives on the committed bench programs and examples/
# ---------------------------------------------------------------------------

def test_bench_set_zero_nonhint_findings_and_zero_fallbacks():
    results = mxshard.analyze_shard_bench_set("dp=2,tp=2")
    assert set(results) == {"llm.lm_micro", "quantization.convnet_fp32",
                            "quantization.convnet_bf16",
                            "quantization.convnet_int8"}
    for name, entry in results.items():
        bad = [f for f in entry["findings"]
               if f["severity"] in ("error", "warn")]
        assert bad == [], (name, bad)
        assert entry["fallback_ops"] == {}, name
        assert entry["per_device_peak_hbm_bytes"] > 0
        assert entry["ici_bytes_per_step"] > 0


def test_unsharded_device_put_zero_findings_on_examples():
    from incubator_mxnet_tpu import analysis
    found = []
    for path in glob.glob(os.path.join(REPO, "examples", "**", "*.py"),
                          recursive=True):
        found += [f.format() for f in analysis.check_source_file(path)
                  if f.code == "unsharded-device-put"]
    assert found == []


# ---------------------------------------------------------------------------
# static ICI vs measured KVStore counters (dp plan is byte-exact)
# ---------------------------------------------------------------------------

def test_measured_ici_check_dp4_byte_exact():
    res = mxshard.measured_ici_check("dp=4")
    assert res["dp"] == 4
    assert res["agreement_pct"] <= 10.0
    assert res["static_bytes_per_step"] == res["measured_bytes_per_step"]
    assert res["static_collectives_per_step"] == \
        res["measured_allreduce_dispatches"]
    assert res["ok"]


def test_measured_ici_check_dp2_tp2():
    res = mxshard.measured_ici_check("dp=2,tp=2")
    assert res["dp"] == 2
    assert res["agreement_pct"] <= 10.0
    assert res["static_bytes_per_step"] == res["measured_bytes_per_step"]
    assert res["ok"]


# ---------------------------------------------------------------------------
# budget gate: COST_BUDGETS.json "sharding" section
# ---------------------------------------------------------------------------

def test_committed_shard_budgets_pass_on_head():
    results = mxshard.analyze_shard_bench_set("dp=2,tp=2")
    budgets = mxbudgets.load(BUDGETS_PATH)
    assert budgets.get("sharding", {}).get("mesh") == "dp=2,tp=2"
    rep, deltas = mxshard.check_shard_budgets(results, budgets)
    assert [f for f in rep if f.severity == "error"] == []
    assert all(m["ok"] for prog in deltas.values() for m in prog.values())


def test_seeded_budget_regression_is_error():
    results = mxshard.analyze_shard_bench_set("dp=2,tp=2")
    budgets = {"sharding":
               mxshard.snapshot_shard_budgets(results, "dp=2,tp=2")}
    rep, _ = mxshard.check_shard_budgets(results, budgets)
    assert [f for f in rep if f.code == "budget-regression"] == []
    # shrink one committed budget under the measured value: regression
    budgets["sharding"]["programs"]["llm.lm_micro"][
        "ici_bytes_per_step"] //= 2
    rep, deltas = mxshard.check_shard_budgets(results, budgets)
    regs = [f for f in rep if f.code == "budget-regression"]
    assert regs and all(f.severity == "error" for f in regs)
    assert any("llm.lm_micro" in (f.node or "") + f.message for f in regs)
    assert not deltas["sharding.llm.lm_micro"]["ici_bytes_per_step"]["ok"]


# ---------------------------------------------------------------------------
# the mxlint --shard-report CLI contract
# ---------------------------------------------------------------------------

def test_cli_shard_report_clean_on_head(capsys):
    cli = _cli()
    rc = cli.main(["--shard-report", "--json", "--fail-on=warn",
                   "--budgets", BUDGETS_PATH])
    summary = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert summary["failing"] == 0
    assert set(summary["programs"]) >= {"llm.lm_micro"}


def test_cli_seeded_spec_mismatch_exits_nonzero(tmp_path, capsys):
    data = sym.Variable("data")
    h = sym.FullyConnected(data, num_hidden=2048, name="blk_qkv",
                           no_bias=True)
    out = sym.LayerNorm(h, name="blk_ln")
    path = tmp_path / "mismatch-symbol.json"
    path.write_text(out.tojson())
    cli = _cli()
    rc = cli.main(["--shard-report", str(path), "--json",
                   "--fail-on=warn", "--shape", "data=256,2048"])
    summary = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert summary["failing"] >= 1
    prog = summary["programs"]["mismatch-symbol.json"]
    assert any(f["code"] == "hidden-reshard" and "blk_qkv" in f["message"]
               and "blk_ln" in f["message"] for f in prog["findings"])


def test_cli_seeded_coverage_gap_exits_nonzero(tmp_path, capsys):
    data = sym.Variable("data")
    h = sym.FullyConnected(data, num_hidden=64, name="enc_qkv",
                           no_bias=True)           # matches a rule, so
    out = sym.FullyConnected(h, num_hidden=64, name="enc_attn",
                             no_bias=True)         # the set applies;
    path = tmp_path / "gap-symbol.json"            # enc_attn_weight
    path.write_text(out.tojson())                  # matches NONE
    cli = _cli()
    rc = cli.main(["--shard-report", str(path), "--json",
                   "--fail-on=error", "--shape", "data=8,64"])
    summary = json.loads(capsys.readouterr().out)
    assert rc == 1
    prog = summary["programs"]["gap-symbol.json"]
    assert any(f["code"] == "rule-coverage" and
               "enc_attn_weight" in f["message"]
               for f in prog["findings"])


# ---------------------------------------------------------------------------
# shard_collectives: the per-step economy of a dp-sharded step
# ---------------------------------------------------------------------------

def test_shard_collectives_dp_static_block():
    h = sym.FullyConnected(sym.Variable("data"), num_hidden=256, name="fc1")
    h = sym.Activation(h, act_type="relu")
    net = sym.SoftmaxOutput(
        sym.FullyConnected(h, num_hidden=10, name="head"), name="softmax")
    stats = mxshard.shard_collectives(
        net, shapes={"data": (64, 128), "softmax_label": (64,)},
        mesh={"dp": 2}, name="dp2.mlp")
    rep, dp_plan = stats["report"], stats["dp"]
    assert rep.per_device_peak_hbm_bytes > 0
    assert rep.per_device_peak_hbm_bytes < rep.replicated_peak_hbm_bytes
    assert dp_plan["collectives_per_step"] >= 1
    assert dp_plan["bytes_per_step"] > 0
