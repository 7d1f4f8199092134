"""mxcost static cost & communication analysis (ISSUE-13 acceptance).

Gates: the dequantize-before-dot chain in the bench int8 convnet is
flagged with exact node names and the fp32/bf16 bench models produce
zero false positives; the static collective enumeration for a dp=8
bucketed plan matches `KVStore.stats()` measured bytes/dispatches
within 10%; `mxlint --cost-report --fail-on=warn` passes on HEAD
against COST_BUDGETS.json and fails on seeded regressions (extra
collectives from a shrunk bucket cap, a forced f32 upcast inside a
bf16 graph); plus roofline/FLOPs rules, liveness/peak-HBM, donation
opportunities, hidden host-transfer detection, the `--fail-on` CLI
contract, and the budget comparison logic.
"""
import importlib.util
import json
import os

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import analysis, nd, sym
from incubator_mxnet_tpu.analysis import budgets as mxbudgets
from incubator_mxnet_tpu.analysis import cost as mxcost

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BUDGETS_PATH = os.path.join(REPO, "COST_BUDGETS.json")


def _cli():
    spec = importlib.util.spec_from_file_location(
        "_mxlint_cli_cost", os.path.join(REPO, "tools", "mxlint.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _codes(report):
    return [f.code for f in report]


# ---------------------------------------------------------------------------
# dtype flow: the int8-slower-than-fp32 static signature
# ---------------------------------------------------------------------------

def test_int8_bench_convnet_dequant_chain_flagged_with_exact_nodes():
    qsym, shapes, dtypes = mxcost.build_bench_quantized_convnet()
    prog = mxcost.analyze_symbol(qsym, shapes=shapes, dtypes=dtypes,
                                 target="int8")
    chains = [f for f in prog.report if f.code == "dequant-fp32-dot"]
    assert len(chains) == 1
    f = chains[0]
    # exact node names: the dequantize source, the chain, and the dot
    assert f.node == "contrib_dequantize_0"
    assert "contrib_dequantize_0" in f.message
    assert "contrib_quantized_fully_connected_0" in f.message
    assert "flatten0" in f.message and "chain:" in f.message
    assert f.severity == "warn"
    # ... and the fp32-compute declaration on the quantized dot itself
    fp32c = [f for f in prog.report
             if f.code == "quantized-fp32-compute"]
    assert [f.node for f in fp32c] == \
        ["contrib_quantized_fully_connected_0"]
    assert prog.counters["dequant_fp32_dot"] == 1
    assert prog.counters["quantized_fp32_compute"] == 1


def test_bench_graph_names_do_not_depend_on_what_was_composed_before():
    def names():
        qsym, _, _ = mxcost.build_bench_quantized_convnet()
        return [n["name"] for n in json.loads(qsym.tojson())["nodes"]]

    def suffix(s):
        return int(s.name.rsplit("_", 1)[1])

    # auto-named nodes of the kinds the builder makes, composed outside
    # it: they move the thread's counts and must not move the builder's
    x, lo, hi = sym.Variable("x"), sym.Variable("lo"), sym.Variable("hi")
    before = sym.contrib.dequantize(x, lo, hi)
    sym.Flatten(x)
    first = names()
    assert "contrib_dequantize_0" in first
    assert "contrib_quantized_fully_connected_0" in first
    # ... and the builder leaves the thread's counts as it found them
    after = sym.contrib.dequantize(x, lo, hi)
    assert suffix(after) == suffix(before) + 1
    assert names() == first


def test_fp32_and_bf16_bench_models_zero_false_positives():
    for dtype in ("float32", "bfloat16"):
        s, shapes = mxcost.build_bench_convnet(dtype)
        prog = mxcost.analyze_symbol(s, shapes=shapes, target=dtype)
        bad = [f for f in prog.report if f.severity in ("warn", "error")]
        assert bad == [], f"{dtype}: {[f.format() for f in bad]}"
        assert prog.counters["dequant_fp32_dot"] == 0
        assert prog.counters["f32_upcasts"] == 0
        assert prog.unknown_ops == 0
        # the bf16 model really is bf16 end to end
        if dtype == "bfloat16":
            assert prog.dominant_dtype() == "bfloat16"


def test_f32_upcast_in_bf16_graph_flagged_and_clean_without_cast():
    c, hw = 3, 16
    kw = {"dtype": "bfloat16"}
    data = sym.Variable("data", shape=(4, c, hw, hw), **kw)
    x = sym.Convolution(data,
                        sym.Variable("cw", shape=(8, c, 3, 3), **kw),
                        no_bias=True, kernel=(3, 3), num_filter=8,
                        pad=(1, 1), name="conv")
    x = sym.Cast(x, dtype="float32", name="upcast")
    x = sym.Flatten(x, name="flat")
    out = sym.FullyConnected(
        x, sym.Variable("fw", shape=(4, 8 * hw * hw)),
        sym.Variable("fb", shape=(4,)), num_hidden=4, name="fc")
    prog = mxcost.analyze_symbol(out, shapes={"data": (4, c, hw, hw)})
    hits = [f for f in prog.report if f.code == "f32-upcast-in-bf16"]
    assert len(hits) == 1 and hits[0].node == "upcast"
    assert "fc" in hits[0].message and "upcast" in hits[0].message
    assert prog.counters["f32_upcasts"] == 1


# ---------------------------------------------------------------------------
# FLOPs / roofline / liveness
# ---------------------------------------------------------------------------

def test_flops_rules_and_roofline_classification():
    # known matmul: (64,128) x (128,256)W' -> 2*64*128*256 flops
    data = sym.Variable("data")
    out = sym.FullyConnected(data, num_hidden=256, no_bias=True,
                             name="fc")
    prog = mxcost.analyze_symbol(out, shapes={"data": (64, 128)})
    fc = next(c for c in prog.per_op if c.node == "fc")
    assert fc.flops == 2 * 64 * 128 * 256
    # a big matmul is compute-bound on every profile; a tiny one is not
    big = mxcost.analyze_symbol(
        sym.FullyConnected(sym.Variable("data"), num_hidden=4096,
                           no_bias=True, name="big"),
        shapes={"data": (4096, 4096)}, profile="tpu-v3")
    assert next(c for c in big.per_op if c.node == "big").bound == \
        "compute"
    assert big.bound == "compute"
    assert big.step_time_lb_s() > 0
    d = big.as_dict()
    assert d["flops"] == 2 * 4096 ** 3
    assert d["dominant_dtype"] == "float32"


def test_peak_hbm_liveness_and_donation_opportunity(monkeypatch):
    # data (4 MB) dies at the first conv -> donation opportunity; peak
    # covers params + the widest transient
    shape = (32, 8, 64, 64)
    data = sym.Variable("data")
    x = sym.Convolution(data, kernel=(3, 3), num_filter=8, pad=(1, 1),
                        no_bias=True, name="conv")
    out = sym.Activation(x, act_type="relu", name="relu")
    prog = mxcost.analyze_symbol(out, shapes={"data": shape})
    nbytes = int(np.prod(shape)) * 4
    assert prog.peak_hbm_bytes is not None
    assert prog.peak_hbm_bytes >= 2 * nbytes  # data + conv out alive
    don = [f for f in prog.report if f.code == "donation-opportunity"]
    assert [f.node for f in don] == ["data"]
    # below the size floor the hint stays quiet
    monkeypatch.setenv("MXNET_COST_DONATE_MIN_MB", "64")
    quiet = mxcost.analyze_symbol(out, shapes={"data": shape})
    assert not [f for f in quiet.report
                if f.code == "donation-opportunity"]


def test_jaxpr_analysis_scan_host_transfer_and_donation():
    import jax
    import jax.numpy as jnp

    def scan_fn(c, xs):
        def body(c, x):
            return jnp.dot(c, c) + x, None
        return jax.lax.scan(body, c, xs)[0]

    prog = mxcost.analyze_callable(
        scan_fn, [jax.ShapeDtypeStruct((64, 64), np.float32),
                  jax.ShapeDtypeStruct((10, 64, 64), np.float32)],
        name="scan")
    # body dot (2*64^3) x 10 trips dominates
    assert prog.flops >= 2 * 64 ** 3 * 10
    assert prog.counters["host_transfers"] == 0

    def bad(x):
        y = jax.pure_callback(
            lambda v: v, jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return y + 1.0

    hostful = mxcost.analyze_callable(
        bad, [jax.ShapeDtypeStruct((256, 256), np.float32)], name="bad")
    hits = [f for f in hostful.report
            if f.code == "hidden-host-transfer"]
    assert len(hits) == 1 and hostful.counters["host_transfers"] == 1
    assert hostful.bound == "host"

    # an undonated input matching an output aval -> donation hint
    def step(w):
        return w - 0.1 * w

    undonated = mxcost.analyze_callable(
        step, [jax.ShapeDtypeStruct((1024, 1024), np.float32)],
        name="step")
    assert [f.code for f in undonated.report
            if f.code == "donation-opportunity"]
    donated = mxcost.analyze_callable(
        step, [jax.ShapeDtypeStruct((1024, 1024), np.float32)],
        name="step", donate_argnums=(0,))
    assert not [f for f in donated.report
                if f.code == "donation-opportunity"]


def test_analyze_executor_costs_scan_body():
    T, B, H = 8, 4, 32
    data = sym.Variable("data")
    init = sym.Variable("init")
    w = sym.Variable("w")

    def body(x, s):
        out = sym.Activation(sym.broadcast_add(sym.dot(x, w), s),
                             act_type="tanh")
        return out, out

    outs, states = sym.contrib.foreach(body, data, init)
    g = sym.Group([outs, states])
    exe = g.simple_bind(ctx=mx.cpu(), grad_req="null", data=(T, B, H),
                        init=(B, H), w=(H, H))
    prog = mxcost.analyze_executor(exe, name="foreach")
    assert prog.flops >= 2 * B * H * H * T  # the per-step dot x T


# ---------------------------------------------------------------------------
# collective enumeration vs measured kvstore stats (<= 10%)
# ---------------------------------------------------------------------------

def test_static_collectives_match_measured_kvstore_stats(monkeypatch):
    # force a multi-bucket plan on KB-sized tensors
    monkeypatch.setenv("MXNET_KVSTORE_BUCKET_MB", "0.05")
    shapes = [(64, 32), (64,), (96, 64), (96,), (128, 64), (128,)]
    dtypes = [np.dtype("float32")] * len(shapes)
    kv = mx.kv.create("tpu")
    keys = [str(i) for i in range(len(shapes))]
    for k, s in zip(keys, shapes):
        kv.init(k, nd.zeros(s))
    devs = [mx.tpu(i) for i in range(8)]
    vals = [[nd.ones(s, ctx=d) for d in devs] for s in shapes]

    pred = kv.predicted_stats(shapes, dtypes=dtypes, ndev=8)
    kv.push(keys, vals)
    meas = kv.stats()

    assert pred["buckets"] > 1          # the plan is genuinely bucketed
    for metric, measured in (("allreduce_dispatches",
                              meas["allreduce_dispatches"]),
                             ("bytes_reduced", meas["bytes_reduced"])):
        predicted = pred[metric]
        assert abs(predicted - measured) <= 0.10 * max(1, measured), \
            f"{metric}: predicted {predicted} vs measured {measured}"
    assert pred["dispatch_complexity"] == "O(buckets)"

    # the enumerator is the SAME plan rule: byte-exact, not just <=10%
    stats = mxcost.enumerate_collectives(
        shapes, dtypes, dp=8, cap_bytes=kv._bucket_cap_bytes)
    assert stats["collectives_per_step"] == meas["allreduce_dispatches"]
    assert stats["bytes_per_step"] == meas["bytes_reduced"]


def test_pod_plan_prediction_matches_kvstore_rule():
    from incubator_mxnet_tpu import fused
    shapes = [(256, 128), (256,), (64, 256), (64,)]
    pred = fused.predict_pod_plan(shapes, cap_bytes=1 << 20, dp=8)
    # same rule, same priority order as the kvstore scheduler
    from incubator_mxnet_tpu.kvstore import plan_buckets
    sizes = [int(np.prod(s)) * 4 for s in shapes]
    plan = plan_buckets(list(reversed(range(len(shapes)))), sizes,
                        [np.dtype("float32")] * len(shapes), 1 << 20)
    assert pred["plan"] == [list(b) for b in plan]
    assert pred["collectives_per_step"] == len(plan)  # extras fold f32
    assert pred["bytes_per_step"] == sum(sizes)


def test_collective_o_params_warning_on_dtype_interleave():
    # alternating dtypes force one bucket per key: O(params) dispatch
    shapes = [(256,)] * 8
    dtypes = [np.dtype("float32"), np.dtype("float16")] * 4
    stats = mxcost.enumerate_collectives(shapes, dtypes, dp=8,
                                         cap_bytes=1 << 20,
                                         name="interleaved")
    assert stats["dispatch_complexity"] == "O(params)"
    rep = mxcost.collectives_report(stats)
    assert "collective-o-params" in _codes(rep)
    # a clean plan stays quiet
    ok = mxcost.enumerate_collectives([(256,)] * 8, None, dp=8,
                                      cap_bytes=1 << 20)
    assert ok["dispatch_complexity"] == "O(buckets)"
    assert "collective-o-params" not in _codes(
        mxcost.collectives_report(ok))


# ---------------------------------------------------------------------------
# budgets: the CI gate
# ---------------------------------------------------------------------------

def test_budget_check_regression_slack_missing_and_demotion():
    results = mxcost.analyze_bench_set(dp=8)
    budgets = mxbudgets.snapshot(results)

    # HEAD vs its own snapshot: no regressions, known defects demoted
    report, deltas = mxbudgets.check(results, budgets)
    assert not [f for f in report if f.severity == "error"]
    assert all(e["ok"] for progd in deltas.values()
               for e in progd.values())
    demoted = [f for f in report if f.code == "dequant-fp32-dot"]
    assert demoted and all(f.severity == "hint" for f in demoted)
    assert any("budgeted" in f.message for f in demoted)

    # seeded regression: the budget remembers fewer dequant chains
    tight = json.loads(json.dumps(budgets))
    tight["programs"]["quantization.convnet_int8"][
        "dequant_fp32_dot"] = 0
    report2, _ = mxbudgets.check(results, tight)
    errs = [f for f in report2 if f.code == "budget-regression"]
    assert any("dequant_fp32_dot" in f.message for f in errs)
    # the un-budgeted chain keeps its WARN severity
    assert [f for f in report2 if f.code == "dequant-fp32-dot"
            and f.severity == "warn"]

    # bytes over tolerance -> regression; far under -> slack hint
    tight2 = json.loads(json.dumps(budgets))
    tight2["programs"]["quantization.convnet_fp32"]["bytes_moved"] //= 2
    report3, _ = mxbudgets.check(results, tight2)
    assert any(f.code == "budget-regression" and
               "bytes_moved" in f.message for f in report3)
    loose = json.loads(json.dumps(budgets))
    loose["programs"]["quantization.convnet_fp32"]["bytes_moved"] *= 3
    report4, _ = mxbudgets.check(results, loose)
    assert any(f.code == "budget-slack" and "bytes_moved" in f.message
               for f in report4)

    # a program without a baseline entry -> budget-missing hint
    partial = json.loads(json.dumps(budgets))
    del partial["programs"]["quantization.convnet_bf16"]
    report5, _ = mxbudgets.check(results, partial)
    missing = [f for f in report5 if f.code == "budget-missing"]
    assert any("convnet_bf16" in f.message for f in missing)
    assert all(f.severity == "hint" for f in missing)


def test_committed_budgets_match_head_analysis():
    """The committed COST_BUDGETS.json is in sync with HEAD: zero
    budget regressions."""
    budgets = mxbudgets.load(BUDGETS_PATH)
    results = mxcost.analyze_bench_set(dp=8)
    report, _ = mxbudgets.check(results, budgets)
    errs = [f for f in report if f.severity == "error"]
    assert errs == [], [f.format() for f in errs]


# ---------------------------------------------------------------------------
# the CLI: --cost-report and --fail-on (the CI contract)
# ---------------------------------------------------------------------------

def test_mxlint_cost_report_passes_on_head_and_fails_on_regressions(
        tmp_path, capsys):
    cli = _cli()

    # HEAD against the committed budgets: clean at --fail-on=warn
    rc = cli.main(["--cost-report", "--budgets", BUDGETS_PATH,
                   "--fail-on", "warn", "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0 and out["failing"] == 0
    assert "quantization.convnet_int8" in out["programs"]
    assert out["budget_deltas"]["quantization.convnet_int8"][
        "dequant_fp32_dot"]["ok"]

    # seeded regression 1: a shrunk bucket cap = extra collectives/step
    rc = cli.main(["--cost-report", "--budgets", BUDGETS_PATH,
                   "--bucket-mb", "0.05", "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1 and out["failing"] >= 1
    assert not out["budget_deltas"]["dp8_bucketed_convnet"][
        "collectives_per_step"]["ok"]

    # seeded regression 2: a forced f32 upcast inside a bf16 graph
    kw = {"dtype": "bfloat16"}
    c, hw = 3, 32
    data = sym.Variable("data", shape=(8, c, hw, hw), **kw)
    x = sym.Convolution(data, sym.Variable("conv0_weight",
                                           shape=(16, c, 3, 3), **kw),
                        no_bias=True, kernel=(3, 3), num_filter=16,
                        pad=(1, 1), name="conv0")
    x = sym.Cast(x, dtype="float32", name="forced_upcast")
    x = sym.Flatten(x, name="flatten0")
    out_sym = sym.FullyConnected(
        x, sym.Variable("fc0_weight", shape=(32, 16 * hw * hw)),
        sym.Variable("fc0_bias", shape=(32,)), num_hidden=32, name="fc0")
    fixture = tmp_path / "upcast-symbol.json"
    fixture.write_text(out_sym.tojson())
    rc = cli.main(["--cost-report", "--budgets", BUDGETS_PATH,
                   str(fixture), "--json"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    fixture_prog = out["programs"]["upcast-symbol.json"]
    assert fixture_prog["counters"]["f32_upcasts"] == 1
    assert any(f["code"] == "f32-upcast-in-bf16"
               and f["node"] == "forced_upcast"
               for f in fixture_prog["findings"])


def test_mxlint_fail_on_contract(tmp_path, capsys):
    """--fail-on={hint,warn,error} is the documented exit-code ladder:
    exit 1 iff a finding at/above the threshold survives --suppress."""
    cli = _cli()
    # a script whose only finding is a WARN (host-sync-in-loop)
    warn_py = tmp_path / "warny.py"
    warn_py.write_text("for b in it:\n    print(x.asnumpy())\n")
    # a graph whose only finding is a HINT (tpu-layout)
    hint_json = tmp_path / "hint-symbol.json"
    hint_json.write_text(sym.FullyConnected(
        sym.Variable("data"), num_hidden=100, no_bias=True,
        name="odd").tojson())

    assert cli.main([str(warn_py)]) == 1                  # default: warn
    capsys.readouterr()
    assert cli.main([str(warn_py), "--fail-on", "error"]) == 0
    capsys.readouterr()
    # suppression drains the gate
    assert cli.main([str(warn_py), "--fail-on", "warn",
                     "--suppress", "host-sync-in-loop"]) == 0
    capsys.readouterr()

    assert cli.main([str(hint_json)]) == 0                # hints pass...
    capsys.readouterr()
    rc = cli.main([str(hint_json), "--fail-on", "hint", "--json"])
    out = json.loads(capsys.readouterr().out)             # ...until asked
    assert rc == 1 and out["by_code"].get("tpu-layout", 0) >= 1
    assert cli.main([str(hint_json), "--fail-on", "hint",
                     "--suppress", "tpu-layout"]) == 0
    capsys.readouterr()


def test_host_transfer_in_graph_source_lint():
    src = ("import jax\n"
           "import numpy as np\n"
           "@jax.jit\n"
           "def step(w, x):\n"
           "    hw = np.asarray(w)\n"
           "    return x.asnumpy() + hw\n"
           "def host_side(w):\n"
           "    return np.asarray(w)\n")
    report = analysis.check_source(src, filename="t.py")
    hits = [f for f in report if f.code == "host-transfer-in-graph"]
    assert {f.location for f in hits} == {"t.py:5", "t.py:6"}
    # outside a traced function numpy coercion is fine
    assert not [f for f in hits if f.location == "t.py:8"]
