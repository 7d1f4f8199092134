"""`compile/scopes.py` names an instruction after the INNERMOST graph node on
its scope path and the outermost loop node around it (`loop`).  Held on
the compiled training programs of the benchmark's language-model
configurations at their `tiny` sizes (each cell's own adapter builds the
symbol): in the four that hold no loop every instruction reads as it read
under the rule it replaced (the first node on the path) and names no loop;
in the looped LM the body's attention and products read as their own kinds
inside the loop."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.analysis.graph_passes import scan_plan
from incubator_mxnet_tpu.compile import scopes
from incubator_mxnet_tpu.symbol import symbol as symbol_mod

from test_qwen3_next import cells

_NODE = re.compile(r"([A-Za-z_]\w*):([^/();\"]+)")


def _first_node(op_name):
    """The rule before loops: the FIRST `Kind:node` on the path."""
    path = op_name.split(";", 1)[0]
    phase = next((part for part in path.split("/")
                  if part in scopes.PHASES), scopes.OTHER)
    if phase == "fwd" and "transpose(" in path:
        phase = "bwd"
    node = _NODE.search(path)
    return (phase, node.group(1) if node else None,
            node.group(2) if node else None)


def _program_text(workload, monkeypatch):
    """The optimized HLO of one training step (outputs, auxiliary states,
    every argument's gradient) of the cell's symbol at its tiny size."""
    cell = cells.Cell(cells.benchmark_json(), workload, tiny=True)
    cfg, adapter = cell.cfg, cell.adapter
    # the looped LM's adapter sets the process's block knob; keep it here
    monkeypatch.setenv("MXNET_FUSED_STEP_BLOCK",
                       str(cfg.get("fused_step_block", 8)))
    symbol = adapter.build_symbol(mx, cfg)
    data, label = adapter.input_descs(cfg, 2)
    fixed = getattr(adapter, "fixed_params", lambda *a: {})(
        cfg, 2, symbol.list_arguments())
    shapes = {"data": data, "softmax_label": label,
              **{n: np.shape(a) for n, a in fixed.items()}}
    arg_shapes, _, aux_shapes = symbol.infer_shape(**shapes)
    rng = np.random.default_rng(0)
    args = []
    for name, shape in zip(symbol.list_arguments(), arg_shapes):
        if name in ("data", "softmax_label"):
            args.append(jnp.asarray(rng.integers(0, cfg["vocab_size"],
                                                 shape), jnp.float32))
        elif name in fixed:
            args.append(jnp.asarray(fixed[name], jnp.float32))
        else:
            args.append(jnp.asarray(0.05 * rng.standard_normal(shape),
                                    jnp.float32))
    aux = tuple(jnp.zeros(s, jnp.float32) for s in aux_shapes)
    fn, _, _, _ = symbol_mod.graph_eval_fn(symbol, True,
                                           scan=scan_plan(symbol))

    def step(args, aux, key):
        (outs, new_aux), vjp = jax.vjp(lambda a: fn(a, aux, key), args)
        grads, = vjp((tuple(jnp.ones_like(o) for o in outs),
                      tuple(jnp.zeros_like(a) for a in new_aux)))
        return outs, new_aux, grads
    return jax.jit(step).lower(tuple(args), aux, jax.random.PRNGKey(0)) \
        .compile().as_text()


@pytest.mark.parametrize("workload", [
    "lstm_ptb_train", "qwen3_next_train_hostfed", "lfm2_moe_train_hostfed",
    "sdar_moe_bd_train_hostfed"])
def test_maps_without_a_loop_are_unchanged(workload, monkeypatch):
    text = _program_text(workload, monkeypatch)
    now = scopes.parse(text)
    with monkeypatch.context() as m:
        m.setattr(scopes, "_classified", _first_node)
        before = scopes.parse(text)
    assert set(now) == set(before) and len(now) > 50
    kinds = set()
    for name, entry in now.items():
        assert entry.pop("loop") is None, name
        assert entry == before[name], name
        kinds.add(entry["op"])
    assert "FullyConnected" in kinds


def test_the_loops_body_reads_as_its_own_kinds(monkeypatch):
    text = _program_text("ouro_loop_train_hostfed", monkeypatch)
    now = scopes.parse(text)
    inside = {e["op"] for e in now.values() if e["loop"]}
    outside = {e["op"] for e in now.values() if not e["loop"]}
    (loop,) = {e["loop"] for e in now.values() if e["loop"]}
    assert loop.startswith("lm_loop")
    assert {"BlockwiseAttention", "FullyConnected", "RMSNorm"} <= inside
    # the head and its objective stand outside the loop
    assert "LoopExitOutput" in outside and "LoopExitOutput" not in inside
    # the rule it replaced read every instruction of the body as the loop's
    # own kind (what XLA hoists out of the loop, a weight's transpose, is
    # not under the loop's node and reads as its kind under both)
    with monkeypatch.context() as m:
        m.setattr(scopes, "_classified", _first_node)
        before = scopes.parse(text)
    assert {before[n]["op"] for n, e in now.items() if e["loop"]} == \
        {"_foreach"}
