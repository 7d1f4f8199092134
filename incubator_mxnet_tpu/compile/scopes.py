"""From a compiled program's optimized HLO text to the framework's names.

`fused.FusedTrainStep` runs its phases, and `symbol.graph_eval_fn` each
graph node's operator, under `jax.named_scope`s.  XLA carries them through
compilation as the `op_name` metadata of every HLO instruction, e.g.

    jit(stepk)/while/body/closed_call/fwd/jvp(Convolution:conv1)/conv_general_dilated
    jit(stepk)/while/body/closed_call/bwd/transpose(jvp(Convolution:conv1))/...

A profile names its device events by HLO instruction (``fusion.3426``);
this module gives, per instruction of an executable, the phase, the MXNet
operator kind and the node it came from.  The rules live HERE, once, so
that an operator's tool and a benchmark's readers cannot disagree.
Reached through `compile.op_scopes`; nothing is parsed before that call.
"""
from __future__ import annotations

import functools
import re

PHASES = ("fwd", "bwd", "exchange", "optimizer", "guardian", "metric")
OTHER = "other"

# `  ROOT %fusion.3 = f32[8]{0} fusion(...), kind=kLoop, calls=%fused.1,
#  metadata={op_name="..." ...}`; the `%` is optional in newer prints
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_APPLIES = re.compile(r"\bto_apply=%?([\w.\-]+)")
_NODE = re.compile(r"([A-Za-z_]\w*):([^/();\"]+)")


# operator kinds whose node runs a subgraph as a loop (ops/control_flow.py)
LOOPS = ("_foreach", "_while_loop")


@functools.lru_cache(maxsize=None)
def _classified(op_name):
    path = op_name.split(";", 1)[0]
    phase = next((part for part in path.split("/") if part in PHASES),
                 OTHER)
    if phase == "fwd" and "transpose(" in path:
        phase = "bwd"
    nodes = _NODE.findall(path)
    op, node = nodes[-1] if nodes else (None, None)
    loop = next((name for kind, name in nodes if kind in LOOPS), None)
    return phase, op, node, loop


def classify(op_name):
    """`{"phase", "op", "node", "loop"}` of one `op_name` (XLA joins the
    names of merged instructions with ``;``: the first speaks).  `phase` is
    the outermost of `PHASES` on the scope path, else ``other``; an op
    under ``fwd`` that is a transpose belongs to the backward pass.  `op`
    and `node` are the innermost graph node's ``Kind:name`` scope, or None:
    inside a loop's body, the body's node, not the loop's.  `loop` is the
    name of the outermost loop node (`LOOPS`) on the path, or None."""
    return dict(zip(("phase", "op", "node", "loop"),
                    _classified(op_name)))


def parse(hlo_text):
    """`{instruction: {"phase", "op", "node", "loop", "mixed"}}` of every
    instruction a device runs as an event of its own: those of the entry
    computation and of the bodies it calls, not the insides of fusions
    and reducers.  A fusion speaks through its own metadata, which XLA
    takes from the fusion's root; `mixed` says that the instructions of
    its fused computation fall in more than one of `PHASES`, or in one
    that is not the fusion's own, so that its time belongs to `phase` only
    by that choice (the optimizer's update fused into the guardian's
    `where` reads ``guardian``).  A mixed fusion also lists the phases
    `inside` it.  Instructions under no phase (a scan's indexing, XLA's
    own copies) ride along in nearly every fusion and mix nothing."""
    computations = {}     # name -> [(instruction, op_name | None, callee)]
    inner = set()         # fused computations and reducers
    current = None
    for line in hlo_text.splitlines():
        if current is None:
            m = _COMPUTATION.match(line)
            if m and "(" in line:
                current = computations.setdefault(m.group(1), [])
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        op_name = _OP_NAME.search(line)
        calls = _CALLS.search(line) if " fusion(" in line else None
        inner.update(c.group(1) for c in (calls, _APPLIES.search(line))
                     if c)
        current.append((m.group(1), op_name.group(1) if op_name else None,
                        calls.group(1) if calls else None))
    out = {}
    for name, rows in computations.items():
        if name in inner:
            continue
        for instruction, op_name, callee in rows:
            entry = classify(op_name or "")
            inside = {_classified(n)[0]
                      for _, n, _ in computations.get(callee, ()) if n}
            inside.discard(OTHER)
            entry["mixed"] = bool(inside - {entry["phase"]})
            if entry["mixed"]:
                entry["inside"] = sorted(inside)
            out[instruction] = entry
    return out


def of_executable(exe):
    """The map of one compiled (or deserialized) executable; {} where the
    runtime cannot print its HLO."""
    try:
        text = exe.as_text()
    except Exception:
        return {}
    return parse(text or "")
