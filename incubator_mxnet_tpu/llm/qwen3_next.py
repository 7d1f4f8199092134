"""Gluon hybrid linear-attention LM (the Qwen3-Next family) and its
`Module.fit` training symbol, beside `TransformerLM`.

    tokens (B, T) --Embedding--> (B, T, C)
      N x [ h = x + Mixer(RMSNorm(x));  y = h + MoE(RMSNorm(h)) ]
      final RMSNorm -> untied head (FullyConnected, no bias) -> (B, T, V)

Layer i (0-based) mixes with gated softmax attention if
(i + 1) % full_attention_interval == 0 (`GatedAttentionMixer`: grouped-query
`BlockwiseAttention` behind per-head RMS norms and a partial
`RotaryEmbedding`, its output gated by a sigmoid), else with the gated delta
rule (`GatedDeltaNetMixer`: `CausalConv1D`, `GatedDeltaGates`,
`GatedDeltaRule`, a gated `RMSNorm`).  Every layer's feed-forward is
`SparseMoE`: `RoutedExperts` over the share of the experts this chip holds
(`parallel.ExpertShare`) plus a shared expert behind a sigmoid gate.

Layers of one kind are graph-identical, so `analysis/graph_passes.scan_plan`
folds a run of them (the three delta-rule layers of a period, or whole
periods of a deeper stack) into one scanned body; a layer kind that stands
alone between two others stays inlined.  Both new operator kinds ask a
scanned body to recompute its activations in the backward pass
(`OpDef.scan_remat`), all but what an operator's forward rule names as
kept (`ops.registry.scan_kept`): the outputs of the attention's and the
delta rule's forward kernels, so that neither runs a second time.
"""
from __future__ import annotations

from dataclasses import dataclass

from ..gluon import nn
from ..gluon.block import HybridBlock
from ..parallel.expert_parallel import ExpertShare


@dataclass
class Qwen3NextConfig:
    """Static shape of the LM; the names are the family's `config.json`'s."""
    vocab_size: int = 256
    hidden_size: int = 64
    num_hidden_layers: int = 4
    full_attention_interval: int = 4
    linear_num_key_heads: int = 2
    linear_key_head_dim: int = 16
    linear_num_value_heads: int = 4
    linear_value_head_dim: int = 16
    linear_conv_kernel_dim: int = 4
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 32
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    moe_intermediate_size: int = 32
    shared_expert_intermediate_size: int = 32
    num_experts: int = 16            # routed over, wherever they are held
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    experts_held: ExpertShare = None  # None: all of them
    param_dtype: str = "float32"

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = ExpertShare(self.num_experts)

    @classmethod
    def from_dict(cls, d):
        """From a dict of the family's keys; `experts_held` may be a dict
        {"offset", "count", "of"} (`of`: the experts routed over)."""
        return _from_keys(cls, d)

    def is_attention_layer(self, i):
        return (i + 1) % self.full_attention_interval == 0


def _from_keys(cls, d):
    """A configuration dataclass from a dict of its family's keys (others
    are passed over); `experts_held` may be a dict {"offset", "count",
    "of"}, `of` the experts routed over."""
    d = dict(d)
    held = d.get("experts_held")
    if isinstance(held, dict):
        d["num_experts"] = int(held.get("of", d.get("num_experts")))
        d["experts_held"] = ExpertShare(
            d["num_experts"], int(held.get("offset", 0)), int(held["count"]))
    return cls(**{k: v for k, v in d.items()
                  if k in cls.__dataclass_fields__})


def _dense(units, in_units, dtype, prefix):
    return nn.Dense(units, flatten=False, use_bias=False, in_units=in_units,
                    dtype=dtype, prefix=prefix)


class RMSNorm(HybridBlock):
    """Root-mean-square norm over the last axis; `zero_centered` scales by
    1 + gamma with gamma initialised 0."""

    def __init__(self, in_channels, eps=1e-6, zero_centered=True,
                 dtype="float32", **kwargs):
        super().__init__(**kwargs)
        self._kwargs = {"eps": eps, "zero_centered": zero_centered}
        with self.name_scope():
            self.gamma = self.params.get(
                "gamma", shape=(in_channels,), dtype=dtype,
                init="zeros" if zero_centered else "ones",
                allow_deferred_init=True)

    def hybrid_forward(self, F, x, gamma):
        return F.RMSNorm(x, gamma, name="fwd", **self._kwargs)


class GatedDeltaNetMixer(HybridBlock):
    """Gated DeltaNet token mixer (arXiv:2412.06464) as the family wires
    it: fused projections [q | k | v | z] and [b | a], a causal depthwise
    convolution and SiLU on q | k | v, the gated delta rule, a per-head RMS
    norm gated by SiLU(z), the output projection."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        c, dt = cfg.hidden_size, cfg.param_dtype
        self._cfg = cfg
        self._kd = cfg.linear_num_key_heads * cfg.linear_key_head_dim
        self._vd = cfg.linear_num_value_heads * cfg.linear_value_head_dim
        hv = cfg.linear_num_value_heads
        with self.name_scope():
            self.qkvz = _dense(2 * self._kd + 2 * self._vd, c, dt, "qkvz_")
            self.ba = _dense(2 * hv, c, dt, "ba_")
            self.conv_weight = self.params.get(
                "conv_weight", dtype=dt, allow_deferred_init=True,
                shape=(2 * self._kd + self._vd, cfg.linear_conv_kernel_dim))
            self.a_log = self.params.get(
                "a_log", shape=(hv,), dtype=dt, init="loguniform",
                allow_deferred_init=True)
            self.dt_bias = self.params.get(
                "dt_bias", shape=(hv,), dtype=dt, init="ones",
                allow_deferred_init=True)
            self.norm_gamma = self.params.get(
                "norm_gamma", shape=(cfg.linear_value_head_dim,), dtype=dt,
                init="ones", allow_deferred_init=True)
            self.out_proj = _dense(c, self._vd, dt, "out_proj_")

    def hybrid_forward(self, F, x, conv_weight, a_log, dt_bias, norm_gamma):
        cfg, kd, vd = self._cfg, self._kd, self._vd
        hv, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
        qkvz = self.qkvz(x)
        ba = self.ba(x)
        qkv = F.slice_axis(qkvz, axis=-1, begin=0, end=2 * kd + vd)
        z = F.slice_axis(qkvz, axis=-1, begin=2 * kd + vd,
                         end=2 * kd + 2 * vd)
        qkv = F.CausalConv1D(qkv, conv_weight, name="conv",
                             kernel=cfg.linear_conv_kernel_dim)
        qkv = F.Activation(qkv, act_type="silu")
        q = F.slice_axis(qkv, axis=-1, begin=0, end=kd)
        k = F.slice_axis(qkv, axis=-1, begin=kd, end=2 * kd)
        v = F.slice_axis(qkv, axis=-1, begin=2 * kd, end=2 * kd + vd)
        gates = F.GatedDeltaGates(
            F.slice_axis(ba, axis=-1, begin=hv, end=2 * hv),
            F.slice_axis(ba, axis=-1, begin=0, end=hv), a_log, dt_bias,
            name="gates")
        o = F.GatedDeltaRule(
            q, k, v, gates[0], gates[1], name="delta_rule",
            num_heads=cfg.linear_num_key_heads, num_v_heads=hv)
        y = F.RMSNorm(F.Reshape(o, shape=(0, 0, hv, dv)), norm_gamma,
                      F.Reshape(z, shape=(0, 0, hv, dv)), name="norm",
                      eps=cfg.rms_norm_eps, gated=True)
        return self.out_proj(F.Reshape(y, shape=(0, 0, -1)))


class GatedAttentionMixer(HybridBlock):
    """Grouped-query softmax attention with per-head q/k RMS norms
    (`zero_centered` or plain; none with `qk_norm=False`), a rotary
    embedding on `cfg.partial_rotary_factor` of a head and, with `gate`, a
    sigmoid gate on its output, projected beside q.  `llm/lfm2.py` takes it
    without the gate, with plain norms and the rotary embedding on the whole
    head; `llm/sdar.py` likewise, under another `mask` (`BlockwiseAttention`'s
    mask parameters; causal unless told) and with the time axis holding
    `copies` copies of the sequence's positions; `llm/ouro.py` without the
    gate and without q/k norms, one query head a key-value head."""

    def __init__(self, cfg, gate=True, zero_centered=True, mask=None,
                 copies=1, qk_norm=True, **kwargs):
        super().__init__(**kwargs)
        c, dt = cfg.hidden_size, cfg.param_dtype
        self._cfg, self._gate, self._qk_norm = cfg, gate, qk_norm
        self._mask = dict(mask or {"causal": True})
        self._copies = {} if copies == 1 else {"copies": int(copies)}
        h, kv, d = cfg.num_attention_heads, cfg.num_key_value_heads, \
            cfg.head_dim
        with self.name_scope():
            self.q_proj = _dense((2 if gate else 1) * h * d, c, dt,
                                 "q_proj_")
            self.k_proj = _dense(kv * d, c, dt, "k_proj_")
            self.v_proj = _dense(kv * d, c, dt, "v_proj_")
            if qk_norm:
                self.q_norm = RMSNorm(d, cfg.rms_norm_eps, zero_centered,
                                      dtype=dt, prefix="q_norm_")
                self.k_norm = RMSNorm(d, cfg.rms_norm_eps, zero_centered,
                                      dtype=dt, prefix="k_norm_")
            self.out_proj = _dense(c, h * d, dt, "out_proj_")

    def hybrid_forward(self, F, x):
        cfg = self._cfg
        h, kv, d = cfg.num_attention_heads, cfg.num_key_value_heads, \
            cfg.head_dim
        rope = {"rotary_dim": int(d * cfg.partial_rotary_factor),
                "base": cfg.rope_theta, **self._copies,
                **({"after_norm": True} if self._qk_norm else {})}
        q = qg = self.q_proj(x)
        if self._gate:
            q = F.slice_axis(qg, axis=-1, begin=0, end=h * d)
            gate = F.slice_axis(qg, axis=-1, begin=h * d, end=2 * h * d)
        q_norm, k_norm = (self.q_norm, self.k_norm) if self._qk_norm else \
            (lambda a: a, lambda a: a)
        q = F.RotaryEmbedding(
            q_norm(F.Reshape(q, shape=(0, 0, h, d))), **rope)
        k = F.RotaryEmbedding(
            k_norm(F.Reshape(self.k_proj(x), shape=(0, 0, kv, d))), **rope)
        attn = F.BlockwiseAttention(
            F.Reshape(q, shape=(0, 0, -1)), F.Reshape(k, shape=(0, 0, -1)),
            self.v_proj(x), name="attention", num_heads=h, num_kv_heads=kv,
            **self._mask)
        if self._gate:
            attn = attn * F.Activation(gate, act_type="sigmoid")
        return self.out_proj(attn)


class SparseMoE(HybridBlock):
    """The experts this chip holds of a routed layer, plus (with `shared`)
    the shared expert every chip computes, behind its scalar sigmoid gate
    (`shared_gate`; without it the shared expert's output is added as it
    is, as DeepSeek-V3's shared experts are).  `router`: `RoutedExperts`'
    router parameters beyond the softmax router's (`scoring`,
    `select_bias`, `norm_eps`, `scale`, `capacity_factor`); with
    `select_bias` the layer holds the selection bias as an auxiliary
    state, which no gradient and no optimizer reaches."""

    def __init__(self, cfg, shared=True, router=None, shared_gate=True,
                 **kwargs):
        super().__init__(**kwargs)
        c, dt = cfg.hidden_size, cfg.param_dtype
        inter, held = cfg.moe_intermediate_size, cfg.experts_held
        self._cfg, self._shared = cfg, shared
        self._shared_gate = shared and shared_gate
        self._router = dict(router or {})
        with self.name_scope():
            def get(name, shape, **kw):
                return self.params.get(name, shape=shape, dtype=dt,
                                       allow_deferred_init=True, **kw)
            self.router_weight = get("router_weight", (cfg.num_experts, c))
            self.experts_gate_weight = get("experts_gate_weight",
                                           (held.count, inter, c))
            self.experts_up_weight = get("experts_up_weight",
                                         (held.count, inter, c))
            self.experts_down_weight = get("experts_down_weight",
                                           (held.count, c, inter))
            def state(name, count):
                return self.params.get(
                    name, shape=(count,), grad_req="null", init="zeros",
                    allow_deferred_init=True, differentiable=False)
            if self._router.get("select_bias"):
                self.select_bias = state("select_bias", cfg.num_experts)
            self.load = state("load", held.count)
            self.dropped = state("dropped", 2)
            if shared:
                width = cfg.shared_expert_intermediate_size
                self.shared_gate = _dense(width, c, dt, "shared_gate_")
                self.shared_up = _dense(width, c, dt, "shared_up_")
                self.shared_down = _dense(c, width, dt, "shared_down_")
                if self._shared_gate:
                    self.shared_sigmoid = _dense(1, c, dt,
                                                 "shared_sigmoid_")

    def hybrid_forward(self, F, x, router_weight, experts_gate_weight,
                       experts_up_weight, experts_down_weight, load,
                       dropped, select_bias=None):
        cfg = self._cfg
        states = (load, dropped) if select_bias is None else \
            (select_bias, load, dropped)
        routed = F.RoutedExperts(
            x, router_weight, experts_gate_weight, experts_up_weight,
            experts_down_weight, *states, name="experts",
            top_k=cfg.num_experts_per_tok, norm_topk=cfg.norm_topk_prob,
            **cfg.experts_held.op_params(), **self._router)
        if not self._shared:
            return routed
        shared = self.shared_down(
            F.Activation(self.shared_gate(x), act_type="silu") *
            self.shared_up(x))
        if not self._shared_gate:
            return routed + shared
        return routed + F.broadcast_mul(
            F.Activation(self.shared_sigmoid(x), act_type="sigmoid"), shared)


class Qwen3NextBlock(HybridBlock):
    """One layer: pre-norm mixer and pre-norm sparse feed-forward, each
    around a residual."""

    def __init__(self, cfg, attention, **kwargs):
        super().__init__(**kwargs)
        dt = cfg.param_dtype
        with self.name_scope():
            self.norm1 = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps, dtype=dt,
                                 prefix="norm1_")
            self.mixer = GatedAttentionMixer(cfg, prefix="attn_") \
                if attention else GatedDeltaNetMixer(cfg, prefix="gdn_")
            self.norm2 = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps, dtype=dt,
                                 prefix="norm2_")
            self.moe = SparseMoE(cfg, prefix="moe_")

    def hybrid_forward(self, F, x):
        h = x + self.mixer(self.norm1(x))
        return h + self.moe(self.norm2(h))


class Qwen3NextLM(HybridBlock):
    """Embedding -> blocks in periods of `full_attention_interval` -> final
    RMS norm -> untied head."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self.cfg = cfg
        dt = cfg.param_dtype
        with self.name_scope():
            self.embed_weight = self.params.get(
                "embed_weight", shape=(cfg.vocab_size, cfg.hidden_size),
                dtype=dt, allow_deferred_init=True)
            self.blocks = nn.HybridSequential(prefix="")
            for i in range(cfg.num_hidden_layers):
                self.blocks.add(Qwen3NextBlock(
                    cfg, cfg.is_attention_layer(i), prefix="layer%d_" % i))
            self.final_norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                      dtype=dt, prefix="final_norm_")
            self.head = _dense(cfg.vocab_size, cfg.hidden_size, dt, "head_")

    def hybrid_forward(self, F, tokens, embed_weight):
        cfg = self.cfg
        h = F.Embedding(tokens, embed_weight, input_dim=cfg.vocab_size,
                        output_dim=cfg.hidden_size)
        return self.head(self.final_norm(self.blocks(h)))


# how a fresh `Module.fit` initialises the variables whose names say
# nothing to an `Initializer` (the gluon path has them on its parameters)
_VARIABLE_INIT = (("a_log", '["loguniform", {}]'), ("dt_bias", "ones"),
                  ("gdn_norm_gamma", "ones"), ("_gamma", "zeros"),
                  ("moe_load", "zeros"), ("moe_dropped", "zeros"))


def _loss_symbol(model, vocab_size, prefix, variable_init):
    """`model` (tokens -> logits) under a next-token cross-entropy head,
    its variables told how a fresh `Module.fit` initialises them."""
    from .. import symbol as sym
    logits = model(sym.Variable("data"))                 # (B, T, V)
    pred = sym.Reshape(logits, shape=(-1, vocab_size))
    label = sym.Reshape(sym.Variable("softmax_label"), shape=(-1,))
    return _init_variables(sym.SoftmaxOutput(pred, label, name="softmax"),
                           prefix, variable_init)


def _init_variables(out, prefix, variable_init):
    """`out`, its variables told how a fresh `Module.fit` initialises
    them."""
    for node in out._topo():
        if node.is_variable and node.name.startswith(prefix):
            init = next((i for suffix, i in variable_init
                         if node.name.endswith(suffix)), None)
            if init is not None:
                node._extra_attrs.setdefault("__init__", init)
    return out


def qwen3_next_symbol(cfg, prefix="lm_"):
    """`Module.fit`-ready training graph: next-token cross-entropy, as
    `lm_symbol` builds it for `TransformerLM`.  data (B, T) tokens;
    softmax_label (B, T) targets (the caller shifts)."""
    return _loss_symbol(Qwen3NextLM(cfg, prefix=prefix), cfg.vocab_size,
                        prefix, _VARIABLE_INIT)
