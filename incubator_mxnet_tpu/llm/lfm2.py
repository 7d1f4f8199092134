"""Gluon gated-short-convolution / attention hybrid LM with routed experts
(the LFM2 mixture-of-experts family) and its `Module.fit` training symbol.

    tokens (B, T) --Embedding--> (B, T, C)
      layer i:  h = x + Op_i(RMSNorm(x));  y = h + FF_i(RMSNorm(h))
      final RMSNorm -> head TIED to the embedding -> (B, T, V)

`Op_i` follows the published list `layer_types`: "conv" is the gated short
convolution (`ShortConvMixer`: one fused projection [B | C | x], the
operator `GatedShortConv`, the output projection), "full_attention" the
grouped-query softmax mixer of `llm/qwen3_next.py` without its output gate,
with plain RMS norms and the rotary embedding on the whole head.  `FF_i` is
a dense SwiGLU in the first `num_dense_layers` layers and `SparseMoE` of
`llm/qwen3_next.py` after them, without a shared expert and with the
family's router: sigmoid scores, a selection bias that moves the choice
alone (an auxiliary state: held still here, since the balancing rule that
moves it between steps is the training recipe's), weights over the chosen
scores' sum plus 1e-6, times `routed_scaling_factor` (`RoutedExperts`'
`scale`; the family publishes 1).

It is not one repeated block: `analysis/graph_passes.scan_plan` folds the
run of conv + routed layers between two attention layers into one scanned,
re-materialised body, and leaves the dense layer in front and the attention
layer inlined.  The head reads `embed_weight`, so that parameter's gradient
is the sum of the embedding's and the head's.
"""
from __future__ import annotations

from dataclasses import dataclass

from ..base import MXNetError
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..parallel.expert_parallel import ExpertShare
from .qwen3_next import (RMSNorm, GatedAttentionMixer, SparseMoE, _dense,
                         _from_keys, _loss_symbol)

ROUTER_EPS = 1e-6   # the family adds it to the chosen scores' sum


@dataclass
class Lfm2MoeConfig:
    """Static shape of the LM; the names are the family's `config.json`'s
    (its `rope_parameters.rope_theta` is `rope_theta` here)."""
    vocab_size: int = 256
    hidden_size: int = 64
    num_hidden_layers: int = 5
    layer_types: tuple = ("conv", "full_attention", "conv", "conv", "conv")
    num_dense_layers: int = 1
    conv_L_cache: int = 3
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    rope_theta: float = 1000000.0
    intermediate_size: int = 96
    moe_intermediate_size: int = 32
    num_experts: int = 16            # routed over, wherever they are held
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    # (not the family's key) `RoutedExperts`' rows, in mean loads: with the
    # selection bias held still nothing balances the router, and training
    # draws it to the experts held, past twice the mean within 20 steps
    capacity_factor: float = 4.0
    norm_eps: float = 1e-5
    experts_held: ExpertShare = None  # None: all of them
    param_dtype: str = "float32"
    # (not a field) the attention mixer turns the whole head
    partial_rotary_factor = 1.0

    def __post_init__(self):
        self.layer_types = tuple(self.layer_types)
        if self.experts_held is None:
            self.experts_held = ExpertShare(self.num_experts)
        if len(self.layer_types) != self.num_hidden_layers or \
                set(self.layer_types) - {"conv", "full_attention"}:
            raise MXNetError(
                "Lfm2MoeConfig: layer_types must name 'conv' or "
                "'full_attention' for each of the %d layers; got %s"
                % (self.num_hidden_layers, list(self.layer_types)))

    @classmethod
    def from_dict(cls, d):
        """From a dict of the family's keys; `experts_held` may be a dict
        {"offset", "count", "of"} (`of`: the experts routed over)."""
        d = dict(d)
        d.setdefault("rope_theta",
                     (d.get("rope_parameters") or {}).get("rope_theta",
                                                          cls.rope_theta))
        return _from_keys(cls, d)

    # what the mixers of `llm/qwen3_next.py` read under that family's names
    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def rms_norm_eps(self):
        return self.norm_eps

    def router(self):
        """`RoutedExperts`' router parameters."""
        return {"scoring": "sigmoid", "norm_eps": ROUTER_EPS,
                "select_bias": bool(self.use_expert_bias),
                "scale": float(self.routed_scaling_factor),
                "capacity_factor": float(self.capacity_factor)}


class ShortConvMixer(HybridBlock):
    """The family's gated short convolution: [B | C | x] = W_in x,
    y = W_out (C * conv_k(B * x)), a causal depthwise convolution of
    `conv_L_cache` taps, no bias, no activation."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        c, dt = cfg.hidden_size, cfg.param_dtype
        self._kernel = cfg.conv_L_cache
        with self.name_scope():
            self.in_proj = _dense(3 * c, c, dt, "in_proj_")
            self.conv_weight = self.params.get(
                "conv_weight", shape=(c, cfg.conv_L_cache), dtype=dt,
                allow_deferred_init=True)
            self.out_proj = _dense(c, c, dt, "out_proj_")

    def hybrid_forward(self, F, x, conv_weight):
        return self.out_proj(F.GatedShortConv(
            self.in_proj(x), conv_weight, name="conv", kernel=self._kernel))


class DenseSwiGLU(HybridBlock):
    """W_2 (SiLU(W_1 x) * W_3 x)."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        c, dt, width = cfg.hidden_size, cfg.param_dtype, cfg.intermediate_size
        with self.name_scope():
            self.w1 = _dense(width, c, dt, "w1_")
            self.w3 = _dense(width, c, dt, "w3_")
            self.w2 = _dense(c, width, dt, "w2_")

    def hybrid_forward(self, F, x):
        return self.w2(F.Activation(self.w1(x), act_type="silu") * self.w3(x))


class Lfm2MoeBlock(HybridBlock):
    """Layer `index`: pre-norm mixer and pre-norm feed-forward, each around
    a residual; which mixer and which feed-forward the config says."""

    def __init__(self, cfg, index, **kwargs):
        super().__init__(**kwargs)
        dt, eps = cfg.param_dtype, cfg.norm_eps
        with self.name_scope():
            self.norm1 = RMSNorm(cfg.hidden_size, eps, False, dtype=dt,
                                 prefix="norm1_")
            if cfg.layer_types[index] == "conv":
                self.mixer = ShortConvMixer(cfg, prefix="conv_")
            else:
                self.mixer = GatedAttentionMixer(
                    cfg, gate=False, zero_centered=False, prefix="attn_")
            self.norm2 = RMSNorm(cfg.hidden_size, eps, False, dtype=dt,
                                 prefix="norm2_")
            if index < cfg.num_dense_layers:
                self.ffn = DenseSwiGLU(cfg, prefix="ffn_")
            else:
                self.ffn = SparseMoE(cfg, shared=False, router=cfg.router(),
                                     prefix="moe_")

    def hybrid_forward(self, F, x):
        h = x + self.mixer(self.norm1(x))
        return h + self.ffn(self.norm2(h))


class Lfm2MoeLM(HybridBlock):
    """Embedding -> the layers `layer_types` lists -> final RMS norm -> the
    head, which is the embedding's matrix again."""

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
                self.blocks.add(Lfm2MoeBlock(cfg, i, prefix="layer%d_" % i))
            self.final_norm = RMSNorm(cfg.hidden_size, cfg.norm_eps, False,
                                      dtype=dt, prefix="final_norm_")

    def hybrid_forward(self, F, tokens, embed_weight):
        cfg = self.cfg
        h = F.Embedding(tokens, embed_weight, input_dim=cfg.vocab_size,
                        output_dim=cfg.hidden_size)
        return F.FullyConnected(
            self.final_norm(self.blocks(h)), embed_weight, name="head",
            num_hidden=cfg.vocab_size, no_bias=True, flatten=False)


# how a fresh `Module.fit` initialises the variables whose names say
# nothing to an `Initializer` (the gluon path has them on its parameters)
_VARIABLE_INIT = (("_gamma", "ones"), ("moe_load", "zeros"),
                  ("moe_dropped", "zeros"), ("moe_select_bias", "zeros"))


def lfm2_moe_symbol(cfg, prefix="lm_"):
    """`Module.fit`-ready training graph: next-token cross-entropy, as
    `qwen3_next_symbol` builds it.  data (B, T) tokens; softmax_label (B, T)
    targets (the caller shifts)."""
    return _loss_symbol(Lfm2MoeLM(cfg, prefix=prefix), cfg.vocab_size,
                        prefix, _VARIABLE_INIT)
