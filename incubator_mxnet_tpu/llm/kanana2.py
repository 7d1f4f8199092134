"""Gluon multi-head latent attention LM with routed experts (Kakao's
Kanana-2 family, DeepSeek-V3's architecture without the query's low-rank
path) and its `Module.fit` training symbol.

    tokens (B, T) --Embedding--> (B, T, C)
      layer i:  h = x + MLA(RMSNorm(x));  y = h + FF_i(RMSNorm(h))
      final RMSNorm -> untied head -> (B, T, V)

MLA, every layer (`MultiHeadLatentAttention`; H heads, scores over
qk_nope_head_dim + qk_rope_head_dim, values of v_head_dim):

    q          = W_q x                      H heads of [pe | nope]
    [c | k_pe] = W_kva x                    kv_lora_rank + qk_rope_head_dim
    [k_nope | v] = W_kvb RMSNorm(c)         H heads of [nope | v]
    q_pe, k_pe turned by the rotary embedding (rope_theta); the ONE k_pe is
    shared by every head: k = [k_pe | k_nope] a head
    o = softmax_causal(q k^T / sqrt(pe + nope)) v;   MLA = W_o o

The head's layout [pe | nope] and the rotate-half pairing of the rotary
embedding (entry i with i + pe/2) are this program's: the family's weights
lay a head out [nope | pe] and, with `rope_interleave`, pair entries 2i and
2i + 1.  Both are permutations of the rows of W_q and of W_kva's k_pe rows,
which a score q . k does not see (benchmark/configs/kanana2_30b_a3b_program.py
maps the family's weights onto this layout).

FF_i is a dense SwiGLU (`llm/lfm2.DenseSwiGLU`) in the first
`first_k_dense_replace` layers, and `SparseMoE` of `llm/qwen3_next.py` after
them: the family's router (sigmoid scores, a selection bias that moves the
choice alone, held still here as in `llm/lfm2.py`, weights over the chosen
scores' sum plus 1e-20, times `routed_scaling_factor`) over the experts this
chip holds, and the `n_shared_experts` shared experts as ONE ungated SwiGLU
of their summed width (two SwiGLUs side by side are one of twice the width).
`analysis/graph_passes.scan_plan` folds the routed layers into one scanned,
re-materialised body and leaves the dense layer inlined in front.  Training
path only: the latent cache of a decode step is not here (ROADMAP R5).
"""
from __future__ import annotations

from dataclasses import dataclass

from ..base import MXNetError
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..parallel.expert_parallel import ExpertShare
from .lfm2 import DenseSwiGLU
from .qwen3_next import RMSNorm, SparseMoE, _dense, _from_keys, _loss_symbol

ROUTER_EPS = 1e-20   # the family adds it to the chosen scores' sum


@dataclass
class Kanana2Config:
    """Static shape of the LM; the names are the family's `config.json`'s
    (DeepSeek-V3's).  `n_routed_experts` counts the experts routed over;
    `experts_held` the share of them this chip holds."""
    vocab_size: int = 256
    hidden_size: int = 64
    intermediate_size: int = 96
    moe_intermediate_size: int = 16
    num_hidden_layers: int = 5
    first_k_dense_replace: int = 1
    num_attention_heads: int = 4
    num_key_value_heads: int = 4
    kv_lora_rank: int = 32
    q_lora_rank: int = None
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    rope_theta: float = 1000000.0
    n_routed_experts: int = 16
    n_shared_experts: int = 2
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.448
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    moe_layer_freq: int = 1
    rms_norm_eps: float = 1e-6
    experts_held: ExpertShare = None  # None: all of them
    param_dtype: str = "float32"

    def __post_init__(self):
        if self.experts_held is None:
            self.experts_held = ExpertShare(self.n_routed_experts)
        given = (self.q_lora_rank, self.scoring_func, self.topk_method,
                 self.n_group, self.topk_group, self.moe_layer_freq,
                 self.num_key_value_heads == self.num_attention_heads)
        if given != (None, "sigmoid", "noaux_tc", 1, 1, 1, True):
            raise MXNetError(
                "Kanana2Config: this stack has no query low-rank path, a "
                "sigmoid router over one group of experts chosen by "
                "noaux_tc in every layer after the dense ones, and one "
                "key-value head a query head; got q_lora_rank %r, "
                "scoring_func %r, topk_method %r, n_group %r, topk_group "
                "%r, moe_layer_freq %r, %d / %d heads"
                % (self.q_lora_rank, self.scoring_func, self.topk_method,
                   self.n_group, self.topk_group, self.moe_layer_freq,
                   self.num_attention_heads, self.num_key_value_heads))

    @classmethod
    def from_dict(cls, d):
        """From a dict of the family's keys (others are passed over);
        `experts_held` may be a dict {"offset", "count", "of"}, `of` the
        experts routed over."""
        d = dict(d)
        held = d.get("experts_held")
        if isinstance(held, dict):
            d["n_routed_experts"] = int(held["of"])
        return _from_keys(cls, d)

    # what `SparseMoE` reads under the names of `llm/qwen3_next.py`
    @property
    def num_experts(self):
        return self.n_routed_experts

    @property
    def shared_expert_intermediate_size(self):
        return self.moe_intermediate_size * self.n_shared_experts

    @property
    def qk_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def router(self):
        """`RoutedExperts`' router parameters."""
        return {"scoring": "sigmoid", "norm_eps": ROUTER_EPS,
                "select_bias": True,
                "scale": float(self.routed_scaling_factor)}


class MultiHeadLatentAttention(HybridBlock):
    """DeepSeek-V3's attention without the query's low-rank path: keys and
    values through a normed latent of `kv_lora_rank`, one rotary key
    shared by every head, scores of qk_nope + qk_rope over values of
    v_head_dim, under `BlockwiseAttention` with a key-value head a query
    head.  Every node of the mixer is named ``mla_*``, so that a profile's
    instructions can be put down to it (`mx.compile.op_scopes`)."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        c, dt = cfg.hidden_size, cfg.param_dtype
        h, rank = cfg.num_attention_heads, cfg.kv_lora_rank
        self._cfg = cfg
        with self.name_scope():
            def get(name, shape, **kw):
                return self.params.get(name, shape=shape, dtype=dt,
                                       allow_deferred_init=True, **kw)
            self.q_proj_weight = get("q_proj_weight",
                                     (h * cfg.qk_head_dim, c))
            self.kv_a_proj_weight = get("kv_a_proj_weight",
                                        (rank + cfg.qk_rope_head_dim, c))
            self.kv_norm_gamma = get("kv_norm_gamma", (rank,), init="ones")
            self.kv_b_proj_weight = get(
                "kv_b_proj_weight",
                (h * (cfg.qk_nope_head_dim + cfg.v_head_dim), rank))
            self.o_proj_weight = get("o_proj_weight",
                                     (c, h * cfg.v_head_dim))

    def hybrid_forward(self, F, x, q_proj_weight, kv_a_proj_weight,
                       kv_norm_gamma, kv_b_proj_weight, o_proj_weight):
        cfg = self._cfg
        h, rank, pe = cfg.num_attention_heads, cfg.kv_lora_rank, \
            cfg.qk_rope_head_dim
        nope, dv = cfg.qk_nope_head_dim, cfg.v_head_dim
        rope = {"rotary_dim": pe, "base": cfg.rope_theta}

        def proj(name, a, w, units):
            return F.FullyConnected(a, w, name="mla_" + name,
                                    num_hidden=units, no_bias=True,
                                    flatten=False)

        def part(name, a, begin, end):
            return F.slice_axis(a, name="mla_" + name, axis=-1, begin=begin,
                                end=end)

        def shape(name, a, *dims):
            return F.Reshape(a, name="mla_" + name, shape=(0, 0) + dims)
        q = F.RotaryEmbedding(
            shape("q_heads", proj("q_proj", x, q_proj_weight,
                                  h * (pe + nope)), h, pe + nope),
            name="mla_q_rope", **rope)
        kva = proj("kv_a_proj", x, kv_a_proj_weight, rank + pe)
        k_pe = F.RotaryEmbedding(
            shape("k_pe", part("k_pe_part", kva, rank, rank + pe), 1, pe),
            name="mla_k_rope", **rope)
        latent = F.RMSNorm(part("latent", kva, 0, rank), kv_norm_gamma,
                           name="mla_kv_norm", eps=cfg.rms_norm_eps)
        kv = shape("kv_heads", proj("kv_b_proj", latent, kv_b_proj_weight,
                                    h * (nope + dv)), h, nope + dv)
        k = F.concat(F.broadcast_axis(k_pe, name="mla_k_pe_heads", axis=2,
                                      size=h),
                     part("k_nope", kv, 0, nope), name="mla_k", dim=3)
        attn = F.BlockwiseAttention(
            shape("q", q, -1), shape("k_flat", k, -1),
            shape("v", part("v_heads", kv, nope, nope + dv), -1),
            name="mla_attention", num_heads=h, num_kv_heads=h, causal=True)
        return proj("o_proj", attn, o_proj_weight, cfg.hidden_size)


class Kanana2Block(HybridBlock):
    """Layer `index`: pre-norm MLA and pre-norm feed-forward, each around a
    residual; the feed-forward dense in the first `first_k_dense_replace`
    layers, routed after them."""

    def __init__(self, cfg, index, **kwargs):
        super().__init__(**kwargs)
        dt, eps = cfg.param_dtype, cfg.rms_norm_eps
        with self.name_scope():
            self.norm1 = RMSNorm(cfg.hidden_size, eps, False, dtype=dt,
                                 prefix="norm1_")
            self.mixer = MultiHeadLatentAttention(cfg, prefix="mla_")
            self.norm2 = RMSNorm(cfg.hidden_size, eps, False, dtype=dt,
                                 prefix="norm2_")
            if index < cfg.first_k_dense_replace:
                self.ffn = DenseSwiGLU(cfg, prefix="ffn_")
            else:
                self.ffn = SparseMoE(cfg, router=cfg.router(),
                                     shared_gate=False, prefix="moe_")

    def hybrid_forward(self, F, x):
        h = x + self.mixer(self.norm1(x))
        return h + self.ffn(self.norm2(h))


class Kanana2LM(HybridBlock):
    """Embedding -> the layers -> final RMS norm -> untied head."""

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
                self.blocks.add(Kanana2Block(cfg, i, prefix="layer%d_" % i))
            self.final_norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                      False, dtype=dt, prefix="final_norm_")
            self.head = _dense(cfg.vocab_size, cfg.hidden_size, dt, "head_")

    def hybrid_forward(self, F, tokens, embed_weight):
        cfg = self.cfg
        h = F.Embedding(tokens, embed_weight, input_dim=cfg.vocab_size,
                        output_dim=cfg.hidden_size)
        return self.head(self.final_norm(self.blocks(h)))


# how a fresh `Module.fit` initialises the variables whose names say
# nothing to an `Initializer` (the gluon path has them on its parameters)
_VARIABLE_INIT = (("_gamma", "ones"), ("moe_load", "zeros"),
                  ("moe_dropped", "zeros"), ("moe_select_bias", "zeros"))


def kanana2_symbol(cfg, prefix="lm_"):
    """`Module.fit`-ready training graph: next-token cross-entropy, as
    `qwen3_next_symbol` builds it.  data (B, T) tokens; softmax_label (B, T)
    targets (the caller shifts)."""
    return _loss_symbol(Kanana2LM(cfg, prefix=prefix), cfg.vocab_size,
                        prefix, _VARIABLE_INIT)
