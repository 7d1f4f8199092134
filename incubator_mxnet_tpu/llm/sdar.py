"""Gluon block-diffusion LM with routed experts (the SDAR mixture-of-experts
family, arXiv:2510.06303) and its `Module.fit` training symbol: the
family's autoregressive stack trained under BD3-LM's objective
(arXiv:2503.09573).

    clean ids x (B, L) --BlockDiffusionNoise--> noisy ids, mask m, weight m/t
    [noisy | x] (B, 2L) --Embedding (vocab_size + 1 rows, the last the
                          mask token)--> (B, 2L, C)
      N x [ h = x + Attn(RMSNorm(x));  y = h + MoE(RMSNorm(h)) ]
      the NOISY half's rows -> final RMSNorm -> untied head -> (B, L, V)

Every layer is the same: grouped-query softmax attention with per-head q/k
RMS norms and the rotary embedding on the whole head (`GatedAttentionMixer`
of `llm/qwen3_next.py` without its gate, under `BlockwiseAttention`'s
``block_diffusion`` mask, both copies at positions 0..L-1), and `SparseMoE`
of the same file without a shared expert, behind its softmax router.  A
block of `block_length` tokens draws one noise level t; a noisy row sees
its own noisy block (both directions) and the CLEAN blocks before it; the
loss weighs the noisy copy's row i by m_i / t and predicts x_i in place.
`analysis/graph_passes.scan_plan` folds the layers into one scanned,
re-materialised body.  Training path only: a decode step that yields a
block is not here (`llm/decode_core.py`, ROADMAP R8).
"""
from __future__ import annotations

from dataclasses import dataclass

from ..base import MXNetError
from ..gluon import nn
from ..gluon.block import HybridBlock
from ..parallel.expert_parallel import ExpertShare
from .qwen3_next import (RMSNorm, GatedAttentionMixer, SparseMoE, _dense,
                         _from_keys)


@dataclass
class SdarMoeConfig:
    """Static shape of the LM; the names are the family's `config.json`'s,
    then the training objective's (which the family's file does not
    hold)."""
    vocab_size: int = 256
    hidden_size: int = 64
    num_hidden_layers: int = 4
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 32
    rope_theta: float = 1000000.0
    moe_intermediate_size: int = 32
    num_experts: int = 16            # routed over, wherever they are held
    num_experts_per_tok: int = 4
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: tuple = ()
    rms_norm_eps: float = 1e-6
    # the objective: tokens a block, the interval a block's noise level is
    # drawn from, and the seed that makes the draw a function of the tokens
    # (None: fresh noise from the graph's random resource every step)
    block_length: int = 4
    noise_interval: tuple = (0.001, 1.0)
    noise_seed: int = None
    capacity_factor: float = 2.0     # `RoutedExperts`' rows, in mean loads
    experts_held: ExpertShare = None  # None: all of them
    param_dtype: str = "float32"
    # (not a field) the attention mixer turns the whole head
    partial_rotary_factor = 1.0

    def __post_init__(self):
        self.noise_interval = tuple(float(t) for t in self.noise_interval)
        if self.experts_held is None:
            self.experts_held = ExpertShare(self.num_experts)
        if self.decoder_sparse_step != 1 or tuple(self.mlp_only_layers):
            raise MXNetError(
                "SdarMoeConfig: decoder_sparse_step %r and mlp_only_layers "
                "%r ask for dense layers; the family publishes 1 and none, "
                "and this stack has no other"
                % (self.decoder_sparse_step, list(self.mlp_only_layers)))

    @classmethod
    def from_dict(cls, d):
        """From a dict of the family's keys; `experts_held` may be a dict
        {"offset", "count", "of"} (`of`: the experts routed over)."""
        return _from_keys(cls, d)

    @property
    def mask_token_id(self):
        """One further row of the embedding, after the vocabulary's."""
        return self.vocab_size

    def attention_mask(self):
        """`BlockwiseAttention`'s mask parameters."""
        return {"mask": "block_diffusion",
                "block_length": int(self.block_length)}


class SdarMoeBlock(HybridBlock):
    """One layer: pre-norm attention and pre-norm routed experts, each
    around a residual, over the 2L rows [noisy | clean]."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        dt, eps = cfg.param_dtype, cfg.rms_norm_eps
        with self.name_scope():
            self.norm1 = RMSNorm(cfg.hidden_size, eps, False, dtype=dt,
                                 prefix="norm1_")
            self.mixer = GatedAttentionMixer(
                cfg, gate=False, zero_centered=False,
                mask=cfg.attention_mask(), copies=2, prefix="attn_")
            self.norm2 = RMSNorm(cfg.hidden_size, eps, False, dtype=dt,
                                 prefix="norm2_")
            self.moe = SparseMoE(
                cfg, shared=False,
                router={"capacity_factor": float(cfg.capacity_factor)},
                prefix="moe_")

    def hybrid_forward(self, F, x):
        h = x + self.mixer(self.norm1(x))
        return h + self.moe(self.norm2(h))


class SdarMoeLM(HybridBlock):
    """Clean ids (B, L) -> (logits of the noisy copy (B, L, V), the mask
    (B, L), the weight m / t (B, L))."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self.cfg = cfg
        dt = cfg.param_dtype
        with self.name_scope():
            self.embed_weight = self.params.get(
                "embed_weight", shape=(cfg.vocab_size + 1, cfg.hidden_size),
                dtype=dt, allow_deferred_init=True)
            self.noise_stats = self.params.get(
                "noise_stats", shape=(3,), grad_req="null", init="zeros",
                allow_deferred_init=True, differentiable=False)
            self.blocks = nn.HybridSequential(prefix="")
            for i in range(cfg.num_hidden_layers):
                self.blocks.add(SdarMoeBlock(cfg, prefix="layer%d_" % i))
            self.final_norm = RMSNorm(cfg.hidden_size, cfg.rms_norm_eps,
                                      False, dtype=dt, prefix="final_norm_")
            self.head = _dense(cfg.vocab_size, cfg.hidden_size, dt, "head_")

    def hybrid_forward(self, F, tokens, embed_weight, noise_stats):
        cfg = self.cfg
        low, high = cfg.noise_interval
        noise = F.BlockDiffusionNoise(
            tokens, noise_stats, name="noise",
            block_length=cfg.block_length, mask_token=cfg.mask_token_id,
            low=low, high=high, seed=cfg.noise_seed)
        h = F.Embedding(F.concat(noise[0], tokens, dim=1), embed_weight,
                        input_dim=cfg.vocab_size + 1,
                        output_dim=cfg.hidden_size)
        noisy = F.split(self.blocks(h), num_outputs=2, axis=1)[0]
        return self.head(self.final_norm(noisy)), noise[1], noise[2]


# how a fresh `Module.fit` initialises the variables whose names say
# nothing to an `Initializer` (the gluon path has them on its parameters)
_VARIABLE_INIT = (("_gamma", "ones"), ("moe_load", "zeros"),
                  ("moe_dropped", "zeros"), ("noise_stats", "zeros"))


def sdar_moe_symbol(cfg, prefix="lm_"):
    """`Module.fit`-ready training graph of block-diffusion training.
    `data` (B, L): the clean sequence x, which the graph corrupts, runs as
    [noisy | clean] and predicts IN PLACE: the gradient is that of sum_i
    m_i / t * -log softmax(logits_i)[x_i] over the noisy copy's rows
    (`SoftmaxOutput(use_weight=True)` reads its label from `data`; the
    optimizer's `rescale_grad` normalises).  Output 0 is the (B L, V)
    probabilities of the noisy copy.  `softmax_label` (B, L) is what a
    metric reads output 0 against and reaches no gradient: bind the clean
    sequence again for the unweighted in-place cross-entropy."""
    from .. import symbol as sym
    from .qwen3_next import _init_variables
    data = sym.Variable("data")
    # an argument of the graph, as `Module` binds it; its value goes nowhere
    tokens = data + sym.zeros_like(sym.Variable("softmax_label"))
    logits, _, weight = SdarMoeLM(cfg, prefix=prefix)(tokens)
    out = sym.SoftmaxOutput(
        sym.Reshape(logits, shape=(-1, cfg.vocab_size)),
        sym.Reshape(tokens, shape=(-1,)), sym.Reshape(weight, shape=(-1,)),
        name="softmax", use_weight=True)
    return _init_variables(out, prefix, _VARIABLE_INIT)
