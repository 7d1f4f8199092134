"""Gluon looped LM (the Ouro family: ByteDance's LoopLM, "Scaling Latent
Reasoning via Looped Language Models", arXiv:2510.25741) and its
`Module.fit` training symbol.

    ids x (B, L) --Embedding--> h^0 (B, L, C)
      for t = 1..T:  h^t = RMSNorm_f(stack(h^{t-1}))     (ONE loop: the same
                     layers and the same final norm every step)
      step t:  z_t = W_head h^t,  L_t = -log softmax(z_t)[y],
               lambda_t = sigmoid(w_g h^t + b_g)
      p_t = lambda_t prod_{j<t} (1 - lambda_j) (t < T),  p_T = prod_{j<T} ...
      objective, every position:  sum_t p_t L_t - beta H(p)

A layer has sandwich norms, as the family lays them out:

    a  = u + RMSNorm_1b(Attn(RMSNorm_1a(u)))
    u' = a + RMSNorm_2b(SwiGLU(RMSNorm_2a(a)))

Attn is `GatedAttentionMixer` of `llm/qwen3_next.py` without its gate and
without q/k norms (full multi-head, causal, rotary on the whole head);
SwiGLU is `llm/lfm2.DenseSwiGLU`.  The T steps are one `contrib.foreach`:
one `lax.scan` over the steps whose body is the held layers and the final
norm, re-materialised in training (`ops/control_flow._foreach`), the layer
weights loop invariants whose gradients the scan's transpose sums.  The
stacked normed states (T, B L, C) go to `LoopExitOutput`
(`ops/loss_output.py`), which walks the steps' heads one at a time.
Training path only: a decode step through the loop is not here
(`llm/decode_core.py`, ROADMAP).
"""
from __future__ import annotations

from dataclasses import dataclass

from ..base import MXNetError
from ..gluon import nn
from ..gluon.block import HybridBlock
from .lfm2 import DenseSwiGLU
from .qwen3_next import RMSNorm, GatedAttentionMixer, _from_keys


@dataclass
class OuroConfig:
    """Static shape of the LM; the names are the family's `config.json`'s,
    then the training objective's (which the family's file does not hold):
    `exit_entropy_weight` is the entropy's weight beta."""
    vocab_size: int = 256
    hidden_size: int = 64
    num_hidden_layers: int = 4
    num_attention_heads: int = 4
    num_key_value_heads: int = 4
    head_dim: int = 16
    intermediate_size: int = 96
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-6
    total_ut_steps: int = 4
    layer_types: tuple = None
    tie_word_embeddings: bool = False
    exit_entropy_weight: float = 0.1
    param_dtype: str = "float32"
    # (not a field) the attention mixer turns the whole head
    partial_rotary_factor = 1.0

    def __post_init__(self):
        kinds = set(self.layer_types or ("full_attention",))
        if kinds != {"full_attention"} or self.tie_word_embeddings or \
                self.total_ut_steps < 1:
            raise MXNetError(
                "OuroConfig: the family's layers are full attention, its "
                "head untied and total_ut_steps >= 1; got layer_types %s, "
                "tie_word_embeddings %r, total_ut_steps %r"
                % (sorted(kinds), self.tie_word_embeddings,
                   self.total_ut_steps))

    @classmethod
    def from_dict(cls, d):
        """From a dict of the family's keys (others are passed over)."""
        return _from_keys(cls, d)


class OuroBlock(HybridBlock):
    """One layer: attention and SwiGLU, each between two RMS norms (one on
    its input, one on its output) and around a residual."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        dt, eps, c = cfg.param_dtype, cfg.rms_norm_eps, cfg.hidden_size

        def norm(prefix):
            return RMSNorm(c, eps, False, dtype=dt, prefix=prefix)
        with self.name_scope():
            self.norm1a, self.norm1b = norm("norm1a_"), norm("norm1b_")
            self.mixer = GatedAttentionMixer(
                cfg, gate=False, zero_centered=False, qk_norm=False,
                prefix="attn_")
            self.norm2a, self.norm2b = norm("norm2a_"), norm("norm2b_")
            self.mlp = DenseSwiGLU(cfg, prefix="mlp_")

    def hybrid_forward(self, F, u):
        a = u + self.norm1b(self.mixer(self.norm1a(u)))
        return a + self.norm2b(self.mlp(self.norm2a(a)))


class OuroLM(HybridBlock):
    """(ids (B, L), next ids (B, L)) -> the last step's probabilities
    (B L, V), trained under the exit objective."""

    def __init__(self, cfg, **kwargs):
        super().__init__(**kwargs)
        self.cfg = cfg
        dt, c = cfg.param_dtype, cfg.hidden_size
        with self.name_scope():
            self.embed_weight = self.params.get(
                "embed_weight", shape=(cfg.vocab_size, c), dtype=dt,
                allow_deferred_init=True)
            self.head_weight = self.params.get(
                "head_weight", shape=(cfg.vocab_size, c), dtype=dt,
                allow_deferred_init=True)
            self.exit_gate_weight = self.params.get(
                "exit_gate_weight", shape=(1, c), dtype=dt,
                allow_deferred_init=True)
            self.exit_gate_bias = self.params.get(
                "exit_gate_bias", shape=(1,), dtype=dt, init="zeros",
                allow_deferred_init=True)
            self.exit_stats = self.params.get(
                "exit_stats", shape=(cfg.total_ut_steps + 3,),
                grad_req="null", init="zeros", allow_deferred_init=True,
                differentiable=False)
            self.blocks = nn.HybridSequential(prefix="")
            for i in range(cfg.num_hidden_layers):
                self.blocks.add(OuroBlock(cfg, prefix="layer%d_" % i))
            self.final_norm = RMSNorm(c, cfg.rms_norm_eps, False, dtype=dt,
                                      prefix="final_norm_")

    def hybrid_forward(self, F, tokens, label, embed_weight, head_weight,
                       exit_gate_weight, exit_gate_bias, exit_stats):
        cfg = self.cfg
        c = cfg.hidden_size
        h0 = F.Embedding(tokens, embed_weight, input_dim=cfg.vocab_size,
                         output_dim=c)

        def step(_, h):
            h = self.final_norm(self.blocks(h))
            return h, h
        states, _ = F.contrib.foreach(
            step, F._internal._zeros(shape=(cfg.total_ut_steps,)), h0,
            name=self.prefix + "loop")
        return F.LoopExitOutput(
            F.Reshape(states, shape=(0, -1, c)), head_weight,
            exit_gate_weight, exit_gate_bias, F.Reshape(label, shape=(-1,)),
            exit_stats, name="exit", beta=cfg.exit_entropy_weight)


# how a fresh `Module.fit` initialises the variables whose names say
# nothing to an `Initializer` (the gluon path has them on its parameters)
_VARIABLE_INIT = (("_gamma", "ones"), ("exit_gate_bias", "zeros"),
                  ("exit_stats", "zeros"))


def ouro_symbol(cfg, prefix="lm_"):
    """`Module.fit`-ready training graph: data (B, L) ids, softmax_label
    (B, L) next ids (the caller shifts).  Output 0 is the (B L, V)
    probabilities of the last recurrent step, which a metric reads against
    `softmax_label`; the gradient is the exit objective's, summed over the
    positions (the optimizer's `rescale_grad` normalises)."""
    from .. import symbol as sym
    from .qwen3_next import _init_variables
    out = OuroLM(cfg, prefix=prefix)(sym.Variable("data"),
                                     sym.Variable("softmax_label"))
    return _init_variables(out, prefix, _VARIABLE_INIT)
