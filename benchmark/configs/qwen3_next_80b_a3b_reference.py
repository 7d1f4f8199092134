"""Plain reference of the `qwen3_next_80b_a3b` configuration: the language
model of Qwen3-Next-80B-A3B-Instruct (config.json of the model card) cut to
one chip's share -- periods of three gated delta-rule layers and one gated
softmax-attention layer, every layer followed by routed experts plus a shared
expert -- written from the equations in straightforward `jax.numpy`, float32
at `Precision.HIGHEST`.  It imports nothing of the program under test.

Block:  h = x + Mixer(norm1(x)),  y = h + MoE(norm2(h)); layer i has the
softmax mixer if (i + 1) % full_attention_interval == 0.  norm1, norm2, the
final norm and the q/k norms are zero-centred RMS norms,
x / sqrt(mean(x^2) + eps) * (1 + w).

Delta-rule mixer (Gated DeltaNet, arXiv:2412.06464), per value head with a
float32 state S (key size x value size), S_0 = 0, TOKEN BY TOKEN:
    [q, k, v, z] = W_qkvz x;  [b, a] = W_ba x
    (q, k, v) <- SiLU(causal depthwise conv_4(q | k | v))
    q <- q / |q| * d_k^-1/2,  k <- k / |k|      (each key head serves
                                   value_heads / key_heads value heads)
    beta = sigmoid(b),  g = -exp(A_log) * softplus(a + dt_bias)
    S' = exp(g_t) S;  u = beta_t (v_t - S'^T k_t);  S = S' + k_t u^T
    o_t = S^T q_t;   y_t = RMSNorm(o_t; w) * SiLU(z_t);   out = W_o y
Softmax mixer:  [q, gate] = W_q x, k = W_k x, v = W_v x; q, k normed per head
and turned by the rotary embedding on the first `rotary` entries (rotate-half
pairing); causal softmax attention, each key-value head serving
heads / kv_heads query heads; out = W_o (attn * sigmoid(gate)).
Experts:  p = softmax(W_g x) over ALL experts, the top k, weights p_e / sum
of the chosen; E(x) = W_down (SiLU(W_gate x) * W_up x); layer output
sum over the chosen experts HELD HERE of w_e E_e(x) + sigmoid(w_s x) *
E_shared(x) -- what the experts held elsewhere would add is left out, as in
the program (a dense mask over the experts held, no dispatch).

Departures, listed as `assumed` in qwen3_next_80b_a3b.json: the fused
projections are laid out [q | k | v | z], [b | a] and [q | gate] (the
checkpoint interleaves them per head: a permutation of rows); no
multi-token-prediction module; SGD with momentum.  Every layer, and every
block of 64 positions of the recurrence, is recomputed in the backward pass
so that the float32 activations of 8,192 tokens fit beside the weights.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST


def _dims(cfg):
    hk, dk = cfg["linear_num_key_heads"], cfg["linear_key_head_dim"]
    hv, dv = cfg["linear_num_value_heads"], cfg["linear_value_head_dim"]
    return hk, dk, hv, dv


def is_attention_layer(cfg, i):
    return (i + 1) % cfg["full_attention_interval"] == 0


def init_params(key, cfg):
    """(params, aux): normal(0, initializer_range) matrices, norms at
    their identity, A_log = log(uniform(0, 16)), dt_bias = 1; rounded to the
    configuration's `param_dtype`.  aux: the assignments each expert held
    has received, zero."""
    c, v = cfg["hidden_size"], cfg["vocab_size"]
    hk, dk, hv, dv = _dims(cfg)
    heads, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    inter, shared = cfg["moe_intermediate_size"], \
        cfg["shared_expert_intermediate_size"]
    held, routed = cfg["experts_held"]["count"], cfg["experts_held"]["of"]
    keys = iter(jax.random.split(key, 4 + 16 * cfg["num_hidden_layers"]))

    def normal(*shape):
        return cfg["initializer_range"] * jax.random.normal(
            next(keys), shape, jnp.float32)

    p = {"embed.w": normal(v, c), "head.w": normal(v, c),
         "norm.w": jnp.zeros((c,), jnp.float32)}
    aux = {}
    for i in range(cfg["num_hidden_layers"]):
        L = f"l{i}."
        p[L + "norm1.w"] = jnp.zeros((c,), jnp.float32)
        p[L + "norm2.w"] = jnp.zeros((c,), jnp.float32)
        if is_attention_layer(cfg, i):
            p[L + "attn.q.w"] = normal(2 * heads * d, c)
            p[L + "attn.k.w"] = normal(kv * d, c)
            p[L + "attn.v.w"] = normal(kv * d, c)
            p[L + "attn.qnorm.w"] = jnp.zeros((d,), jnp.float32)
            p[L + "attn.knorm.w"] = jnp.zeros((d,), jnp.float32)
            p[L + "attn.out.w"] = normal(c, heads * d)
        else:
            p[L + "gdn.qkvz.w"] = normal(2 * hk * dk + 2 * hv * dv, c)
            p[L + "gdn.ba.w"] = normal(2 * hv, c)
            p[L + "gdn.conv.w"] = normal(2 * hk * dk + hv * dv,
                                         cfg["linear_conv_kernel_dim"])
            p[L + "gdn.a_log"] = jnp.log(jax.random.uniform(
                next(keys), (hv,), jnp.float32, 1e-3, 16.0))
            p[L + "gdn.dt_bias"] = jnp.ones((hv,), jnp.float32)
            p[L + "gdn.norm.w"] = jnp.ones((dv,), jnp.float32)
            p[L + "gdn.out.w"] = normal(c, hv * dv)
        p[L + "moe.router.w"] = normal(routed, c)
        p[L + "moe.gate.w"] = normal(held, inter, c)
        p[L + "moe.up.w"] = normal(held, inter, c)
        p[L + "moe.down.w"] = normal(held, c, inter)
        p[L + "moe.shared_gate.w"] = normal(shared, c)
        p[L + "moe.shared_up.w"] = normal(shared, c)
        p[L + "moe.shared_down.w"] = normal(c, shared)
        p[L + "moe.shared_sigmoid.w"] = normal(1, c)
        aux[L + "moe.load"] = jnp.zeros((held,), jnp.float32)
    if cfg.get("param_dtype") == "bfloat16":
        # not astype(bfloat16).astype(float32): XLA folds that pair away
        p = {n: lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
             for n, a in p.items()}
    return p, aux


def _quant(x, numerics):
    if numerics == "float8":
        q = lax.reduce_precision(x, exponent_bits=4, mantissa_bits=3)
        return x + lax.stop_gradient(q - x)      # straight-through
    if numerics == "bfloat16":
        q = lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        return x + lax.stop_gradient(q - x)
    return x


def _mm(x, w, numerics):
    """x (..., in) against a weight stored (out, in)."""
    return jnp.einsum("...i,oi->...o", _quant(x, numerics),
                      _quant(w, numerics), precision=HI)


def _norm(x, w, eps, zero_centered=True):
    scale = (1.0 + w) if zero_centered else w
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                         + eps) * scale


def _l2(x):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta):
    """The recurrence, token by token.  q, k, v (B, T, H, D*) and g, beta
    (B, T, H), all per VALUE head; returns o (B, T, H, Dv)."""
    b, t, h, dk = q.shape
    blk = math.gcd(t, 64)

    def token(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = S * jnp.exp(g_t)[..., None, None]
        u = b_t[..., None] * (v_t - jnp.sum(S * k_t[..., None], axis=-2))
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, jnp.sum(S * q_t[..., None], axis=-2)

    @jax.checkpoint
    def block(S, xs):
        return lax.scan(token, S, xs)

    xs = tuple(jnp.moveaxis(x, 1, 0).reshape((t // blk, blk) + x.shape[:1]
                                             + x.shape[2:])
               for x in (q, k, v, g, beta))
    _, o = lax.scan(block, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
                    xs)
    return jnp.moveaxis(o.reshape((t,) + o.shape[2:]), 0, 1)


def _gdn_mixer(p, x, cfg, numerics):
    hk, dk, hv, dv = _dims(cfg)
    b, t, _ = x.shape
    kd, vd = hk * dk, hv * dv
    qkvz = _mm(x, p["gdn.qkvz.w"], numerics)
    ba = _mm(x, p["gdn.ba.w"], numerics)
    qkv, z = qkvz[..., :2 * kd + vd], qkvz[..., 2 * kd + vd:]
    kern = cfg["linear_conv_kernel_dim"]
    padded = jnp.pad(qkv, ((0, 0), (kern - 1, 0), (0, 0)))
    qkv = jax.nn.silu(sum(padded[:, j:j + t] * p["gdn.conv.w"][:, j]
                          for j in range(kern)))
    q = _l2(qkv[..., :kd].reshape(b, t, hk, dk)) * dk ** -0.5
    k = _l2(qkv[..., kd:2 * kd].reshape(b, t, hk, dk))
    v = qkv[..., 2 * kd:].reshape(b, t, hv, dv)
    q, k = (jnp.repeat(a, hv // hk, axis=2) for a in (q, k))
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["gdn.a_log"]) * jax.nn.softplus(ba[..., hv:]
                                                   + p["gdn.dt_bias"])
    o = delta_rule(q, k, v, g, beta)
    y = _norm(o, p["gdn.norm.w"], cfg["rms_norm_eps"], zero_centered=False) \
        * jax.nn.silu(z.reshape(b, t, hv, dv))
    return _mm(y.reshape(b, t, vd), p["gdn.out.w"], numerics)


def _rotary(x, rotary, base):
    t, half = x.shape[1], rotary // 2
    inv_freq = 1.0 / base ** (jnp.arange(half, dtype=jnp.float32) * 2.0
                              / rotary)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:rotary]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rotary:]], axis=-1)


def _attn_mixer(p, x, cfg, numerics):
    heads, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    b, t, _ = x.shape
    eps = cfg["rms_norm_eps"]
    rotary = int(d * cfg["partial_rotary_factor"])
    qg = _mm(x, p["attn.q.w"], numerics)
    q, gate = qg[..., :heads * d], qg[..., heads * d:]
    q = _norm(q.reshape(b, t, heads, d), p["attn.qnorm.w"], eps)
    k = _norm(_mm(x, p["attn.k.w"], numerics).reshape(b, t, kv, d),
              p["attn.knorm.w"], eps)
    v = _mm(x, p["attn.v.w"], numerics).reshape(b, t, kv, d)
    q, k = (_rotary(a, rotary, float(cfg["rope_theta"])) for a in (q, k))
    k, v = (jnp.repeat(a, heads // kv, axis=2) for a in (k, v))
    bs = min(512, t)

    @jax.checkpoint
    def block(qb, kb, vb, first):
        s = jnp.einsum("bqhd,bkhd->bhqk", _quant(qb, numerics),
                       _quant(kb, numerics), precision=HI) * d ** -0.5
        seen = (first + jnp.arange(qb.shape[1]))[:, None] >= \
            jnp.arange(kb.shape[1])[None, :]
        pr = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", _quant(pr, numerics),
                          _quant(vb, numerics), precision=HI)

    out = jnp.concatenate(
        [block(q[:, f:f + bs], k[:, :f + bs], v[:, :f + bs], f)
         for f in range(0, t, bs)], axis=1)
    out = out.reshape(b, t, heads * d) * jax.nn.sigmoid(gate)
    return _mm(out, p["attn.out.w"], numerics)


def _swiglu(x, gate, up, down, numerics):
    return _mm(jax.nn.silu(_mm(x, gate, numerics)) * _mm(x, up, numerics),
               down, numerics)


def moe(p, x, cfg, numerics, held=None):
    """(this share's part of the experts' output plus the shared expert,
    assignments per expert held).  `held` = (offset, count) overrides the
    configuration's share (the tests' sum over all shares)."""
    offset, count = held or (cfg["experts_held"]["offset"],
                             cfg["experts_held"]["count"])
    prob = jax.nn.softmax(_mm(x, p["moe.router.w"], numerics), axis=-1)
    w, idx = lax.top_k(prob, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)

    def expert(acc, xs):
        e, gate, up, down = xs
        mine = idx == offset + e
        share = jnp.sum(jnp.where(mine, w, 0.0), axis=-1, keepdims=True)
        return acc + share * _swiglu(x, gate, up, down, numerics), \
            jnp.sum(mine)

    routed, load = lax.scan(
        expert, jnp.zeros_like(x),
        (jnp.arange(count), p["moe.gate.w"][:count], p["moe.up.w"][:count],
         p["moe.down.w"][:count]))
    shared = jax.nn.sigmoid(_mm(x, p["moe.shared_sigmoid.w"], numerics)) * \
        _swiglu(x, p["moe.shared_gate.w"], p["moe.shared_up.w"],
                p["moe.shared_down.w"], numerics)
    return routed + shared, load.astype(jnp.float32)


def layer(p, x, cfg, numerics, attention):
    """One block on (B, T, C); `p` holds the layer's leaves without their
    `l<i>.` prefix.  Returns (y, assignments per expert held)."""
    eps = cfg["rms_norm_eps"]
    mixer = _attn_mixer if attention else _gdn_mixer
    h = x + mixer(p, _norm(x, p["norm1.w"], eps), cfg, numerics)
    out, load = moe(p, _norm(h, p["norm2.w"], eps), cfg, numerics)
    return h + out, load


def forward(params, aux, tokens, cfg, numerics="float32"):
    """(logits (B * T, V) batch-major, aux with this step's loads added)."""
    x = params["embed.w"][tokens]
    new_aux = dict(aux)
    for i in range(cfg["num_hidden_layers"]):
        L = f"l{i}."
        p = {n[len(L):]: a for n, a in params.items() if n.startswith(L)}
        run = jax.checkpoint(functools.partial(
            layer, cfg=cfg, numerics=numerics,
            attention=is_attention_layer(cfg, i)))
        x, load = run(p, x)
        new_aux[L + "moe.load"] = aux[L + "moe.load"] + load
    x = _norm(x, params["norm.w"], cfg["rms_norm_eps"])
    x = x.reshape(-1, x.shape[-1])
    return _mm(x, params["head.w"], numerics), new_aux


def outputs(params, aux, data, cfg, numerics="float32"):
    """The probabilities the program's `SoftmaxOutput` head hands out."""
    logits, _ = forward(params, aux, data.astype(jnp.int32), cfg, numerics)
    return jax.nn.softmax(logits, axis=-1)


def loss_fn(params, aux, data, label, cfg, numerics="float32"):
    """Mean over all tokens of -log(softmax(logits)[label] + eps), as the
    program's cross-entropy metric reports it."""
    logits, aux = forward(params, aux, data.astype(jnp.int32), cfg, numerics)
    prob = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1),
                               label.reshape(-1, 1).astype(jnp.int32), axis=-1)
    return -jnp.mean(jnp.log(prob[:, 0] + cfg["metric_eps"])), aux


def train_step(params, mom, aux, data, label, cfg, numerics="float32",
               rows=None):
    """One step of SGD with momentum.  The program's SoftmaxOutput head sums
    the gradient over all batch * seq_len rows and the optimizer rescales by
    1 / batch, so the step follows `seq_len` times the gradient of the mean
    loss.  `rows` exists for the planted fault of the control test."""
    opt = cfg["optimizer"]
    if rows is not None:
        data, label = data[rows], label[rows]
    (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, aux, data, label, cfg, numerics)
    scale = float(cfg["seq_len"])
    new_p, new_m = {}, {}
    for n, w in params.items():
        g = grads[n] * scale + opt["wd"] * w
        new_m[n] = opt["momentum"] * mom[n] - opt["learning_rate"] * g
        new_p[n] = w + new_m[n]
    return new_p, new_m, aux, loss
