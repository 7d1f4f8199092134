"""Plain reference of the `lfm2_24b_a2b` configuration: the language model of
LiquidAI/LFM2-24B-A2B (config.json of the model card) cut to one chip's
share, written from the equations in straightforward `jax.numpy`, float32 at
`Precision.HIGHEST`.  It imports nothing of the program under test.

Layer i of those held (`layer_types[i]`, `num_dense_layers`):
    h = x + Op_i(RMSNorm(x; w_op)),   y = h + FF_i(RMSNorm(h; w_ff))
    RMSNorm(x; w) = x / sqrt(mean(x^2) + norm_eps) * w       (w starts at 1;
                                                      NOT zero-centred)
After the last layer one more RMS norm, then the logits h W_embed^T: the
head is TIED to the embedding.

Op_i, "conv" (the gated short convolution):
    [B | C | x'] = W_in x          (hidden -> 3 x hidden, no bias)
    u = B * x';   v_t = sum_{j=0..K-1} w[:, j] * u_{t-(K-1)+j}
                  (causal, depthwise, K = conv_L_cache, no bias, zeros before
                  the start of the sequence, no activation)
    Op = W_out (C * v)
Op_i, "full_attention":  q = W_q x, k = W_k x, v = W_v x, no biases; q and k
RMS-normed per head over the head size (w starts at 1), then the rotary
embedding on the WHOLE head (rotate-half pairing, theta = rope_theta); causal
softmax attention at scale head_size^-1/2, each key-value head serving
heads / kv_heads query heads; Op = W_o attn.  No output gate.

FF_i, i < num_dense_layers:  W_2 (SiLU(W_1 x) * W_3 x), width
intermediate_size.
FF_i otherwise:  s = sigmoid(W_r x) over ALL the experts routed over; the
chosen set T = the num_experts_per_tok largest of s + b (b the selection
bias: it moves the choice alone, and takes no gradient); w_e = s_e /
(sum_{e' in T} s_e' + 1e-6), times routed_scaling_factor;
    output = sum over e in T HELD HERE of  w_e E_e(x),
    E_e(x) = W_2e (SiLU(W_1e x) * W_3e x),  width moe_intermediate_size
-- what the experts held elsewhere would add is left out, as in the program
(a dense mask over the experts held, no dispatch).  No shared expert.

Departures, listed as `assumed` in lfm2_24b_a2b.json: SGD with momentum; the
selection bias is a seeded buffer that no step moves (the balancing rule
that moves it between steps is a training recipe the row does not give);
the head is tied.  Every layer is recomputed in the backward pass, a
sequence at a time, and the loss is taken a sequence at a time, so that the
float32 activations of 2 x 8,192 tokens fit beside 20 bytes a parameter.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
ROUTER_EPS = 1e-6


def is_dense_layer(cfg, i):
    return i < cfg["num_dense_layers"]


def init_params(key, cfg):
    """(params, aux): normal(0, initializer_range) matrices, norm weights 1,
    the selection bias normal(0, expert_bias_scale); rounded to the
    configuration's `param_dtype`.  aux: the assignments each expert held
    has received, zero."""
    c, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = c // heads
    inter, wide = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    held, routed = cfg["experts_held"]["count"], cfg["experts_held"]["of"]
    keys = iter(jax.random.split(key, 2 + 12 * cfg["num_hidden_layers"]))

    def normal(*shape, scale=cfg["initializer_range"]):
        return scale * jax.random.normal(next(keys), shape, jnp.float32)

    p = {"embed.w": normal(v, c), "norm.w": jnp.ones((c,), jnp.float32)}
    aux = {}
    for i, kind in enumerate(cfg["layer_types"]):
        L = f"l{i}."
        p[L + "norm1.w"] = jnp.ones((c,), jnp.float32)
        p[L + "norm2.w"] = jnp.ones((c,), jnp.float32)
        if kind == "conv":
            p[L + "conv.in.w"] = normal(3 * c, c)
            p[L + "conv.conv.w"] = normal(c, cfg["conv_L_cache"])
            p[L + "conv.out.w"] = normal(c, c)
        else:
            p[L + "attn.q.w"] = normal(heads * d, c)
            p[L + "attn.k.w"] = normal(kv * d, c)
            p[L + "attn.v.w"] = normal(kv * d, c)
            p[L + "attn.qnorm.w"] = jnp.ones((d,), jnp.float32)
            p[L + "attn.knorm.w"] = jnp.ones((d,), jnp.float32)
            p[L + "attn.out.w"] = normal(c, heads * d)
        if is_dense_layer(cfg, i):
            p[L + "ffn.w1.w"] = normal(wide, c)
            p[L + "ffn.w3.w"] = normal(wide, c)
            p[L + "ffn.w2.w"] = normal(c, wide)
            continue
        p[L + "moe.router.w"] = normal(routed, c)
        p[L + "moe.gate.w"] = normal(held, inter, c)
        p[L + "moe.up.w"] = normal(held, inter, c)
        p[L + "moe.down.w"] = normal(held, c, inter)
        if cfg["use_expert_bias"]:
            p[L + "moe.bias"] = normal(routed, scale=cfg["expert_bias_scale"])
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


def _norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * w


def short_conv(bcx, w):
    """bcx (B, T, 3C) = [B | C | x'], w (C, K): C * conv_K(B * x')."""
    c, kern = w.shape
    t = bcx.shape[1]
    b, gate, x = bcx[..., :c], bcx[..., c:2 * c], bcx[..., 2 * c:]
    padded = jnp.pad(b * x, ((0, 0), (kern - 1, 0), (0, 0)))
    return gate * sum(padded[:, j:j + t] * w[:, j] for j in range(kern))


def _conv_mixer(p, x, cfg, numerics):
    return _mm(short_conv(_mm(x, p["conv.in.w"], numerics), p["conv.conv.w"]),
               p["conv.out.w"], numerics)


def _rotary(x, base):
    t, d = x.shape[1], x.shape[-1]
    half = d // 2
    inv_freq = 1.0 / base ** (jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _attn_mixer(p, x, cfg, numerics):
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    b, t, c = x.shape
    d = c // heads
    eps, base = cfg["norm_eps"], float(cfg["rope_parameters"]["rope_theta"])
    q = _norm(_mm(x, p["attn.q.w"], numerics).reshape(b, t, heads, d),
              p["attn.qnorm.w"], eps)
    k = _norm(_mm(x, p["attn.k.w"], numerics).reshape(b, t, kv, d),
              p["attn.knorm.w"], eps)
    v = _mm(x, p["attn.v.w"], numerics).reshape(b, t, kv, d)
    q, k = _rotary(q, base), _rotary(k, base)
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
    return _mm(out.reshape(b, t, heads * d), p["attn.out.w"], numerics)


def _swiglu(x, gate, up, down, numerics):
    return _mm(jax.nn.silu(_mm(x, gate, numerics)) * _mm(x, up, numerics),
               down, numerics)


def route(p, x, cfg, numerics):
    """(weights (..., k), experts (..., k)) of every token: sigmoid scores,
    the choice by score + bias, the weights by the score alone."""
    s = jax.nn.sigmoid(_mm(x, p["moe.router.w"], numerics))
    choose = s + p["moe.bias"] if cfg["use_expert_bias"] else s
    idx = lax.top_k(choose, cfg["num_experts_per_tok"])[1]
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTER_EPS)
    return w * cfg["routed_scaling_factor"], idx


def moe(p, x, cfg, numerics, held=None):
    """(this share's part of the experts' output, assignments per expert
    held).  `held` = (offset, count) overrides the configuration's share
    (the tests' sum over all shares)."""
    offset, count = held or (cfg["experts_held"]["offset"],
                             cfg["experts_held"]["count"])
    w, idx = route(p, x, cfg, numerics)

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
    return routed, load.astype(jnp.float32)


def layer(p, x, cfg, numerics, kind, dense):
    """One layer on (B, T, C); `p` holds the layer's leaves without their
    `l<i>.` prefix.  Returns (y, assignments per expert held, or none)."""
    eps = cfg["norm_eps"]
    mixer = _conv_mixer if kind == "conv" else _attn_mixer
    h = x + mixer(p, _norm(x, p["norm1.w"], eps), cfg, numerics)
    z = _norm(h, p["norm2.w"], eps)
    if dense:
        return h + _swiglu(z, p["ffn.w1.w"], p["ffn.w3.w"], p["ffn.w2.w"],
                           numerics), jnp.zeros((0,), jnp.float32)
    out, load = moe(p, z, cfg, numerics)
    return h + out, load


def _trunk(params, aux, tokens, cfg, numerics):
    """(the final norm's output (B, T, C), aux with this step's loads
    added); every layer a sequence at a time, made again in the backward
    pass."""
    x = params["embed.w"][tokens]
    new_aux = dict(aux)
    for i, kind in enumerate(cfg["layer_types"]):
        L = f"l{i}."
        p = {n[len(L):]: a for n, a in params.items() if n.startswith(L)}
        run = jax.checkpoint(functools.partial(
            layer, cfg=cfg, numerics=numerics, kind=kind,
            dense=is_dense_layer(cfg, i)))
        x, load = lax.map(lambda row: run(p, row[None]), x)
        x = x[:, 0]
        if not is_dense_layer(cfg, i):
            new_aux[L + "moe.load"] = aux[L + "moe.load"] + load.sum(axis=0)
    return _norm(x, params["norm.w"], cfg["norm_eps"]), new_aux


def forward(params, aux, tokens, cfg, numerics="float32"):
    """(logits (B * T, V) batch-major, aux with this step's loads added)."""
    x, aux = _trunk(params, aux, tokens, cfg, numerics)
    return _mm(x.reshape(-1, x.shape[-1]), params["embed.w"], numerics), aux


def outputs(params, aux, data, cfg, numerics="float32"):
    """The probabilities the program's `SoftmaxOutput` head hands out."""
    logits, _ = forward(params, aux, data.astype(jnp.int32), cfg, numerics)
    return jax.nn.softmax(logits, axis=-1)


def loss_fn(params, aux, data, label, cfg, numerics="float32"):
    """Mean over all tokens of -log(softmax(logits)[label] + eps), as the
    program's cross-entropy metric reports it."""
    x, aux = _trunk(params, aux, data.astype(jnp.int32), cfg, numerics)

    @jax.checkpoint
    def sequence(xs):
        x, label = xs
        prob = jnp.take_along_axis(
            jax.nn.softmax(_mm(x, params["embed.w"], numerics), axis=-1),
            label.reshape(-1, 1).astype(jnp.int32), axis=-1)
        return -jnp.sum(jnp.log(prob[:, 0] + cfg["metric_eps"]))
    return jnp.sum(lax.map(sequence, (x, label))) / label.size, aux


def train_step(params, mom, aux, data, label, cfg, numerics="float32",
               rows=None):
    """One step of SGD with momentum.  The program's SoftmaxOutput head sums
    the gradient over all batch * seq_len rows and the optimizer rescales by
    1 / batch, so the step follows `seq_len` times the gradient of the mean
    loss.  The selection bias moves no weight, so its gradient is zero and
    the step leaves it where it was.  `rows` exists for the planted fault of
    the control test."""
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
