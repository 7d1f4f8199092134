"""Plain reference of the `kanana2_30b_a3b` configuration: the language model
of kakaocorp/kanana-2-30b-a3b-instruct-2601 (config.json of the model card,
`model_type: deepseek_v3`) cut to one chip's share, written from the
equations in straightforward `jax.numpy`, float32 at `Precision.HIGHEST`,
in the family's own layout.  It imports nothing of the program under test.

Layer i of those held:
    h = x + MLA(RMSNorm(x; w_1)),   y = h + FF_i(RMSNorm(h; w_2))
    RMSNorm(x; w) = x / sqrt(mean(x^2) + rms_norm_eps) * w   (w starts at 1)
After the last layer one more RMS norm, then the untied head.

MLA (DeepSeek-V3 without q_lora_rank; H heads, nope = qk_nope_head_dim,
pe = qk_rope_head_dim, dv = v_head_dim, r = kv_lora_rank):
    q = W_q x, H heads of [q_nope (nope) | q_pe (pe)]
    [c | k_pe] = W_kva x                   (r + pe; ONE k_pe for all heads)
    [k_nope | v] = W_kvb RMSNorm(c; w_kv), H heads of [nope | dv]
    q_pe and k_pe turned by the rotary embedding with INTERLEAVED pairs
    (`rope_interleave`: entries 2i and 2i + 1 turn together, by position x
    rope_theta^(-2i / pe); i and i + pe/2 where it is false);
    q = [q_nope | q_pe], k = [k_nope | k_pe]
    o = causal softmax(q k^T / sqrt(nope + pe)) v;   MLA = W_o o
No biases; `rope_scaling` is null, so the scale is sqrt(nope + pe)^-1.

FF_i, i < first_k_dense_replace:  W_2 (SiLU(W_1 x) * W_3 x), width
intermediate_size.
FF_i otherwise:  s = sigmoid(W_r x) over ALL the experts routed over; the
chosen set T = the num_experts_per_tok largest of s + b (b the selection
bias, `e_score_correction_bias`: it moves the choice alone and takes no
gradient; n_group = topk_group = 1, so the group-limited choice of
`noaux_tc` chooses among all); w_e = s_e / (sum_{e' in T} s_e' + 1e-20)
times routed_scaling_factor;
    output = sum over e in T HELD HERE of w_e E_e(x)
             + sum over the n_shared_experts shared experts of S_j(x),
    E_e, S_j = W_2 (SiLU(W_1 x) * W_3 x), width moe_intermediate_size
-- what the experts held elsewhere would add is left out, as in the program
(a dense mask over the experts held, no dispatch).  The shared experts are
written as separate experts; the family's module holds them as one SwiGLU
of their summed width, which is the same function.

Departures, listed as `assumed` in kanana2_30b_a3b.json: SGD with momentum;
the selection bias is a seeded buffer that no step moves; the weights'
ranges.  Every layer is recomputed in the backward pass, a sequence at a
time, attention a block of 512 queries at a time, and the loss a sequence
at a time, so that the float32 activations of 2 x 8,192 tokens fit beside
20 bytes a parameter.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
ROUTER_EPS = 1e-20


def is_dense_layer(cfg, i):
    return i < cfg["first_k_dense_replace"]


def _normal(key, shape, scale):
    return scale * jax.random.normal(key, shape, jnp.float32)


def placed_leaves(cfg):
    """{leaf: array} of what decides which experts a token goes to -- the
    embedding, and every routed layer's router and selection bias -- drawn
    from `routing_seed` (`assumed.routing_seed`), or None where the
    configuration has none (then `init_params` draws them from its key)."""
    if cfg.get("routing_seed") is None:
        return None
    return _placed(iter(jax.random.split(
        jax.random.PRNGKey(cfg["routing_seed"]),
        1 + 2 * cfg["num_hidden_layers"])), cfg)


def _placed(keys, cfg):
    c, v = cfg["hidden_size"], cfg["vocab_size"]
    routed = cfg["experts_held"]["of"]
    p = {"embed.w": _normal(next(keys), (v, c), cfg.get(
        "embed_initializer_range", cfg["initializer_range"]))}
    for i in range(cfg["num_hidden_layers"]):
        if is_dense_layer(cfg, i):
            continue
        L = f"l{i}."
        p[L + "moe.router.w"] = _normal(next(keys), (routed, c),
                                        cfg["initializer_range"])
        p[L + "moe.bias"] = _normal(next(keys), (routed,),
                                    cfg["expert_bias_scale"])
    return p


def init_params(key, cfg):
    """(params, aux): normal(0, initializer_range) matrices (the embedding
    and the projections back into the residual stream at ranges of their
    own, where the configuration gives them), the selection bias normal(0,
    expert_bias_scale), norm weights 1; rounded to the configuration's
    `param_dtype`.  aux: the assignments each expert held has received,
    zero."""
    c, v = cfg["hidden_size"], cfg["vocab_size"]
    h = cfg["num_attention_heads"]
    nope, pe, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    rank = cfg["kv_lora_rank"]
    inter, wide = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    held = cfg["experts_held"]["count"]
    keys = iter(jax.random.split(key, 3 + 20 * cfg["num_hidden_layers"]))
    back = cfg.get("residual_initializer_range", cfg["initializer_range"])

    def normal(*shape, scale=cfg["initializer_range"]):
        return _normal(next(keys), shape, scale)

    p = placed_leaves(cfg) or _placed(keys, cfg)
    p.update({"head.w": normal(v, c), "norm.w": jnp.ones((c,), jnp.float32)})
    aux = {}
    for i in range(cfg["num_hidden_layers"]):
        L = f"l{i}."
        p[L + "norm1.w"] = jnp.ones((c,), jnp.float32)
        p[L + "norm2.w"] = jnp.ones((c,), jnp.float32)
        p[L + "attn.q.w"] = normal(h * (nope + pe), c)
        p[L + "attn.kva.w"] = normal(rank + pe, c)
        p[L + "attn.kvnorm.w"] = jnp.ones((rank,), jnp.float32)
        p[L + "attn.kvb.w"] = normal(h * (nope + dv), rank)
        p[L + "attn.o.w"] = normal(c, h * dv, scale=back)
        if is_dense_layer(cfg, i):
            p[L + "mlp.gate.w"] = normal(wide, c)
            p[L + "mlp.up.w"] = normal(wide, c)
            p[L + "mlp.down.w"] = normal(c, wide, scale=back)
            continue
        p[L + "moe.gate.w"] = normal(held, inter, c)
        p[L + "moe.up.w"] = normal(held, inter, c)
        p[L + "moe.down.w"] = normal(held, c, inter, scale=back)
        for j in range(cfg["n_shared_experts"]):
            S = f"{L}moe.shared{j}."
            p[S + "gate.w"] = normal(inter, c)
            p[S + "up.w"] = normal(inter, c)
            p[S + "down.w"] = normal(c, inter, scale=back)
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


def rotary_interleaved(x, base):
    """x (B, T, H, D): entries 2i and 2i + 1 turn together by position x
    base^(-2i / D)."""
    t, d = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / base ** (jnp.arange(d // 2, dtype=jnp.float32) * 2.0
                              / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def rotary_half(x, base):
    """x (B, T, H, D): entries i and i + D/2 turn together by position x
    base^(-2i / D) (the family's pairing where `rope_interleave` is
    false)."""
    t, d = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / base ** (jnp.arange(d // 2, dtype=jnp.float32) * 2.0
                              / d)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def mla(p, x, cfg, numerics):
    """The latent attention mixer on (B, T, C)."""
    b, t, _ = x.shape
    h = cfg["num_attention_heads"]
    nope, pe, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], \
        cfg["v_head_dim"]
    rank, base = cfg["kv_lora_rank"], float(cfg["rope_theta"])
    q = _mm(x, p["attn.q.w"], numerics).reshape(b, t, h, nope + pe)
    kva = _mm(x, p["attn.kva.w"], numerics)
    kv = _mm(_norm(kva[..., :rank], p["attn.kvnorm.w"], cfg["rms_norm_eps"]),
             p["attn.kvb.w"], numerics).reshape(b, t, h, nope + dv)
    rotary = rotary_interleaved if cfg["rope_interleave"] else rotary_half
    k_pe = rotary(kva[..., None, rank:], base)
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], base)],
                        axis=-1)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (b, t, h, pe))], axis=-1)
    v = kv[..., nope:]
    bs = min(512, t)

    @jax.checkpoint
    def block(qb, kb, vb, first):
        s = jnp.einsum("bqhd,bkhd->bhqk", _quant(qb, numerics),
                       _quant(kb, numerics), precision=HI) * \
            (nope + pe) ** -0.5
        seen = (first + jnp.arange(qb.shape[1]))[:, None] >= \
            jnp.arange(kb.shape[1])[None, :]
        pr = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", _quant(pr, numerics),
                          _quant(vb, numerics), precision=HI)

    out = jnp.concatenate(
        [block(q[:, f:f + bs], k[:, :f + bs], v[:, :f + bs], f)
         for f in range(0, t, bs)], axis=1)
    return _mm(out.reshape(b, t, h * dv), p["attn.o.w"], numerics)


def _swiglu(x, gate, up, down, numerics):
    return _mm(jax.nn.silu(_mm(x, gate, numerics)) * _mm(x, up, numerics),
               down, numerics)


def route(p, x, cfg, numerics):
    """(weights (..., k), experts (..., k)) of every token: sigmoid scores,
    the choice by score + bias, the weights by the score alone."""
    s = jax.nn.sigmoid(_mm(x, p["moe.router.w"], numerics))
    idx = lax.top_k(s + p["moe.bias"], cfg["num_experts_per_tok"])[1]
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTER_EPS)
    return w * cfg["routed_scaling_factor"], idx


def moe(p, x, cfg, numerics, held=None):
    """(this share's part of the routed experts' output, assignments per
    expert held).  `held` = (offset, count) overrides the configuration's
    share (the tests' sum over all shares)."""
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


def shared_experts(p, x, cfg, numerics):
    """The shared experts' output, one expert after the other."""
    return sum(_swiglu(x, p[f"moe.shared{j}.gate.w"],
                       p[f"moe.shared{j}.up.w"], p[f"moe.shared{j}.down.w"],
                       numerics) for j in range(cfg["n_shared_experts"]))


def layer(p, x, cfg, numerics, dense):
    """One layer on (B, T, C); `p` holds the layer's leaves without their
    `l<i>.` prefix.  Returns (y, assignments per expert held, or none)."""
    eps = cfg["rms_norm_eps"]
    h = x + mla(p, _norm(x, p["norm1.w"], eps), cfg, numerics)
    z = _norm(h, p["norm2.w"], eps)
    if dense:
        return h + _swiglu(z, p["mlp.gate.w"], p["mlp.up.w"],
                           p["mlp.down.w"], numerics), \
            jnp.zeros((0,), jnp.float32)
    out, load = moe(p, z, cfg, numerics)
    return h + out + shared_experts(p, z, cfg, numerics), load


def _trunk(params, aux, tokens, cfg, numerics):
    """(the final norm's output (B, T, C), aux with this step's loads
    added); every layer a sequence at a time, made again in the backward
    pass."""
    x = params["embed.w"][tokens]
    new_aux = dict(aux)
    for i in range(cfg["num_hidden_layers"]):
        L = f"l{i}."
        p = {n[len(L):]: a for n, a in params.items() if n.startswith(L)}
        run = jax.checkpoint(functools.partial(
            layer, cfg=cfg, numerics=numerics, dense=is_dense_layer(cfg, i)))
        x, load = lax.map(lambda row: run(p, row[None]), x)
        x = x[:, 0]
        if not is_dense_layer(cfg, i):
            new_aux[L + "moe.load"] = aux[L + "moe.load"] + load.sum(axis=0)
    return _norm(x, params["norm.w"], cfg["rms_norm_eps"]), new_aux


def forward(params, aux, tokens, cfg, numerics="float32"):
    """(logits (B * T, V) batch-major, aux with this step's loads added)."""
    x, aux = _trunk(params, aux, tokens, cfg, numerics)
    return _mm(x.reshape(-1, x.shape[-1]), params["head.w"], numerics), aux


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
            jax.nn.softmax(_mm(x, params["head.w"], numerics), axis=-1),
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
