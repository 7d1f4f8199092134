"""Plain reference of the `sdar_30b_a3b_chat` configuration: the language
model of JetLM/SDAR-30B-A3B-Chat (config.json of the model card) cut to one
chip's share, trained under the block-diffusion objective of BD3-LM
(arXiv:2503.09573), which SDAR (arXiv:2510.06303) adopts to turn an
autoregressive model into a block-diffusion one.  Written from the
equations in straightforward `jax.numpy`, float32 at `Precision.HIGHEST`.
It imports nothing of the program under test.

The objective.  A clean sequence x of L tokens is cut into blocks of B
(b(i) = i // B).  Block b draws a noise level t_b uniform on [low, high];
token i is replaced by the mask token with probability t_b(i) (m_i = 1
there).  One forward pass runs the 2L rows [noisy copy | clean copy], both
copies at positions 0..L-1, under the mask (rows and keys indexed inside
their copy):
    noisy row i  sees  noisy key j  iff b(j) == b(i)     (block diagonal)
    noisy row i  sees  clean key j  iff b(j) <  b(i)     (offset block causal)
    clean row i  sees  clean key j  iff b(j) <= b(i)     (block causal)
    clean row i  sees  no noisy key
and the loss is read on the noisy copy alone, in place (row i predicts x_i):
    sum_i m_i / t_b(i) * -log softmax(logits_i)[x_i]  /  (clean tokens)

The layers, every one the same (no dense layer; period 1):
    h = x + Attn(RMSNorm(x; w_1)),   y = h + MoE(RMSNorm(h; w_2))
    RMSNorm(x; w) = x / sqrt(mean(x^2) + rms_norm_eps) * w      (w starts at 1)
Attn: q = W_q x, k = W_k x, v = W_v x, no biases; q and k RMS-normed per
head over the head size (w starts at 1), then the rotary embedding on the
WHOLE head (rotate-half pairing, theta = rope_theta) at the row's position
INSIDE ITS COPY; softmax attention under the mask above at scale
head_dim^-1/2, each key-value head serving heads / kv_heads query heads;
Attn = W_o attn.
MoE: s = softmax(W_r x) over ALL the experts routed over; the chosen set T =
the num_experts_per_tok largest; w_e = s_e / sum_{e' in T} s_e'
(norm_topk_prob);
    output = sum over e in T HELD HERE of  w_e E_e(x),
    E_e(x) = W_2e (SiLU(W_1e x) * W_3e x),  width moe_intermediate_size
-- what the experts held elsewhere would add is left out, as in the program
(a dense mask over the experts held, no dispatch).  No shared expert.
After the last layer the noisy copy's L rows take one more RMS norm and the
untied head W_head.  The embedding has vocab_size + 1 rows: the last is the
mask token's.

Departures, listed as `assumed` in sdar_30b_a3b_chat.json: SGD with
momentum; the block length, the interval of t and the mask token's row are
not in the published row; nor is any initialiser: normal(0, 0.02), but the
embedding at `embed_initializer_range` and the projections back into the
residual stream at `residual_initializer_range`.  Every layer is recomputed in the backward pass, a
sequence at a time, attention a block of queries at a time, and the loss is
taken a sequence at a time, so that the float32 activations of 2 x 8,192
rows fit beside 20 bytes a parameter.

What the harness hands over (it is not edited): `data` and `label`, both
(batch, L), the second the same stream one token later; `outputs` gets
`data` alone.  So the clean sequence x is `data` (the program's graph reads
its targets from `data` too), and `label` is what the reported loss -- the
program's `ce` metric of output 0 against `softmax_label` -- is read
against; it reaches no gradient on either side.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST


def init_params(key, cfg):
    """(params, aux): normal(0, initializer_range) matrices (the embedding and
    the projections back into the residual stream at ranges of their own,
    where the configuration gives them), norm weights 1; rounded to the
    configuration's `param_dtype`.  aux: the assignments
    each expert held has received and the noise's counts (rows, rows
    masked, the weights' sum), zero."""
    c, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    inter = cfg["moe_intermediate_size"]
    held, routed = cfg["experts_held"]["count"], cfg["experts_held"]["of"]
    keys = iter(jax.random.split(key, 2 + 8 * cfg["num_hidden_layers"]))
    # what decides which experts a token goes to, the embedding and the
    # routers, may be a draw of its own that no run's seed moves
    # (`assumed.routing_seed`): the checkpoint's part in a chip's load
    placed = keys if cfg.get("routing_seed") is None else iter(
        jax.random.split(jax.random.PRNGKey(cfg["routing_seed"]),
                         1 + cfg["num_hidden_layers"]))

    def normal(*shape, scale=cfg["initializer_range"], keys=keys):
        return scale * jax.random.normal(next(keys), shape, jnp.float32)

    # the projections back into the residual stream, and the embedding,
    # may have ranges of their own (`assumed`)
    back = cfg.get("residual_initializer_range", cfg["initializer_range"])
    # one row more than the vocabulary: the mask token's
    p = {"embed.w": normal(v + 1, c, keys=placed, scale=cfg.get(
             "embed_initializer_range", cfg["initializer_range"])),
         "head.w": normal(v, c),
         "norm.w": jnp.ones((c,), jnp.float32)}
    aux = {"noise.stats": jnp.zeros((3,), jnp.float32)}
    for i in range(cfg["num_hidden_layers"]):
        L = f"l{i}."
        p[L + "norm1.w"] = jnp.ones((c,), jnp.float32)
        p[L + "norm2.w"] = jnp.ones((c,), jnp.float32)
        p[L + "attn.q.w"] = normal(heads * d, c)
        p[L + "attn.k.w"] = normal(kv * d, c)
        p[L + "attn.v.w"] = normal(kv * d, c)
        p[L + "attn.qnorm.w"] = jnp.ones((d,), jnp.float32)
        p[L + "attn.knorm.w"] = jnp.ones((d,), jnp.float32)
        p[L + "attn.out.w"] = normal(c, heads * d, scale=back)
        p[L + "moe.router.w"] = normal(routed, c, keys=placed)
        p[L + "moe.gate.w"] = normal(held, inter, c)
        p[L + "moe.up.w"] = normal(held, inter, c)
        p[L + "moe.down.w"] = normal(held, c, inter, scale=back)
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


# -- the objective's three pieces: the draw, the positions, the mask --------------

def noise(x, cfg):
    """(noisy ids, m, m / t) of clean ids x (batch, L) int32.  The draws
    are `jax.random`'s under fold_in(fold_in(PRNGKey(noise_seed), checksum
    of the row's ids), 0 for the blocks' levels, 1 for the tokens),
    checksum = sum_i x_i (2 i + 1) mod 2^32: a function of the tokens and
    the seed alone (`assumed`: the published row gives no schedule; t
    uniform on `noise_interval`, linear schedule, weight 1 / t, are
    BD3-LM's).  Departure: t is drawn from the THOUSANDTHS of the interval
    (0.001, 0.002, ..., 1), not from the reals in it, and t and 1 / t are
    looked up in tables made by numpy: with a multiply-add on the device,
    t's last bit would depend on whether a compiler fuses it, and the
    program's mask with it."""
    length, block = x.shape[1], cfg["block_length"]
    low, high = cfg["noise_interval"]
    blocks = -(-length // block)
    grid = np.arange(round(1000 * low), round(1000 * high) + 1)
    levels = jnp.asarray(grid.astype(np.float32) / np.float32(1000))
    inverse = jnp.asarray(np.float32(1) /
                          (grid.astype(np.float32) / np.float32(1000)))
    odd = 2 * jnp.arange(length, dtype=jnp.uint32) + jnp.uint32(1)
    check = jnp.sum(x.astype(jnp.uint32) * odd, axis=1, dtype=jnp.uint32)
    base = jax.random.PRNGKey(cfg["noise_seed"])

    def row(c):
        k = jax.random.fold_in(base, c)
        level = jax.random.randint(jax.random.fold_in(k, 0), (blocks,), 0,
                                   len(grid))
        level = jnp.repeat(level, block)[:length]
        u = jax.random.uniform(jax.random.fold_in(k, 1), (length,),
                               jnp.float32)
        return u < levels[level], level
    masked, level = jax.vmap(row)(check)
    m = masked.astype(jnp.float32)
    # the mask token is the embedding's last row (`assumed`)
    return jnp.where(masked, cfg["vocab_size"], x), m, m * inverse[level]


def positions(length):
    """The 2L rows' positions: both copies at 0..L-1."""
    return jnp.concatenate([jnp.arange(length), jnp.arange(length)])


def visible(rows, length, block):
    """(len(rows), 2L) booleans: which of the 2L keys [noisy | clean] the
    rows `rows` (numbers among the 2L) see -- the four lines of the
    module's text."""
    keys = jnp.arange(2 * length)
    q_noisy, k_noisy = rows[:, None] < length, keys[None, :] < length
    bq = (rows % length)[:, None] // block
    bk = (keys % length)[None, :] // block
    return jnp.where(
        q_noisy,
        jnp.where(k_noisy, bk == bq, bk < bq),
        jnp.where(k_noisy, False, bk <= bq))


# -- the layer ------------------------------------------------------------------

def _rotary(x, base, pos):
    d = x.shape[-1]
    half = d // 2
    inv_freq = 1.0 / base ** (jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def _attn(p, x, cfg, numerics):
    """x (B, 2L, C): the rows [noisy | clean] of each sequence."""
    heads, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    b, t, c = x.shape
    length = t // 2
    eps, base = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    q = _norm(_mm(x, p["attn.q.w"], numerics).reshape(b, t, heads, d),
              p["attn.qnorm.w"], eps)
    k = _norm(_mm(x, p["attn.k.w"], numerics).reshape(b, t, kv, d),
              p["attn.knorm.w"], eps)
    v = _mm(x, p["attn.v.w"], numerics).reshape(b, t, kv, d)
    pos = positions(length)
    q, k = _rotary(q, base, pos), _rotary(k, base, pos)
    k, v = (jnp.repeat(a, heads // kv, axis=2) for a in (k, v))
    # a block of queries at a time against all 2L keys under the dense
    # mask, its scores made again in the backward pass: 2 x 8,192 rows fit
    bs = min(256, t)
    while t % bs:
        bs -= 1

    @jax.checkpoint
    def block(xs):
        qb, first = xs
        s = jnp.einsum("bqhd,bkhd->bhqk", _quant(qb, numerics),
                       _quant(k, numerics), precision=HI) * d ** -0.5
        see = visible(first + jnp.arange(bs), length, cfg["block_length"])
        pr = jax.nn.softmax(jnp.where(see, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", _quant(pr, numerics),
                          _quant(v, numerics), precision=HI)

    out = lax.map(block, (q.reshape(b, t // bs, bs, heads, d).swapaxes(0, 1),
                          jnp.arange(0, t, bs)))
    return _mm(out.swapaxes(0, 1).reshape(b, t, heads * d), p["attn.out.w"],
               numerics)


def _swiglu(x, gate, up, down, numerics):
    return _mm(jax.nn.silu(_mm(x, gate, numerics)) * _mm(x, up, numerics),
               down, numerics)


def route(p, x, cfg, numerics):
    """(weights (..., k), experts (..., k)) of every token: softmax over all
    the experts routed over, the largest k, renormalised over the chosen."""
    s = jax.nn.softmax(_mm(x, p["moe.router.w"], numerics), axis=-1)
    w, idx = lax.top_k(s, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return w, idx


def moe(p, x, cfg, numerics, held=None):
    """(this share's part of the experts' output, assignments per expert
    held).  `held` = (offset, count) overrides the configuration's share
    (the tests' sum over all shares)."""
    offset, count = held or (cfg["experts_held"]["offset"],
                             cfg["experts_held"]["count"])
    w, idx = route(p, x, cfg, numerics)

    @jax.checkpoint
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


def layer(p, x, cfg, numerics):
    """One layer on (B, 2L, C); `p` holds the layer's leaves without their
    `l<i>.` prefix.  Returns (y, assignments per expert held)."""
    eps = cfg["rms_norm_eps"]
    h = x + _attn(p, _norm(x, p["norm1.w"], eps), cfg, numerics)
    out, load = moe(p, _norm(h, p["norm2.w"], eps), cfg, numerics)
    return h + out, load


def _trunk(params, aux, x, cfg, numerics):
    """(the final norm's output on the noisy copy (B, L, C), the weight
    m / t (B, L), aux with this step's counts added) of clean ids x
    (B, L); every layer a sequence at a time, made again in the backward
    pass."""
    noisy, m, weight = noise(x, cfg)
    h = params["embed.w"][jnp.concatenate([noisy, x], axis=1)]
    new_aux = dict(aux)
    new_aux["noise.stats"] = aux["noise.stats"] + jnp.stack(
        [jnp.float32(x.size), jnp.sum(m), jnp.sum(weight)])
    for i in range(cfg["num_hidden_layers"]):
        L = f"l{i}."
        p = {n[len(L):]: a for n, a in params.items() if n.startswith(L)}
        run = jax.checkpoint(functools.partial(
            layer, cfg=cfg, numerics=numerics))
        h, load = lax.map(lambda row: run(p, row[None]), h)
        h = h[:, 0]
        new_aux[L + "moe.load"] = aux[L + "moe.load"] + load.sum(axis=0)
    # the loss reads the noisy copy alone
    h = h[:, :x.shape[1]]
    return _norm(h, params["norm.w"], cfg["rms_norm_eps"]), weight, new_aux


def forward(params, aux, tokens, cfg, numerics="float32"):
    """(logits of the noisy copy (B * L, V) batch-major, aux with this
    step's counts added)."""
    h, _, aux = _trunk(params, aux, tokens, cfg, numerics)
    return _mm(h.reshape(-1, h.shape[-1]), params["head.w"], numerics), aux


def outputs(params, aux, data, cfg, numerics="float32"):
    """The probabilities the program's `SoftmaxOutput` head hands out."""
    logits, _ = forward(params, aux, data.astype(jnp.int32), cfg, numerics)
    return jax.nn.softmax(logits, axis=-1)


def loss_fn(params, aux, data, label, cfg, numerics="float32"):
    """(the objective, (aux, the reported loss)).  The objective: sum over
    the noisy copy's rows of m_i / t * -log softmax(logits_i)[x_i], over
    the number of clean tokens, x = `data`.  The reported loss: the mean
    over all rows of -log(softmax(logits_i)[label_i] + eps), as the
    program's cross-entropy metric reads output 0 against `softmax_label`;
    no gradient is taken of it."""
    x = data.astype(jnp.int32)
    h, weight, aux = _trunk(params, aux, x, cfg, numerics)

    @jax.checkpoint
    def sequence(xs):
        h, x, w, label = xs
        logp = jax.nn.log_softmax(_mm(h, params["head.w"], numerics), -1)
        at = lambda ids: jnp.take_along_axis(
            logp, ids.reshape(-1, 1).astype(jnp.int32), axis=-1)[:, 0]
        read = -jnp.sum(jnp.log(jnp.exp(at(label)) + cfg["metric_eps"]))
        return -jnp.sum(w * at(x)), lax.stop_gradient(read)
    objective, read = lax.map(sequence, (h, x, weight, label))
    return jnp.sum(objective) / x.size, (aux, jnp.sum(read) / x.size)


def train_step(params, mom, aux, data, label, cfg, numerics="float32",
               rows=None):
    """One step of SGD with momentum.  The program's weighted SoftmaxOutput
    head sums the gradient over all batch * L rows and the optimizer
    rescales by 1 / batch, so the step follows L times the gradient of the
    objective.  Returns the loss as the program reports it.  `rows` exists
    for the planted fault of the control test."""
    opt = cfg["optimizer"]
    if rows is not None:
        data, label = data[rows], label[rows]
    (_, (aux, loss)), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        params, aux, data, label, cfg, numerics)
    scale = float(data.shape[1])
    new_p, new_m = {}, {}
    for n, w in params.items():
        g = grads[n] * scale + opt["wd"] * w
        new_m[n] = opt["momentum"] * mom[n] - opt["learning_rate"] * g
        new_p[n] = w + new_m[n]
    return new_p, new_m, aux, loss
