"""Plain reference of the `ouro_2_6b` configuration: the looped language
model of ByteDance/Ouro-2.6B (config.json of the model card; the family is
LoopLM, "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741) cut to one chip's stage, trained under the paper's
stage-I objective.  Written from the equations in straightforward
`jax.numpy`, float32 at `Precision.HIGHEST`.  It imports nothing of the
program under test.

The loop, for ids x and next ids y, T = total_ut_steps:
    h^0 = E[x]
    h^t = RMSNorm(stack(h^{t-1}); w_f)        t = 1..T, the same layers and
                                              the same final norm every step
    z_t = W_head h^t,  L_t = -log softmax(z_t)[y],  lambda_t = sigmoid(w_g h^t + b_g)
    p_t = lambda_t prod_{j<t} (1 - lambda_j)  (t < T),   p_T = prod_{j<T} (1 - lambda_j)
    objective, at every position:  sum_t p_t L_t - beta H(p),  H(p) = -sum_t p_t log p_t
The served model (early_exit_threshold 1) runs all T steps and reads z_T.

A layer, every one the same (full attention; period 1):
    a  = u + RMSNorm(Attn(RMSNorm(u; w_1a)); w_1b)
    u' = a + RMSNorm(W_down (SiLU(W_gate n) * W_up n); w_2b),  n = RMSNorm(a; w_2a)
    RMSNorm(x; w) = x / sqrt(mean(x^2) + rms_norm_eps) * w      (w starts at 1)
Attn: q = W_q x, k = W_k x, v = W_v x, no biases, no q/k norms; the rotary
embedding on the WHOLE head (rotate-half pairing, theta = rope_theta) at
positions 0..L-1; causal softmax attention at scale head_dim^-1/2, as many
key-value heads as query heads; Attn = W_o attn.

Departures, listed as `assumed` in ouro_2_6b.json: SGD with momentum; beta,
the gate's initialisation and every initialiser (normal(0, 0.02), norm
weights 1, the gate's bias 0) are not in the published row.  The schedule,
not the mathematics, is cut to fit the chip: every layer application is
made again in the backward pass a sequence at a time, attention a block of
queries at a time, and each step's head and loss a sequence at a time, so
that T steps of 2 x 4,096 float32 rows fit beside 20 bytes a parameter.

What the harness hands over (it is not edited): `data` and `label`, both
(batch, L), the second the same stream one token later; `outputs` gets
`data` alone.
"""
import jax
import jax.numpy as jnp
from jax import lax
from jax.scipy.special import xlogy

HI = lax.Precision.HIGHEST


def init_params(key, cfg):
    """(params, aux): normal(0, initializer_range) matrices, norm weights 1,
    the exit gate's bias 0; rounded to the configuration's `param_dtype`.
    aux: the exit distribution's counts (rows, per step the sum of p_t, the
    sums of the expected step count and of the entropy), zero."""
    c, v = cfg["hidden_size"], cfg["vocab_size"]
    heads, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    inter = cfg["intermediate_size"]
    keys = iter(jax.random.split(key, 3 + 7 * cfg["num_hidden_layers"]))

    def normal(*shape):
        return cfg["initializer_range"] * jax.random.normal(
            next(keys), shape, jnp.float32)

    p = {"embed.w": normal(v, c), "head.w": normal(v, c),
         "gate.w": normal(1, c), "gate.b": jnp.zeros((1,), jnp.float32),
         "norm.w": jnp.ones((c,), jnp.float32)}
    for i in range(cfg["num_hidden_layers"]):
        L = f"l{i}."
        for norm in ("norm1a", "norm1b", "norm2a", "norm2b"):
            p[L + norm + ".w"] = jnp.ones((c,), jnp.float32)
        p[L + "attn.q.w"] = normal(heads * d, c)
        p[L + "attn.k.w"] = normal(kv * d, c)
        p[L + "attn.v.w"] = normal(kv * d, c)
        p[L + "attn.out.w"] = normal(c, heads * d)
        p[L + "mlp.gate.w"] = normal(inter, c)
        p[L + "mlp.up.w"] = normal(inter, c)
        p[L + "mlp.down.w"] = normal(c, inter)
    if cfg.get("param_dtype") == "bfloat16":
        # not astype(bfloat16).astype(float32): XLA folds that pair away
        p = {n: lax.reduce_precision(a, exponent_bits=8, mantissa_bits=7)
             for n, a in p.items()}
    aux = {"exit.stats": jnp.zeros((cfg["total_ut_steps"] + 3,),
                                   jnp.float32)}
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
    """Causal multi-head attention on x (B, L, C)."""
    heads, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], \
        cfg["head_dim"]
    b, t, _ = x.shape
    base = float(cfg["rope_theta"])
    pos = jnp.arange(t)
    q = _rotary(_mm(x, p["attn.q.w"], numerics).reshape(b, t, heads, d),
                base, pos)
    k = _rotary(_mm(x, p["attn.k.w"], numerics).reshape(b, t, kv, d),
                base, pos)
    v = _mm(x, p["attn.v.w"], numerics).reshape(b, t, kv, d)
    k, v = (jnp.repeat(a, heads // kv, axis=2) for a in (k, v))
    # a block of queries at a time against all L keys under the causal
    # mask, its scores made again in the backward pass
    bs = min(256, t)
    while t % bs:
        bs -= 1

    @jax.checkpoint
    def block(xs):
        qb, first = xs
        s = jnp.einsum("bqhd,bkhd->bhqk", _quant(qb, numerics),
                       _quant(k, numerics), precision=HI) * d ** -0.5
        see = (first + jnp.arange(bs))[:, None] >= jnp.arange(t)[None, :]
        pr = jax.nn.softmax(jnp.where(see, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", _quant(pr, numerics),
                          _quant(v, numerics), precision=HI)

    out = lax.map(block, (q.reshape(b, t // bs, bs, heads, d).swapaxes(0, 1),
                          jnp.arange(0, t, bs)))
    return _mm(out.swapaxes(0, 1).reshape(b, t, heads * d), p["attn.out.w"],
               numerics)


def layer(p, u, cfg, numerics):
    """One layer on (B, L, C); `p` holds the layer's leaves without their
    `l<i>.` prefix."""
    eps = cfg["rms_norm_eps"]
    a = u + _norm(_attn(p, _norm(u, p["norm1a.w"], eps), cfg, numerics),
                  p["norm1b.w"], eps)
    n = _norm(a, p["norm2a.w"], eps)
    mlp = _mm(jax.nn.silu(_mm(n, p["mlp.gate.w"], numerics)) *
              _mm(n, p["mlp.up.w"], numerics), p["mlp.down.w"], numerics)
    return a + _norm(mlp, p["norm2b.w"], eps)


def states(params, x, cfg, numerics):
    """[h^1, ..., h^T], each (B, L, C), of ids x (B, L): the T steps
    unrolled over the same weights, every layer application a sequence at a
    time and made again in the backward pass."""
    h = params["embed.w"][x]
    out = []
    run = jax.checkpoint(lambda p, row: layer(p, row[None], cfg, numerics))
    for _ in range(cfg["total_ut_steps"]):
        for i in range(cfg["num_hidden_layers"]):
            L = f"l{i}."
            p = {n[len(L):]: a for n, a in params.items() if n.startswith(L)}
            h = lax.map(lambda row: run(p, row)[0], h)
        h = _norm(h, params["norm.w"], cfg["rms_norm_eps"])
        out.append(h)
    return out


def exit_distribution(gate):
    """p (T, ...) of the exit gate's logits (T, ...): p_t = lambda_t
    prod_{j<t} (1 - lambda_j) for t < T, p_T = prod_{j<T} (1 - lambda_j)."""
    lam = jax.nn.sigmoid(gate)
    survive = jnp.cumprod(1 - lam[:-1], axis=0)
    before = jnp.concatenate([jnp.ones_like(lam[:1]), survive])
    return jnp.concatenate([lam[:-1] * before[:-1], before[-1:]])


def _step_terms(params, h, y, cfg, numerics):
    """(L_t, the gate's logit) of one step's states h (B, L, C) against y
    (B, L): the head and its loss a sequence at a time, the logits made
    again in the backward pass."""
    @jax.checkpoint
    def sequence(xs):
        hs, ys = xs
        z = _mm(hs, params["head.w"], numerics)
        loss = jax.nn.logsumexp(z, axis=-1) - jnp.take_along_axis(
            z, ys[:, None], axis=-1)[:, 0]
        return loss, _mm(hs, params["gate.w"], numerics)[:, 0] + \
            params["gate.b"][0]
    return lax.map(sequence, (h, y))


def _head_probs(params, h, numerics):
    """softmax(W_head h) of the states h (B, L, C), as (B L, V)."""
    probs = lax.map(lambda hs: jax.nn.softmax(
        _mm(hs, params["head.w"], numerics), axis=-1), h)
    return probs.reshape(-1, probs.shape[-1])


def outputs(params, aux, data, cfg, numerics="float32"):
    """The probabilities the program hands out: the last step's."""
    hs = states(params, data.astype(jnp.int32), cfg, numerics)
    return _head_probs(params, hs[-1], numerics)


def loss_fn(params, aux, data, label, cfg, numerics="float32"):
    """(the objective's mean over the positions, (aux, the reported loss)).
    The reported loss: the mean over all positions of -log(softmax(z_T)[y]
    + eps), as the program's cross-entropy metric reads output 0 against
    `softmax_label`; no gradient is taken of it."""
    x, y = data.astype(jnp.int32), label.astype(jnp.int32)
    hs = states(params, x, cfg, numerics)
    losses, gates = (jnp.stack(a) for a in zip(
        *[_step_terms(params, h, y, cfg, numerics) for h in hs]))
    p = exit_distribution(gates)                       # (T, B, L)
    entropy = -jnp.sum(xlogy(p, p), axis=0)
    objective = jnp.sum(p * losses, axis=0) - \
        cfg["exit_entropy_weight"] * entropy
    read = -jnp.mean(jnp.log(jnp.exp(-losses[-1]) + cfg["metric_eps"]))
    t = jnp.arange(1, len(hs) + 1, dtype=jnp.float32).reshape(-1, 1, 1)
    new_aux = {"exit.stats": aux["exit.stats"] + lax.stop_gradient(
        jnp.concatenate([jnp.full((1,), x.size, jnp.float32),
                         jnp.sum(p, axis=(1, 2)),
                         jnp.sum(t * p).reshape(1),
                         jnp.sum(entropy).reshape(1)]))}
    return jnp.mean(objective), (new_aux, lax.stop_gradient(read))


def train_step(params, mom, aux, data, label, cfg, numerics="float32",
               rows=None):
    """One step of SGD with momentum.  The program's head sums the gradient
    over all batch * L positions and the optimizer rescales by 1 / batch, so
    the step follows L times the gradient of the mean objective.  Returns
    the loss as the program reports it.  `rows` exists for the planted fault
    of the control test."""
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
