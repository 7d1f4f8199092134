"""Control-flow operators `_foreach` / `_while_loop` / `_cond`
(ops/control_flow.py, symbol/contrib.py builders) — reference
`src/operator/control_flow.cc:1255-1423` + `python/mxnet/symbol/contrib.py`.

Covers: symbolic vs imperative parity, gradients through the scan,
symbol JSON round trips, closure capture of outer symbols, and the
one-scan hybrid unroll of recurrent cells."""
import numpy as np

import incubator_mxnet_tpu as mx


def _bind_fwd(sym, args, grads=None):
    ex = sym.bind(mx.cpu(), {k: mx.nd.array(v) for k, v in args.items()},
                  args_grad={k: mx.nd.zeros(v.shape)
                             for k, v in grads.items()} if grads else None)
    return ex


def test_foreach_symbolic_imperative_parity():
    data = mx.sym.Variable("data")
    init = mx.sym.Variable("init")
    w = mx.sym.Variable("w")

    def body(x, s):
        out = mx.sym.broadcast_add(mx.sym.broadcast_mul(x, w), s)
        return out, out

    outs, states = mx.sym.contrib.foreach(body, data, init)
    g = mx.sym.Group([outs, states])
    rng = np.random.RandomState(0)
    dnp = rng.rand(5, 4).astype("f4")
    inp = rng.rand(4).astype("f4")
    wnp = rng.rand(4).astype("f4")
    ex = _bind_fwd(g, {"data": dnp, "init": inp, "w": wnp})
    o = ex.forward()

    wa = mx.nd.array(wnp)
    io_, is_ = mx.nd.contrib.foreach(
        lambda x, s: (x * wa + s, x * wa + s),
        mx.nd.array(dnp), mx.nd.array(inp))
    np.testing.assert_allclose(o[0].asnumpy(), io_.asnumpy(), rtol=1e-6)
    np.testing.assert_allclose(o[1].asnumpy(), is_.asnumpy(), rtol=1e-6)
    # one _foreach node, not 5 unrolled bodies
    cf = [n for n in g._topo() if not n.is_variable and
          n.op.name == "_foreach"]
    assert len(cf) == 1


def test_foreach_json_roundtrip():
    data = mx.sym.Variable("data")
    init = mx.sym.Variable("init")
    w = mx.sym.Variable("w")
    # closure includes a COMPUTED outer symbol (w * 2): the subgraph keeps
    # the upstream node and XLA hoists the loop-invariant multiply
    w2 = w * 2.0

    def body(x, s):
        return mx.sym.broadcast_add(mx.sym.broadcast_mul(x, w2), s), s + 1.0

    outs, _ = mx.sym.contrib.foreach(body, data, init)
    rng = np.random.RandomState(1)
    args = {"data": rng.rand(3, 4).astype("f4"),
            "init": rng.rand(4).astype("f4"),
            "w": rng.rand(4).astype("f4")}
    o1 = _bind_fwd(outs, args).forward()[0].asnumpy()
    g2 = mx.sym.load_json(outs.tojson())
    o2 = _bind_fwd(g2, args).forward()[0].asnumpy()
    np.testing.assert_allclose(o2, o1, rtol=1e-6)


def test_foreach_gradient_matches_static_unroll():
    """d/dw through the scan == d/dw through T unrolled bodies."""
    T, C = 4, 3
    rng = np.random.RandomState(2)
    dnp = rng.rand(T, C).astype("f4")
    inp = rng.rand(C).astype("f4")
    wnp = rng.rand(C).astype("f4")

    def build_scan():
        data = mx.sym.Variable("data")
        init = mx.sym.Variable("init")
        w = mx.sym.Variable("w")
        outs, states = mx.sym.contrib.foreach(
            lambda x, s: ((mx.sym.broadcast_mul(x, w) + s,
                           mx.sym.broadcast_mul(x, w) + s))[0:2],
            data, init)
        return mx.sym.sum(outs)

    def build_unrolled():
        data = mx.sym.Variable("data")
        init = mx.sym.Variable("init")
        w = mx.sym.Variable("w")
        s = init
        outs = []
        for t in range(T):
            x = mx.sym.squeeze(mx.sym.slice_axis(data, axis=0, begin=t,
                                                 end=t + 1), axis=0)
            s = mx.sym.broadcast_mul(x, w) + s
            outs.append(s)
        return mx.sym.sum(mx.sym.stack(*outs, axis=0, num_args=T))

    grads = {}
    for name, build in [("scan", build_scan), ("unrolled", build_unrolled)]:
        ex = mx.sym.Group([build()]).bind(
            mx.cpu(),
            {"data": mx.nd.array(dnp), "init": mx.nd.array(inp),
             "w": mx.nd.array(wnp)},
            args_grad={"w": mx.nd.zeros(C), "data": mx.nd.zeros((T, C)),
                       "init": mx.nd.zeros(C)})
        ex.forward(is_train=True)
        ex.backward([mx.nd.ones(())])
        grads[name] = {k: v.asnumpy().copy()
                       for k, v in ex.grad_dict.items()}
    for k in ("w", "data", "init"):
        np.testing.assert_allclose(grads["scan"][k], grads["unrolled"][k],
                                   rtol=1e-5, atol=1e-6, err_msg=k)


def test_while_loop_parity_and_padding():
    i = mx.sym.Variable("i")
    s = mx.sym.Variable("s")
    outs, fin = mx.sym.contrib.while_loop(
        cond=lambda i, s: i < 5,
        func=lambda i, s: ([i + s], [i + 1, s + i]),
        loop_vars=[i, s], max_iterations=10)
    g = mx.sym.Group(list(outs) + list(fin))
    ex = _bind_fwd(g, {"i": np.array([0.0], "f4"),
                       "s": np.array([1.0], "f4")})
    o = ex.forward()
    io_, if_ = mx.nd.contrib.while_loop(
        lambda i, s: (i < 5), lambda i, s: ([i + s], [i + 1, s + i]),
        [mx.nd.array([0.0]), mx.nd.array([1.0])], max_iterations=10)
    # symbolic output is padded to max_iterations (reference semantics);
    # the valid prefix must equal the imperative (sliced) output
    n = io_[0].shape[0]
    np.testing.assert_allclose(o[0].asnumpy()[:n], io_[0].asnumpy())
    np.testing.assert_allclose(o[0].asnumpy()[n:], 0.0)
    np.testing.assert_allclose(o[1].asnumpy(), if_[0].asnumpy())
    np.testing.assert_allclose(o[2].asnumpy(), if_[1].asnumpy())


def test_cond_both_branches():
    a = mx.sym.Variable("a")
    b = mx.sym.Variable("b")
    out = mx.sym.contrib.cond(mx.sym.sum(a * b) < 5,
                              lambda: (a + 5) * (b + 5),
                              lambda: (a - 5) * (b - 5))
    for av, bv, want in [(1.0, 2.0, 42.0), (3.0, 4.0, 2.0)]:
        ex = _bind_fwd(out, {"a": np.array([av], "f4"),
                             "b": np.array([bv], "f4")})
        got = ex.forward()[0].asnumpy()
        np.testing.assert_allclose(got, [want], rtol=1e-6)
        # imperative parity
        imp = mx.nd.contrib.cond(
            mx.nd.sum(mx.nd.array([av]) * mx.nd.array([bv])) < 5,
            lambda: (mx.nd.array([av]) + 5) * (mx.nd.array([bv]) + 5),
            lambda: (mx.nd.array([av]) - 5) * (mx.nd.array([bv]) - 5))
        np.testing.assert_allclose(got, imp.asnumpy())


def test_cell_unroll_emits_one_foreach():
    """A hybrid LSTM cell unroll over a symbolic sequence compiles to ONE
    scan, and matches the classic static unroll numerically."""
    T, N, C, H = 5, 2, 3, 4
    cell = mx.gluon.rnn.LSTMCell(H, input_size=C)
    cell.initialize()
    data = mx.sym.Variable("data")
    begin = [mx.sym.Variable("h0"), mx.sym.Variable("c0")]
    out_scan, st_scan = cell.unroll(T, data, begin_state=begin,
                                    layout="NTC", merge_outputs=True)
    g_scan = mx.sym.Group([out_scan] + list(st_scan))
    cf = [n for n in g_scan._topo() if not n.is_variable and
          n.op.name == "_foreach"]
    assert len(cf) == 1, "hybrid unroll must emit exactly one _foreach"

    # static unroll via pre-sliced inputs (the classic path)
    slices = list(mx.sym.split(data, num_outputs=T, axis=1,
                               squeeze_axis=True))
    out_st, st_st = cell.unroll(T, slices, begin_state=begin,
                                layout="NTC", merge_outputs=True)
    g_st = mx.sym.Group([out_st] + list(st_st))

    rng = np.random.RandomState(3)
    vals = {"data": rng.rand(N, T, C).astype("f4"),
            "h0": np.zeros((N, H), "f4"), "c0": np.zeros((N, H), "f4")}
    params = {k: v.data().asnumpy()
              for k, v in cell.collect_params().items()}
    args = dict(vals)
    for name in g_scan.list_arguments():
        if name in params:
            args[name] = params[name]
    o1 = _bind_fwd(g_scan, args).forward()
    args2 = dict(vals)
    for name in g_st.list_arguments():
        if name in params:
            args2[name] = params[name]
    o2 = _bind_fwd(g_st, args2).forward()
    np.testing.assert_allclose(o1[0].asnumpy(), o2[0].asnumpy(),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(o1[1].asnumpy(), o2[1].asnumpy(),
                               rtol=1e-5, atol=1e-6)


def test_foreach_multi_data_multi_state():
    d1 = mx.sym.Variable("d1")
    d2 = mx.sym.Variable("d2")
    s1 = mx.sym.Variable("s1")
    s2 = mx.sym.Variable("s2")

    def body(xs, ss):
        a, b = xs
        u, v = ss
        return [a + u, b * v], [u + 1.0, v * 2.0]

    outs, states = mx.sym.contrib.foreach(body, [d1, d2], [s1, s2])
    g = mx.sym.Group(list(outs) + list(states))
    rng = np.random.RandomState(4)
    args = {"d1": rng.rand(3, 2).astype("f4"),
            "d2": rng.rand(3, 2).astype("f4"),
            "s1": rng.rand(2).astype("f4"),
            "s2": rng.rand(2).astype("f4")}
    o = _bind_fwd(g, args).forward()
    # imperative parity
    io_, is_ = mx.nd.contrib.foreach(
        lambda xs, ss: ([xs[0] + ss[0], xs[1] * ss[1]],
                        [ss[0] + 1.0, ss[1] * 2.0]),
        [mx.nd.array(args["d1"]), mx.nd.array(args["d2"])],
        [mx.nd.array(args["s1"]), mx.nd.array(args["s2"])])
    np.testing.assert_allclose(o[0].asnumpy(), io_[0].asnumpy(), rtol=1e-6)
    np.testing.assert_allclose(o[1].asnumpy(), io_[1].asnumpy(), rtol=1e-6)
    np.testing.assert_allclose(o[2].asnumpy(), is_[0].asnumpy(), rtol=1e-6)
    np.testing.assert_allclose(o[3].asnumpy(), is_[1].asnumpy(), rtol=1e-6)


def test_unroll_honors_length():
    """unroll(length=3) over a T=5 symbolic sequence computes exactly 3
    steps (the scan path must not silently consume the full axis)."""
    T_data, T_req, N, C, H = 5, 3, 2, 3, 4
    cell = mx.gluon.rnn.LSTMCell(H, input_size=C)
    cell.initialize()
    data = mx.sym.Variable("data")
    begin = [mx.sym.Variable("h0"), mx.sym.Variable("c0")]
    outs, _ = cell.unroll(T_req, data, begin_state=begin, layout="NTC",
                          merge_outputs=True)
    args = {"data": np.random.RandomState(0).rand(N, T_data, C)
            .astype("f4"),
            "h0": np.zeros((N, H), "f4"), "c0": np.zeros((N, H), "f4")}
    params = {k: v.data().asnumpy() for k, v in cell.collect_params().items()}
    for name in outs.list_arguments():
        if name in params:
            args[name] = params[name]
    o = _bind_fwd(outs, args).forward()[0]
    assert o.shape == (N, T_req, H), o.shape


def test_while_loop_gradient_not_poisoned_past_termination():
    """Ops that are only safe while cond holds (e.g. sqrt of a shrinking
    value) must not inject NaN gradients from terminated-range steps —
    the func subgraph executes under lax.cond, like the reference stops
    executing outright."""
    x = mx.sym.Variable("x")
    i = mx.sym.Variable("i")
    # while i < 3: out = sqrt(x - i); i += 1   (x - i < 0 once i >= x:
    # executing past termination would produce NaN)
    outs, fin = mx.sym.contrib.while_loop(
        cond=lambda i, x: i < 3,
        func=lambda i, x: ([mx.sym.sqrt(x - i)], [i + 1, x]),
        loop_vars=[i, x], max_iterations=8)
    loss = mx.sym.sum(outs[0])
    ex = loss.bind(mx.cpu(),
                   {"i": mx.nd.array([0.0]), "x": mx.nd.array([3.5])},
                   args_grad={"x": mx.nd.zeros(1)})
    ex.forward(is_train=True)
    ex.backward([mx.nd.ones(())])
    g = ex.grad_dict["x"].asnumpy()
    assert np.isfinite(g).all(), g
    # d/dx sum_t sqrt(x - t) for t=0,1,2
    want = sum(0.5 / np.sqrt(3.5 - t) for t in range(3))
    np.testing.assert_allclose(g, [want], rtol=1e-5)


def test_foreach_lstm_module_fit_fused():
    """The lstm_bucketing shape end-to-end on CPU: a Module whose graph
    contains ONE _foreach trains through the fused scan-block fit loop
    (the PTB example's path), loss/perplexity improving."""
    import os
    from incubator_mxnet_tpu import rnn, io

    vocab, embed, hidden, seq, bs = 40, 8, 16, 6, 8
    stack = rnn.SequentialRNNCell()
    stack.add(rnn.LSTMCell(hidden, prefix="lstm_l0_"))
    data = mx.sym.Variable("data")
    label = mx.sym.Variable("softmax_label")
    emb = mx.sym.Embedding(data, input_dim=vocab, output_dim=embed,
                           name="embed")
    stack.reset()
    outputs, _ = stack.unroll(seq, inputs=emb, merge_outputs=True)
    pred = mx.sym.Reshape(outputs, shape=(-1, hidden))
    pred = mx.sym.FullyConnected(pred, num_hidden=vocab, name="pred")
    net = mx.sym.SoftmaxOutput(pred, mx.sym.Reshape(label, shape=(-1,)),
                               name="softmax")
    assert sum(1 for n in net._topo()
               if not n.is_variable and n.op.name == "_foreach") == 1

    rng = np.random.RandomState(0)
    tokens = rng.randint(1, vocab, (64, seq)).astype("f4")
    it = mx.io.NDArrayIter({"data": tokens},
                           {"softmax_label": np.roll(tokens, -1, 1)},
                           batch_size=bs)
    mod = mx.mod.Module(net, context=mx.cpu())
    vals = []
    mod.fit(it, num_epoch=3, optimizer="sgd",
            optimizer_params={"learning_rate": 0.5,
                              "rescale_grad": 1.0 / bs},
            eval_metric=mx.metric.Perplexity(0),
            initializer=mx.initializer.Xavier(),
            epoch_end_callback=lambda e, s, a, x: vals.append(None),
            kvstore=None)
    assert mod._fused_step is not None and not mod._fused_step.broken, \
        "the _foreach graph must train through the fused step"
    assert len(mod._fused_step._jit_block) >= 1, \
        "scan-block mode must engage"


def test_while_loop_early_termination_cost():
    """With num_out_data == 0 (no per-step outputs) the imperative
    while_loop lowers to a TRUE `lax.while_loop`: cost scales with the
    ACTUAL iteration count, not max_iterations.  The
    masked-scan lowering would run all max_iterations — at 5M that is
    seconds of wall time; the fast path finishes in milliseconds."""
    import time

    def run(max_iter):
        t0 = time.perf_counter()
        outs, fin = mx.nd.contrib.while_loop(
            lambda i, s: i < 5,
            lambda i, s: ([], [i + 1, s + i]),
            [mx.nd.array([0.0]), mx.nd.array([1.0])],
            max_iterations=max_iter)
        assert outs == []
        np.testing.assert_allclose(fin[0].asnumpy(), [5.0])
        np.testing.assert_allclose(fin[1].asnumpy(), [11.0])
        return time.perf_counter() - t0

    run(100)                      # compile warmup for the small signature
    t_small = run(100)
    t_big = run(5_000_000)        # includes ITS compile: still bounded
    # identical results, and 50,000x more max_iterations must not cost
    # 50,000x the time — allow generous CI jitter, catch the O(max_iter)
    # regression which would be seconds here
    assert t_big < max(50 * t_small, 2.0), (t_small, t_big)


def test_while_loop_fast_path_matches_masked_scan():
    """Fast-path numerics equal the masked-scan lowering (forced by
    requesting a per-step output) and the symbolic padded path."""
    cond = lambda i, s: i < 7
    body_out = lambda i, s: ([i * s], [i + 1, s + i])
    body_noout = lambda i, s: ([], [i + 1, s + i])
    init = lambda: [mx.nd.array([0.0]), mx.nd.array([2.0])]
    _, fin_fast = mx.nd.contrib.while_loop(cond, body_noout, init(),
                                           max_iterations=64)
    _, fin_scan = mx.nd.contrib.while_loop(cond, body_out, init(),
                                           max_iterations=64)
    for a, b in zip(fin_fast, fin_scan):
        np.testing.assert_allclose(a.asnumpy(), b.asnumpy())


def test_foreach_duplicate_closure_names_bind_correctly():
    """Two distinct outer Variables sharing one NAME (legal in the symbol
    API, and what nested loop bodies reusing inner names produce) must
    each bind their own closure slot.  The round-5 known issue: the
    rebuilt-from-JSON subgraph bound by name, collapsing both onto one
    slot and silently computing with the wrong input."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.symbol.symbol import graph_eval_fn

    data = mx.sym.Variable("data")
    init = mx.sym.Variable("init")
    w1 = mx.sym.Variable("w")
    w2 = mx.sym.Variable("w")   # distinct node, same name

    def body(x, s):
        y = mx.sym.broadcast_add(mx.sym.broadcast_mul(x, w1),
                                 mx.sym.broadcast_mul(s, w2))
        return y, s + 1.0

    outs, _ = mx.sym.contrib.foreach(body, data, init)
    for sym in (outs, mx.sym.load_json(outs.tojson())):  # + JSON round trip
        gfn, arg_nodes, _aux, _nrng = graph_eval_fn(sym, False)
        names = [n.name for n in arg_nodes]
        assert names.count("w") == 2

        rng = np.random.RandomState(3)
        dnp = rng.rand(4, 3).astype("f4")
        inp = rng.rand(3).astype("f4")
        w1v = rng.rand(3).astype("f4")
        w2v = rng.rand(3).astype("f4")
        # positional feed (executor bind rejects duplicate top-level
        # names by design; the subgraph binding is what's under test)
        by_pos = {"data": dnp, "init": inp}
        vals, w_feed = [], [w1v, w2v]
        for n in arg_nodes:
            if n.name in by_pos:
                vals.append(jnp.asarray(by_pos[n.name]))
            else:
                vals.append(jnp.asarray(w_feed.pop(0)))
        (ys,), _ = gfn(tuple(vals), (), jax.random.PRNGKey(0))
        # reference: y_t = x_t * w1 + s_t * w2, s advancing by +1
        s = inp.copy()
        want = np.zeros_like(dnp)
        for t in range(dnp.shape[0]):
            want[t] = dnp[t] * w1v + s * w2v
            s = s + 1.0
        np.testing.assert_allclose(np.asarray(ys), want, rtol=1e-5,
                                   atol=1e-6)
