"""One run of one cell: load, warm up, measure, compare, print one line.

The timed path is `mx.mod.Module.fit`, the entry every training user calls.
Set-up builds ONE module, drives it through `fit` for its first block of K
steps (the block whose readings `correct` compares, and the compile or cache
load of the window's program), once more for a warm-up block, and hands the
same module to the window, which is one more `fit` call over a feed that
ends at the first block boundary after `--seconds`.
"""
import gc
import json
import math
import shutil
import sys
import tempfile
import time

import numpy as np

from . import cells, compare, flops, peaks, trace_reduce, traffic as _traffic

TRACE_SECONDS = 15      # a traced run measures (and traces) at most this long


def log(msg):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Stamps:
    def __init__(self, t0):
        self.t0 = t0
        self.marks = []

    def __call__(self, name):
        self.marks.append((name, time.perf_counter() - self.t0))
        log(f"t+{self.marks[-1][1]:8.3f}s  {name}")


def _program_state(mod):
    """{leaf kind: {program name: jax array}} of the module after a fit
    call: float32 masters (or the weights themselves), momentum, statistics."""
    fs = mod._fused_step
    exec0 = mod._exec_group.execs[0]
    masters, moms = {}, {}
    for name, index in zip(fs._param_names, fs._indices):
        state = mod._updater.states.get(index)
        if isinstance(state, tuple):
            mom, w32 = state
            masters[name] = w32._data
        else:
            mom = state
            masters[name] = exec0.arg_dict[name]._data
        if mom is not None:
            moms[name] = mom._data
    auxs = {n: exec0.aux_dict[n]._data for n in fs._aux_names}
    return masters, moms, auxs


def _jax_compile_counter():
    """Counts JAX's backend compilations from now on (a mutable [n])."""
    import jax.monitoring
    count = [0]

    def on_event(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            count[0] += 1
    jax.monitoring.register_event_duration_secs_listener(on_event)
    return count


class Program:
    """The system under test for one cell and one seed: ONE module, built
    and driven through `Module.fit` for its first block of K steps, whose
    readings (`prog`) the comparison takes.  The same object then serves the
    warm-up and the window (`fit`)."""

    def __init__(self, cell, seed, stamp=lambda name: None, sabotage=None):
        import jax
        import incubator_mxnet_tpu as mx     # places the compile cache
        from incubator_mxnet_tpu.ndarray.ndarray import NDArray
        cfg, tr, adapter, ref = cell.cfg, cell.traffic, cell.adapter, \
            cell.reference
        self.mx, self.cell = mx, cell
        self.k = k = int(cfg["fused_step_block"])
        self.batch = batch = int(tr["batch_per_chip"])
        ctx = mx.tpu(0)
        self.pool = _traffic.make_pool(tr, cfg, adapter, batch, seed)
        fed = sabotage.pool(self.pool) if sabotage is not None else self.pool
        self.feed = _traffic.make_feed(mx, tr, cfg, adapter, batch, fed, k)
        stamp("traffic made")

        symbol = adapter.build_symbol(mx, cfg)
        fixed = getattr(adapter, "fixed_params", lambda *a: {})(
            cfg, batch, symbol.list_arguments())
        self.mod = mod = mx.mod.Module(
            symbol, context=ctx, label_names=("softmax_label",),
            fixed_param_names=list(fixed))
        self.key = compare.program_key(seed)
        params, aux = jax.jit(lambda kk: ref.init_params(kk, cfg))(self.key)
        names = symbol.list_arguments() + symbol.list_auxiliary_states()
        given = adapter.to_program({**params, **aux}, cfg, names)
        aux_names = set(symbol.list_auxiliary_states())
        arg_params = {n: NDArray(a, ctx=ctx) for n, a in given.items()
                      if n not in aux_names}
        aux_params = {n: NDArray(a, ctx=ctx) for n, a in given.items()
                      if n in aux_names}
        arg_params.update({n: mx.nd.array(a, ctx=ctx)
                           for n, a in fixed.items()})
        opt = cfg["optimizer"]
        opt_params = {"learning_rate": opt["learning_rate"],
                      "momentum": opt["momentum"], "wd": opt["wd"],
                      "rescale_grad": 1.0 / batch}
        if opt.get("multi_precision"):
            opt_params["multi_precision"] = True
        self.fit_kw = dict(
            num_epoch=1, optimizer=opt["name"], optimizer_params=opt_params,
            eval_metric=mx.metric.create("ce"), kvstore=None)
        if sabotage is not None:
            sabotage.fit_kwargs(self.fit_kw)
        stamp("module and weights made")

        # the first block: compile (or load), and the program's readings
        sums, out0 = [], []

        def on_batch(param):
            param.eval_metric.get()
            if not sums and hasattr(ref, "outputs"):
                # the first step's outputs, as any callback of a user's
                # reads them (the block's cursor stands at this step)
                out0.append(mod.get_outputs()[0]._data)
            sums.append((param.eval_metric.sum_metric,
                         param.eval_metric.num_inst))

        self.feed.arm(blocks=1)
        mod.fit(self.feed, arg_params=arg_params, aux_params=aux_params,
                batch_end_callback=on_batch, **self.fit_kw)
        stamp("first block done (compile or cache load)")
        fs = mod._fused_step
        self.unfused = int(fs is None or fs.broken or
                           mod._fit_block_k() != k or len(sums) != k)
        self.prog = prog = {
            "loss": [(s1 - s0) / max(n1 - n0, 1) for (s0, n0), (s1, n1)
                     in zip([(0.0, 0)] + sums, sums)]}
        if out0:
            prog["out0"] = np.asarray(out0[0].astype("float32"))
        if not self.unfused:
            masters, moms, auxs = _program_state(mod)
            prog["dw"] = compare.leaf_norms(
                adapter.from_program(masters, cfg), params)
            if moms:
                prog["mom"] = compare.leaf_norms(
                    adapter.from_program(moms, cfg))
            if auxs:
                prog["aux"] = compare.leaf_norms(
                    adapter.from_program(auxs, cfg), aux)
        stamp("program's readings taken")

    def fit(self, blocks=None, seconds=None):
        """The window's own call; returns the steps it ran."""
        self.feed.arm(blocks=blocks, seconds=seconds)
        self.mod.fit(self.feed, **self.fit_kw)
        return self.feed.served

    def close(self):
        """Free the program's state on the device."""
        self.mod = self.feed = None
        gc.collect()


def run(cell_name, seed, seconds, trace, t0, tiny=False, sabotage=None):
    """Returns the process's exit code; prints the result line.

    `tiny` and `sabotage` are for the tests under benchmark/tests/ only:
    the first skips the look for a chip and shrinks the cell to the `tiny`
    sizes its own configuration and traffic files state, the second is an
    object whose hooks break the timed path underneath (`fit_kwargs(kw)`,
    `pool(pool)`)."""
    stamp = Stamps(t0)
    cell = cells.Cell(cells.benchmark_json(), cell_name, tiny)
    cfg, adapter, ref = cell.cfg, cell.adapter, cell.reference
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if cell.chips != 1:
        print(f"benchmark: cell {cell_name} asks for {cell.chips} chips; "
              "this harness drives one (PERF.md section 7)", file=sys.stderr)
        return 2
    if not tiny and device["platform"] != "tpu":
        print(f"benchmark: cell {cell_name} needs a TPU chip; JAX reports "
              f"{device}; nothing was run", file=sys.stderr)
        return 2
    chip = devs[0]
    compiles = _jax_compile_counter()
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import io_plane
    stamp("imports done")
    log(f"compile cache: {jax.config.jax_compilation_cache_dir}")
    program = Program(cell, seed, stamp, sabotage)
    mod, k, batch = program.mod, program.k, program.batch
    prog, unfused, key, pool = program.prog, program.unfused, program.key, \
        program.pool

    # -- warm-up: the window's own call once more, uncompared --------------
    program.fit(blocks=1)
    stamp("warm-up block done")
    programs = [p for p in mx.compile.stats()["programs"]
                if p["compiles"] or p["disk_hits"]]

    # -- the window ---------------------------------------------------------
    seconds = float(seconds)
    if trace:
        seconds = min(seconds, TRACE_SECONDS)
        mx.obs.trace.enable()            # spans to memory, no file
        mx.obs.trace.reset()
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
    gc.collect()
    jax.block_until_ready(jax.live_arrays())
    mem0 = chip.memory_stats() or {}
    io0, cache0 = io_plane.stats(), dict(mx.compile.stats()["counters"])
    guard0 = mod._guardian.stats()["skips"] if mod._guardian else 0
    compiles0 = compiles[0]
    if trace:
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    setup_s = time.perf_counter() - t0
    wall0_ns, w0 = time.time_ns(), time.perf_counter()
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_MARK):
        steps = program.fit(seconds=seconds)
        jax.block_until_ready(jax.live_arrays())
    window_s = time.perf_counter() - w0
    window_end_us = time.time_ns() / 1e3
    if trace:
        jax.profiler.stop_trace()
    stamp(f"window closed: {steps} steps in {window_s:.3f} s")
    io1, cache1 = io_plane.stats(), dict(mx.compile.stats()["counters"])
    guardian = mod._guardian
    skips = (guardian.stats()["skips"] - guard0) if guardian else 0
    # The chip holds the live buffers (`bytes_in_use`) AND the scratch the
    # runtime reserves for loaded programs (`bytes_reserved`), two disjoint
    # regions; `peak_bytes_in_use` never sees the second.  The reading is
    # the larger of that peak and of in use + reserved AT ONE MOMENT, taken
    # at the window's start and at its close: no sum of two peaks that need
    # not coincide, so it can only under-read (PERF.md section 4, "Memory")
    mem1 = chip.memory_stats() or {}
    log(f"{chip}: memory_stats at the window's start {json.dumps(mem0)}")
    log(f"{chip}: memory_stats at its close {json.dumps(mem1)}")
    device["memory_peak_bytes"] = int(max(
        [mem1.get("peak_bytes_in_use", 0)] +
        [m.get("bytes_in_use", 0) + m.get("bytes_reserved", 0)
         for m in (mem0, mem1)]))
    fs = mod._fused_step
    conditions = {
        "unfused": float(unfused or fs is None or fs.broken),
        "fallbacks": float(cache1["fallbacks"]),
        "compiles_in_window": float(
            max(compiles[0] - compiles0,
                cache1["compiles"] - cache0["compiles"])),
        "guardian_off": float(guardian is None),
        "limits_unset": float(not cell.limits),
    }
    spans = mx.obs.trace.buffered() if trace else []

    # -- free the program, then the reference -------------------------------
    del mod, fs, guardian
    program.close()
    rate = steps * batch * cfg["rate"]["per_row"] / window_s
    metrics = {}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    breakdown = None
    if not trace:
        metrics[cfg["rate"]["metric"]] = rate
        metrics["setup_s"] = setup_s
    else:
        planes = trace_reduce.load_xplane(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        for p in planes:
            for line in p["lines"]:
                log(f"trace: plane {p['name']} line {line['name']}: "
                    f"{len(line['events'])} events")
        win = trace_reduce.window_of(planes)
        shift = (win[0] - wall0_ns) if win else 0.0
        host_spans = [(s["name"], s["ts"] * 1e3 + shift,
                       (s["ts"] + s["dur"]) * 1e3 + shift) for s in spans]
        summary = trace_reduce.reduce(
            planes, host_spans + trace_reduce.annotations(planes))
        del planes
        stamp("trace reduced")
        count = adapter.flops_per_sample(cfg, flops)
        ctx = {
            "cell": cell.name, "cfg": cfg, "traffic": cell.traffic,
            "chips": cell.chips, "k": k, "platform": device["platform"],
            "window_s": window_s, "window_end_us": window_end_us,
            "steps": steps, "rate": rate,
            "train_flops_per_sample": count.train_flops,
            "peaks": peaks.of(device["kind"])
            if device["platform"] == "tpu" else None,
            "spans": spans, "trace": summary, "programs": programs,
            "io": {key: io1[key] - io0[key] for key in io1
                   if isinstance(io1[key], (int, float)) and key in io0},
            "compiles_in_window": conditions["compiles_in_window"],
        }
        for m in cell.per_layer:
            value = cells.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = value
        if summary is not None:
            device["busy_s"] = summary["busy_ns_mean"] / 1e9
            device["window_s"] = summary["window_ns"] / 1e9
            breakdown = {
                "device_ops": [[n, ns / 1e9]
                               for n, ns in summary["device_ops"][:10]],
                "idle_gaps": [[n, ns / 1e9]
                              for n, ns in summary["idle_gaps"][:10]]}

    if device["platform"] != "tpu":
        # a number only a chip can give is never printed from another
        # platform (the tests' CPU runs): counts and program spans stay
        source = {m["name"]: m["source"]
                  for m in cell.end_to_end + cell.per_layer}
        metrics = {n: v for n, v in metrics.items()
                   if source.get(n) not in ("device_trace", "host_clock")}
    reference = compare.run_reference(ref, cfg, key, pool, k)
    stamp("reference done")
    log("loss per step, program:   " + " ".join(f"{v:.6f}"
                                                 for v in prog["loss"]))
    log("loss per step, reference: " + " ".join(f"{v:.6f}"
                                                 for v in reference["loss"]))
    nums = compare.numbers(prog, reference) if not unfused else {}
    for name, value in conditions.items():
        nums[name] = (value, "run")
    limits = dict(cell.limits)
    limits.update({name: 0.0 for name in conditions})
    correct, compared = compare.judge(nums, limits)

    result = {
        "correct": bool(correct), "attempted": int(steps),
        "failed": int(skips),
        "metrics": {n: {"value": v, "unit": units.get(n, "")}
                    for n, v in metrics.items()},
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["setup_marks"] = [[n, round(t, 3)] for n, t in stamp.marks]
    result["compared"] = {
        n: {"value": None if c["value"] is None or
            not math.isfinite(c["value"]) else c["value"],
            "limit": c["limit"], "at": c["at"]}
        for n, c in compared.items()}
    for n, c in compared.items():
        log(f"compared {n}: {c['value']} (limit {c['limit']}, {c['at']})")
    print(json.dumps(result), flush=True)
    return 0
