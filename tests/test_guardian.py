"""Training guardian: in-graph health word, skip/rollback/quarantine
(ISSUE-10).

Covers: the fused step's in-graph health word observes every step with
no per-step host sync; an injected non-finite gradient is refused
in-graph (skip-batch) and two identical seeded runs end bit-identical —
while the same injection WITHOUT the guardian poisons the parameters;
an injected loss spike triggers rollback-to-last-good and the recovered
run ends bit-identical to a clean reference over the same schedule with
zero program-cache compiles during recovery; checkpoints carry a
``health`` stamp and `latest_healthy` honors stamp + max_step; the
consecutive-failure budget escalates to `TrainingDivergedError` naming
step/signal/shard; quarantined positions are skipped on resume;
multi-worker health bits agree through a kvstore-style reduction; the
RecordIO reader skips torn tails and magic mismatches with a
`corrupt_records` count instead of raising; the `corrupt` fault kind
bit-flips payloads deterministically through `faults.mutate`; the
image iterator quarantines corrupt records and never re-reads them;
guardian events surface in `analysis.runtime_report()`; and the
`nan-swallow` mxlint AST lint flags hand-rolled catch-and-continue
training loops.
"""
import json
import os
import struct

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import analysis, config, io, recordio, sym
from incubator_mxnet_tpu import compile as mxcompile
from incubator_mxnet_tpu.resilience import (RollbackRequested,
                                            TrainingDivergedError,
                                            TrainingGuardian, faults)
from incubator_mxnet_tpu.resilience.guardian import QuarantineLog


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.clear()
    yield
    faults.clear()
    analysis.reset_runtime()


@pytest.fixture()
def fast_guardian(monkeypatch):
    monkeypatch.setenv("MXNET_GUARDIAN_INTERVAL", "4")
    monkeypatch.setenv("MXNET_GUARDIAN_SPIKE_WINDOW", "4")


def _model(seed=0):
    np.random.seed(seed)
    mx.random.seed(seed)
    net = sym.Variable("data")
    net = sym.FullyConnected(net, num_hidden=16, name="fc1")
    net = sym.Activation(net, act_type="tanh")
    net = sym.FullyConnected(net, num_hidden=4, name="fc2")
    net = sym.SoftmaxOutput(net, name="softmax")
    return mx.mod.Module(net, context=mx.cpu())


def _data(n=128, bs=8):
    rng = np.random.RandomState(3)
    x = rng.standard_normal((n, 10)).astype("float32")
    y = rng.randint(0, 4, n).astype("float32")
    return io.NDArrayIter(x, y, batch_size=bs, shuffle=False)


def _fit(mod, ckpt=None, n=128, num_epoch=2, resume=False, period=4):
    mod.fit(_data(n=n), num_epoch=num_epoch, optimizer="sgd",
            optimizer_params={"learning_rate": 0.05}, eval_metric="acc",
            initializer=mx.initializer.Xavier(),
            checkpoint_dir=ckpt, checkpoint_period=period, resume=resume)
    return mod


def _sha(mod):
    import hashlib
    args, auxs = mod.get_params()
    h = hashlib.sha256()
    for k in sorted(args):
        h.update(args[k].asnumpy().tobytes())
    for k in sorted(auxs):
        h.update(auxs[k].asnumpy().tobytes())
    return h.hexdigest()


# -- in-graph health word ------------------------------------------------------

def test_guardian_observes_every_step_without_fault():
    mod = _fit(_model())
    g = mod._guardian
    assert g is not None
    st = g.stats()
    assert st["steps_observed"] == 32          # 128/8 batches x 2 epochs
    assert st["skips"] == st["spikes"] == st["rollbacks"] == 0
    fs = mod._fused_step
    assert fs is not None and not fs.broken and fs._guard


def test_skip_batch_deterministic(fast_guardian):
    def run():
        faults.configure("seed=7;grad.nonfinite:error(at=5)")
        mod = _fit(_model())
        st = mod._guardian.stats()
        faults.clear()
        return _sha(mod), st

    sha1, st1 = run()
    sha2, st2 = run()
    assert st1["skips"] == 1 and st1["injected_nonfinite"] == 1
    assert st1["quarantined"] == 1
    assert sha1 == sha2
    # the update really was refused: every parameter stays finite
    faults.configure("seed=7;grad.nonfinite:error(at=5)")
    mod = _fit(_model())
    for name, arr in mod.get_params()[0].items():
        assert np.isfinite(arr.asnumpy()).all(), name


def test_nan_batch_guardian_on_vs_off(monkeypatch):
    """The contrast claim: a NaN batch without the guardian poisons the
    parameters; with it (default) the update is refused and params stay
    finite."""
    def run_with_nan_batch():
        mod = _model()
        it = _data(n=32)
        batch = next(iter(it))
        bad = io.DataBatch(
            data=[mx.nd.array(np.full((8, 10), np.nan, np.float32))],
            label=batch.label, pad=0, provide_data=batch.provide_data,
            provide_label=batch.provide_label)
        mod.fit(_NanIter(it, bad), num_epoch=1, optimizer="sgd",
                optimizer_params={"learning_rate": 0.05},
                eval_metric="acc", initializer=mx.initializer.Xavier())
        return [a.asnumpy()
                for a in mod.get_params()[0].values()]

    class _NanIter(io.DataIter):
        def __init__(self, inner, bad):
            super().__init__(inner.batch_size)
            self._inner, self._bad, self._i = inner, bad, 0

        @property
        def provide_data(self):
            return self._inner.provide_data

        @property
        def provide_label(self):
            return self._inner.provide_label

        def reset(self):
            self._inner.reset()
            self._i = 0

        def next(self):
            self._i += 1
            nxt = self._inner.next()
            return self._bad if self._i == 2 else nxt

    vals_on = run_with_nan_batch()
    assert all(np.isfinite(v).all() for v in vals_on)
    monkeypatch.setenv("MXNET_GUARDIAN", "0")
    vals_off = run_with_nan_batch()
    assert not all(np.isfinite(v).all() for v in vals_off)


def test_guarded_matches_unguarded_numerics(monkeypatch):
    """The health word + conditional update must not change healthy
    training: guardian on vs off, same seed, bit-identical params."""
    sha_on = _sha(_fit(_model()))
    monkeypatch.setenv("MXNET_GUARDIAN", "0")
    sha_off = _sha(_fit(_model()))
    assert sha_on == sha_off


# -- the poll lags by one dispatch ---------------------------------------------

def _dispatch(g, k, nbatch0, ok=None):
    """What the fused step and the fit loop do around one dispatch of k
    steps that starts at batch `nbatch0` of epoch 0."""
    g.step_multipliers(k)
    ok = np.ones(k, np.float32) if ok is None else np.asarray(ok, np.float32)
    sig = np.full(k, 0.01, np.float32)
    g.record_health(k, ok if k > 1 else ok[0], sig if k > 1 else sig[0])
    g.tag(0, nbatch0)


def _judged(g, monkeypatch):
    """Every poll's tokens as [(pos, step)], in the order judged."""
    out = []
    classify = g._classify

    def spy(raw):
        out.append([(pos, step) for pos, step, _, _ in raw])
        return classify(raw)

    monkeypatch.setattr(g, "_classify", spy)
    return out


@pytest.mark.parametrize("k,interval", [(8, 8), (1, 4)],
                         ids=["block", "per-step"])
def test_unforced_poll_leaves_newest_dispatch_in_flight(monkeypatch, k,
                                                        interval):
    g = TrainingGuardian(interval=interval, window=4)
    judged = _judged(g, monkeypatch)
    per_interval = interval // k         # dispatches that fill an interval
    for i in range(per_interval):
        _dispatch(g, k, i * k)
        g.maybe_poll((i + 1) * k)
    assert judged == []                  # nothing OLDER than the newest is due
    _dispatch(g, k, interval)
    g.maybe_poll(interval + k)
    assert judged == [[((0, j), j + 1) for j in range(interval)]]
    (kept,) = g._pending
    assert kept["k"] == k and kept["pos"] == (0, interval)
    st = g.stats()
    assert st["polls"] == st["polls_lagged"] == 1
    assert st["steps_in_flight_max"] == k
    # forced: the dispatch in flight too, numbered and placed after the rest
    g.maybe_poll(interval + k, force=True)
    assert judged[1] == [((0, j), j + 1)
                         for j in range(interval, interval + k)]
    assert g._pending == [] and g.stats()["polls_lagged"] == 1
    g.maybe_poll(interval + k, force=True)       # nothing pending: no poll
    assert g.stats()["polls"] == 2
    # forced with two dispatches pending: both, in step order
    done = interval + k
    for i in range(2):
        _dispatch(g, k, done + i * k)
    g.maybe_poll(done + 2 * k, force=True)
    assert judged[2] == [((0, j), j + 1) for j in range(done, done + 2 * k)]
    assert g._pending == []


def test_retained_entry_still_takes_its_tag(monkeypatch):
    """A poll between a dispatch and its `tag` (a guardian driven outside
    the fit loop) keeps the newest entry untagged, and the next `tag`
    still places it."""
    g = TrainingGuardian(interval=8, window=4)
    judged = _judged(g, monkeypatch)
    for _ in range(2):
        g.step_multipliers(8)
        g.record_health(8, np.ones(8, np.float32), np.ones(8, np.float32))
    g.maybe_poll(16)
    assert judged == [[(None, j + 1) for j in range(8)]]
    g.tag(0, 8)
    assert g._pending[0]["pos"] == (0, 8) and g._untagged == 0


def test_fit_never_waits_for_the_newest_dispatch(monkeypatch):
    """Through a whole fit no unforced poll asks `jax.device_get` for the
    arrays of the dispatch in flight; every forced one does.  A clean
    run's `polls_lagged` counts exactly its unforced polls."""
    import jax
    now = {}
    gathers = []        # (forced, asked for the newest dispatch's arrays)
    poll, device_get = TrainingGuardian.maybe_poll, jax.device_get

    def spy_poll(self, gstep, force=False):
        now.update(force=force, newest=self._pending[-1]
                   if self._pending else None)
        try:
            return poll(self, gstep, force=force)
        finally:
            now.clear()

    def spy_get(x):
        if now:
            newest = now["newest"]
            gathers.append((now["force"], any(
                leaf is newest["ok"] or leaf is newest["sig"] for leaf in x)))
        return device_get(x)

    monkeypatch.setattr(TrainingGuardian, "maybe_poll", spy_poll)
    monkeypatch.setattr(jax, "device_get", spy_get)
    mod = _fit(_model(), n=256)                  # 4 blocks of 8 an epoch
    unforced = [asked for forced, asked in gathers if not forced]
    assert len(unforced) == 6 and not any(unforced)
    assert [asked for forced, asked in gathers if forced] == [True, True]
    st = mod._guardian.stats()
    assert st["polls"] == 8 and st["polls_lagged"] == len(unforced)
    assert st["steps_in_flight_max"] == 8 and st["steps_observed"] == 64


def test_nonfinite_quarantined_at_true_position_one_poll_later(tmp_path,
                                                               monkeypatch):
    """Step 5 (epoch 0, batch 4) is refused in-graph when it runs; its
    quarantine line is written once block 2 is dispatched, by the poll
    that judges block 1, and names the step's own position."""
    monkeypatch.setenv("MXNET_GUARDIAN_QUARANTINE", str(tmp_path / "q.jsonl"))
    seen = []
    quarantine = TrainingGuardian._quarantine

    def spy(self, pos, step, reason, signal):
        seen.append((self._gstep, pos, step, reason))
        return quarantine(self, pos, step, reason, signal)

    monkeypatch.setattr(TrainingGuardian, "_quarantine", spy)
    faults.configure("seed=7;grad.nonfinite:error(at=5)")
    mod = _fit(_model(), n=256, num_epoch=1)
    assert seen == [(16, (0, 4), 5, "nonfinite")]
    (entry,) = QuarantineLog(str(tmp_path / "q.jsonl")).load()
    assert (entry["epoch"], entry["nbatch"], entry["step"]) == (0, 4, 5)
    assert mod._guardian.stats()["skips"] == 1


# -- rollback ------------------------------------------------------------------

def _spike_rollback(tmp_path, n, period, at):
    """A run with a `loss.spike` injected at step `at`, and the clean
    reference over the same schedule with the same quarantine file."""
    ck_a = str(tmp_path / "ck-spike")
    ck_b = str(tmp_path / "ck-ref")
    # warm the scan AND 1-step programs: the post-rollback resume trains
    # a partial block (the quarantine break), and the zero-compile claim
    # below covers recovery, not first-of-process cold compiles
    _fit(_model(), n=128, num_epoch=1)
    os.environ["MXNET_FUSED_STEP_BLOCK"] = "1"
    try:
        _fit(_model(), n=32, num_epoch=1)
    finally:
        os.environ.pop("MXNET_FUSED_STEP_BLOCK", None)

    faults.configure(f"seed=7;loss.spike:error(at={at})")
    c0 = mxcompile.stats()["counters"]["compiles"]
    mod = _fit(_model(), ck_a, n=n, period=period)
    st = mod._guardian.stats()
    compiles_during_recovery = mxcompile.stats()["counters"]["compiles"] - c0
    faults.clear()
    assert st["rollbacks"] == 1 and st["spikes"] == 1
    assert st["quarantined"] >= 1
    assert compiles_during_recovery == 0

    # clean reference: same schedule, no fault, same quarantined window
    os.makedirs(ck_b)
    q = (tmp_path / "ck-spike" / "quarantine.jsonl").read_text()
    (tmp_path / "ck-ref" / "quarantine.jsonl").write_text(q)
    ref = _fit(_model(), ck_b, n=n, period=period)
    assert _sha(mod) == _sha(ref)
    assert ref._guardian.stats()["rollbacks"] == 0
    return mod


def test_spike_rollback_bit_identical(tmp_path, fast_guardian):
    _spike_rollback(tmp_path, n=128, period=4, at=10)


def test_spike_in_lagged_block_rollback_bit_identical(tmp_path, monkeypatch,
                                                      fast_guardian):
    """Snapshots 16 steps apart leave the polls of blocks 3 and 4 unforced:
    the spike at step 20 (block 3) is diagnosed once block 4 is dispatched,
    with the last good step a poll right after block 3 would name; block 4
    is dropped unjudged, so its positions are not quarantined and the
    replay trains on them again."""
    polls = []
    ladder = TrainingGuardian._apply_ladder

    def spy(self, agreed, tokens, gstep):
        polls.append((self._gstep, [t[1] for t in tokens]))
        return ladder(self, agreed, tokens, gstep)

    monkeypatch.setattr(TrainingGuardian, "_apply_ladder", spy)
    mod = _spike_rollback(tmp_path, n=256, period=16, at=20)
    dispatched, judged = next(p for p in polls if 20 in p[1])
    assert judged == list(range(17, 25)) and dispatched == 32
    g = mod._guardian
    assert g.last_rollback_window == (20, 20)      # last good step 19
    entries = QuarantineLog(g.quarantine.path).load()
    assert sorted((e["epoch"], e["nbatch"]) for e in entries) == \
        [(0, nb) for nb in range(19, 24)]
    assert {e["reason"] for e in entries} == {"loss-spike"}


def test_health_stamp_in_manifest(tmp_path):
    from incubator_mxnet_tpu import checkpoint as ckpt
    mod = _fit(_model(), str(tmp_path / "ck"))
    path = ckpt.latest(str(tmp_path / "ck"))
    manifest = ckpt.manifest.read_manifest(path)
    health = manifest["meta"]["health"]
    assert health["status"] == "healthy"
    assert health["rollbacks"] == 0


def test_latest_healthy_selection(tmp_path):
    from incubator_mxnet_tpu import checkpoint as ckpt
    root = str(tmp_path / "ck")
    for step, status in ((4, "healthy"), (8, "healthy"), (12, "suspect")):
        mgr = ckpt.CheckpointManager(root, async_snapshots=False)
        mgr.snapshot(arrays={"arg:w": np.zeros(2, np.float32)}, step=step,
                     meta={"health": {"status": status}})
        mgr.close()
    assert ckpt.latest(root).endswith("%010d" % 12)
    assert ckpt.latest_healthy(root).endswith("%010d" % 8)
    assert ckpt.latest_healthy(root, max_step=7).endswith("%010d" % 4)
    assert ckpt.latest_healthy(root, max_step=3) is None


def test_rollback_without_checkpoint_dir_does_not_raise(monkeypatch,
                                                        fast_guardian):
    """No checkpoint_dir -> no rollback rung: the spike is reported as
    an unrecoverable finding and training continues."""
    monkeypatch.setenv("MXNET_GUARDIAN_MAX_FAILURES", "100")
    faults.configure("seed=7;loss.spike:error(at=10)")
    mod = _fit(_model())
    st = mod._guardian.stats()
    assert st["spikes"] >= 1 and st["rollbacks"] == 0
    codes = {f.code for f in analysis.runtime_report().findings}
    assert "spike-unrecoverable" in codes


# -- divergence budget ---------------------------------------------------------

def test_divergence_budget_names_step_and_shard(monkeypatch,
                                                fast_guardian):
    monkeypatch.setenv("MXNET_GUARDIAN_MAX_FAILURES", "2")
    faults.configure("seed=7;grad.nonfinite:error(at=3-12)")
    with pytest.raises(TrainingDivergedError) as exc:
        _fit(_model())
    err = exc.value
    assert err.step > 0
    assert "ndarray[" in str(err)          # shard attribution
    assert "MXNET_GUARDIAN_MAX_FAILURES" in str(err)


def test_rollback_budget_escalates(tmp_path, monkeypatch, fast_guardian):
    monkeypatch.setenv("MXNET_GUARDIAN_MAX_ROLLBACKS", "0")
    faults.configure("seed=7;loss.spike:error(at=10)")
    with pytest.raises(TrainingDivergedError, match="rollback"):
        _fit(_model(), str(tmp_path / "ck"))


# -- quarantine ----------------------------------------------------------------

def test_quarantine_skipped_on_resume(tmp_path, fast_guardian):
    ck = str(tmp_path / "ck")
    faults.configure("seed=7;grad.nonfinite:error(at=5)")
    mod = _fit(_model(), ck, num_epoch=1)
    faults.clear()
    entries = QuarantineLog(os.path.join(ck, "quarantine.jsonl")).load()
    assert len(entries) == 1 and entries[0]["reason"] == "nonfinite"
    pos = (entries[0]["epoch"], entries[0]["nbatch"])
    # resume for a second epoch: the guardian loads the quarantine and
    # the position is skip-listed from the start
    mod2 = _fit(_model(), ck, num_epoch=2, resume=True)
    g = mod2._guardian
    assert g.should_skip(*pos)
    assert g.stats()["skips"] == 0             # no new skips needed


def test_quarantine_log_multiprocess_format(tmp_path):
    log = QuarantineLog(str(tmp_path / "q.jsonl"))
    log.append(reason="nonfinite", epoch=0, nbatch=3, step=4)
    log.append(reason="corrupt_record", source="x.rec", record=17)
    log.close()
    lines = (tmp_path / "q.jsonl").read_text().splitlines()
    assert len(lines) == 2
    assert all("pid" in json.loads(l) for l in lines)
    log2 = QuarantineLog(str(tmp_path / "q.jsonl"))
    assert log2.batch_positions() == {(0, 3)}
    assert log2.records("x.rec") == {17}


# -- multi-worker agreement ----------------------------------------------------

class _StubKV:
    """kvstore-shaped shared store: push sums, pull reads (the dist
    server's aggregation contract for the guardian's health key)."""

    num_workers = 2

    def __init__(self, store):
        self._store = store

    def init(self, key, value):
        self._store.setdefault(key, np.zeros_like(value.asnumpy()))

    def push(self, key, value):
        self._store[key] = self._store[key] + value.asnumpy()

    def pull(self, key, out):
        from incubator_mxnet_tpu import nd
        out._set_data(nd.array(self._store[key])._data)


def test_multi_worker_agreement():
    store = {}
    g_bad = TrainingGuardian(interval=4, window=4)
    g_ok = TrainingGuardian(interval=4, window=4)
    g_bad._wire_kvstore(_StubKV(store))
    g_ok._wire_kvstore(_StubKV(store))
    # worker A diagnosed a spike at step 9; worker B saw a clean window
    agreed_bad = g_bad._agree(np.asarray([0, 1, 9], np.float64))
    agreed_ok = g_ok._agree(np.asarray([0, 0, 0], np.float64))
    assert agreed_bad[1] >= 1 and agreed_ok[1] >= 1
    assert agreed_ok[2] == 9                   # adopts the peer's step
    assert agreed_bad[2] == 9
    # the store SUMS across polls: a later clean window must not replay
    # the old verdict (decisions are taken on deltas)
    again = g_ok._agree(np.asarray([0, 0, 0], np.float64))
    assert again[0] == 0 and again[1] == 0


def test_multi_worker_agreement_lagged_polls_pair_up():
    """Two workers lag alike: every poll of one meets a poll of the other
    over the same steps, so their all-reduces pair up one for one, and the
    forced drain at the end leaves neither with a dispatch in flight."""
    store = {}
    calls = {"bad": 0, "ok": 0}
    workers = {name: TrainingGuardian(interval=8, window=4)
               for name in calls}
    for name, g in workers.items():
        g._wire_kvstore(_StubKV(store))

        def counted(vec, name=name, allreduce=g._allreduce):
            calls[name] += 1
            return allreduce(vec)

        g._allreduce = counted
    for block in range(3):
        for name, g in workers.items():
            # worker "bad" alone saw a non-finite step, in the second block
            ok = np.ones(8, np.float32)
            ok[3] = 0.0 if (name, block) == ("bad", 1) else 1.0
            _dispatch(g, 8, block * 8, ok=ok)
            g.maybe_poll((block + 1) * 8)
        assert calls["bad"] == calls["ok"] == block   # first block: none yet
    # the second block's verdict came with the third in flight, on both sides
    assert workers["bad"].stats()["skips"] == 1
    assert workers["ok"].stats()["consecutive_failures"] == 1
    assert workers["ok"].stats()["skips"] == 0
    for g in workers.values():
        g.maybe_poll(24, force=True)
        assert g._pending == [] and g.stats()["polls_lagged"] == 2
    assert calls == {"bad": 3, "ok": 3}


def test_agreement_degrades_to_local():
    g = TrainingGuardian(interval=4, window=4)

    def broken(vec):
        raise ConnectionError("store down")

    g._allreduce = broken
    local = np.asarray([1, 0, 0], np.float64)
    assert (g._agree(local) == local).all()
    assert g.stats()["sync_degraded"] == 1


# -- recordio corruption tolerance ---------------------------------------------

def _write_rec(path, payloads):
    w = recordio.MXRecordIO(str(path), "w")
    for p in payloads:
        w.write(p)
    w.close()


def test_recordio_torn_tail_skips_not_raises(tmp_path):
    rec = tmp_path / "t.rec"
    _write_rec(rec, [b"a" * 40, b"b" * 40, b"c" * 40])
    raw = rec.read_bytes()
    rec.write_bytes(raw[:-25])                 # torn mid-payload
    r = recordio.MXRecordIO(str(rec), "r")
    assert r.read() == b"a" * 40
    assert r.read() == b"b" * 40
    assert r.read() is None                    # torn tail -> EOF, no raise
    assert r.corrupt_records == 1
    r.close()


def test_recordio_short_header_tail(tmp_path):
    rec = tmp_path / "h.rec"
    _write_rec(rec, [b"x" * 16])
    rec.write_bytes(rec.read_bytes() + b"\x0a\xd7")   # 2 stray bytes
    r = recordio.MXRecordIO(str(rec), "r")
    assert r.read() == b"x" * 16
    assert r.read() is None
    assert r.corrupt_records == 1
    r.close()


def test_recordio_magic_mismatch_resyncs(tmp_path):
    rec = tmp_path / "m.rec"
    _write_rec(rec, [b"a" * 40, b"b" * 40, b"c" * 40])
    raw = bytearray(rec.read_bytes())
    raw[48] ^= 0xFF                            # damage record 2's magic
    rec.write_bytes(bytes(raw))
    r = recordio.MXRecordIO(str(rec), "r")
    got = []
    while True:
        rec_bytes = r.read()
        if rec_bytes is None:
            break
        got.append(rec_bytes)
    assert b"a" * 40 in got                    # before the damage
    assert b"c" * 40 in got                    # resynced past it
    assert r.corrupt_records >= 1
    r.close()


def test_recordio_quarantine_feed(tmp_path):
    rec = tmp_path / "q.rec"
    _write_rec(rec, [b"a" * 40])
    rec.write_bytes(rec.read_bytes()[:-20])
    log = QuarantineLog(str(tmp_path / "q.jsonl"))
    r = recordio.MXRecordIO(str(rec), "r")
    r.set_quarantine(log)
    assert r.read() is None
    r.close()
    entries = log.load()
    assert entries and entries[0]["reason"] == "corrupt_record"
    assert entries[0]["source"] == str(rec)


def test_indexed_read_never_returns_wrong_record(tmp_path):
    """`read_idx` must not leak the resync: a damaged record returns
    None (and quarantines its id) rather than the NEXT record's payload
    — a misaligned sample/label pair would be silent data corruption."""
    rec = tmp_path / "ix.rec"
    w = recordio.MXIndexedRecordIO(str(tmp_path / "ix.idx"), str(rec), "w")
    for i in range(3):
        w.write_idx(i, bytes([65 + i]) * 40)
    w.close()
    raw = bytearray(rec.read_bytes())
    raw[48] ^= 0xFF                            # record 1's magic
    rec.write_bytes(bytes(raw))
    log = QuarantineLog(str(tmp_path / "q.jsonl"))
    r = recordio.MXIndexedRecordIO(str(tmp_path / "ix.idx"), str(rec), "r")
    r.set_quarantine(log)
    assert r.read_idx(0) == b"A" * 40
    assert r.read_idx(1) is None               # damaged: NOT record 2
    assert r.read_idx(2) == b"C" * 40
    r.close()
    assert 1 in log.records(str(rec))


def test_index_records_tolerant(tmp_path):
    from incubator_mxnet_tpu.image import _index_records_tolerant
    rec = tmp_path / "i.rec"
    _write_rec(rec, [b"a" * 40, b"b" * 40, b"c" * 40])
    raw = rec.read_bytes()
    records, corrupt = _index_records_tolerant(raw)
    assert len(records) == 3 and corrupt == 0
    records, corrupt = _index_records_tolerant(raw[:-25])
    assert len(records) == 2 and corrupt == 1


# -- the corrupt fault kind ----------------------------------------------------

def test_corrupt_kind_fires_through_mutate_only():
    faults.configure("seed=5;io.corrupt_record:corrupt(at=2)")
    payload = bytes(range(64)) * 4
    # fire() ignores corrupt clauses entirely (no payload to damage)
    faults.fire("io.corrupt_record")
    assert faults.trace() == []
    a = faults.mutate("io.corrupt_record", payload)
    b = faults.mutate("io.corrupt_record", payload)
    assert a == payload and b != payload       # fires on the 2nd mutate
    assert len(b) == len(payload)
    assert faults.trace()[-1]["kind"] == "corrupt"
    # deterministic: the same seeded schedule flips the same bytes
    faults.reset()
    faults.mutate("io.corrupt_record", payload)
    assert faults.mutate("io.corrupt_record", payload) == b


def test_corrupt_kind_args():
    faults.configure("seed=5;io.corrupt_record:corrupt(at=1,bytes=1,"
                     "offset=0)")
    out = faults.mutate("io.corrupt_record", b"\x00" * 8)
    assert out != b"\x00" * 8
    assert out[1:] == b"\x00" * 7              # only byte 0 flipped


def test_image_iter_corrupt_record_quarantined(tmp_path):
    cv2 = pytest.importorskip("cv2")
    from incubator_mxnet_tpu.image import ImageRecordIterImpl
    rec = str(tmp_path / "c.rec")
    rng = np.random.RandomState(0)
    w = recordio.MXRecordIO(rec, "w")
    for i in range(12):
        ok, enc = cv2.imencode(
            ".png", rng.randint(0, 255, (40, 40, 3), dtype=np.uint8))
        w.write(recordio.pack(recordio.IRHeader(0, float(i), i, 0),
                              enc.tobytes()))
    w.close()
    log = QuarantineLog(str(tmp_path / "q.jsonl"))
    # record= targeting: deterministic under the threaded batch builders
    faults.configure("seed=6;io.corrupt_record:corrupt(record=5)")
    it = ImageRecordIterImpl(path_imgrec=rec, data_shape=(3, 32, 32),
                             batch_size=4, preprocess_threads=2)
    it.set_quarantine(log)
    n = sum(b.data[0].shape[0] - b.pad for b in it)
    assert n == 12 and it.corrupt_records == 1
    it.close()
    faults.clear()
    bad = {e["record"] for e in log.load() if e.get("record") is not None}
    assert bad == {5}
    # resume: the quarantined record is dropped from the epoch order
    it2 = ImageRecordIterImpl(path_imgrec=rec, data_shape=(3, 32, 32),
                              batch_size=4, preprocess_threads=2)
    it2.apply_quarantine(log.load())
    labels = []
    for b in it2:
        labels.extend(
            b.label[0].asnumpy()[:b.data[0].shape[0] - b.pad].tolist())
    it2.close()
    assert len(labels) == 11
    assert not any(float(r) in labels for r in bad)
    assert it2.corrupt_records == 0


# -- observability -------------------------------------------------------------

def test_guardian_events_in_runtime_report(fast_guardian):
    faults.configure("seed=7;grad.nonfinite:error(at=5)")
    _fit(_model())
    report = analysis.runtime_report()
    codes = {f.code for f in report.findings}
    assert "skip-batch" in codes
    analysis.reset_runtime()
    codes = {f.code for f in analysis.runtime_report().findings}
    assert "skip-batch" not in codes


def test_guardian_events_in_fault_trace(fast_guardian):
    faults.configure("seed=7;grad.nonfinite:error(at=5)")
    _fit(_model())
    events = [e.get("event") for e in faults.trace()]
    assert "skip-batch" in events and "quarantine" in events


# -- config / lint -------------------------------------------------------------

def test_guardian_knobs_registered():
    for knob in ("MXNET_GUARDIAN", "MXNET_GUARDIAN_INTERVAL",
                 "MXNET_GUARDIAN_SPIKE_WINDOW", "MXNET_GUARDIAN_SPIKE_K",
                 "MXNET_GUARDIAN_MAX_FAILURES",
                 "MXNET_GUARDIAN_MAX_ROLLBACKS",
                 "MXNET_GUARDIAN_QUARANTINE"):
        assert knob in config.KNOBS, knob
        assert config.KNOBS[knob][2] == "honored"
    assert config.get("MXNET_GUARDIAN_INTERVAL") >= 1


def test_nan_swallow_lint():
    bad = (
        "for epoch in range(10):\n"
        "    for batch in data:\n"
        "        try:\n"
        "            mod.fit_step(batch, metric)\n"
        "        except Exception:\n"
        "            continue\n")
    codes = [f.code for f in analysis.check_source(bad).findings]
    assert "nan-swallow" in codes
    bad2 = (
        "while True:\n"
        "    try:\n"
        "        trainer.step(batch_size)\n"
        "    except FloatingPointError:\n"
        "        if np.isnan(float(loss.asnumpy())):\n"
        "            pass\n")
    codes = [f.code for f in analysis.check_source(bad2).findings]
    assert "nan-swallow" in codes
    good = (
        "try:\n"
        "    mod.fit(it, num_epoch=2)\n"
        "except TrainingDivergedError:\n"
        "    raise\n")
    assert "nan-swallow" not in [
        f.code for f in analysis.check_source(good).findings]
    suppressed = (
        "try:\n"
        "    mod.fit_step(batch, metric)\n"
        "except Exception:  # mxlint: disable=nan-swallow\n"
        "    continue_flag = True\n")
    assert "nan-swallow" not in [
        f.code for f in analysis.check_source(suppressed).findings]
