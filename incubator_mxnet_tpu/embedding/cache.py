"""Device-resident hot-row cache for sharded embedding tables.

A recommender's id traffic is power-law: a small hot set covers most
lookups.  The cache pins up to `capacity` rows in ONE device buffer
``(capacity, dim)`` and serves hits with a batched device gather — the
steady-state lookup for hot ids never leaves HBM and never touches the
parameter servers.  Misses are pulled from their shards in one batch,
scattered into LRU-evicted slots, then the whole request is gathered.

Program-cache discipline: the gather and the scatter are TWO
`cached_jit` programs.  The scatter donates the cache buffer (the old
buffer dies the moment the new one exists — no 2x cache HBM spike), and
both pad their id axis to the next power of two so the signature set is
O(log capacity) and the steady state (fixed batch, all hits) replays one
executable with ZERO recompiles
(tests/test_embedding.py::test_cache_steady_state_has_zero_recompiles).
"""
from __future__ import annotations

from collections import OrderedDict

import numpy as np

from .. import config as _config
from ..analysis import locks as _locks
from ..compile.program import cached_jit


def _pad_pow2(n):
    return 1 if n <= 1 else 1 << (int(n - 1).bit_length())


def _gather(buf, slots):
    return buf[slots]


def _scatter(buf, slots, rows):
    return buf.at[slots].set(rows)


class HotRowCache:
    """LRU over row ids; one device buffer, batched gather/scatter."""

    def __init__(self, dim, capacity=None, dtype="float32", name="embed"):
        if capacity is None:
            capacity = int(_config.get("MXNET_EMBED_CACHE_ROWS"))
        self.capacity = int(capacity)
        self.dim = int(dim)
        self.dtype = np.dtype(dtype)
        self._lock = _locks.make_lock("embedding.cache")
        # id -> slot, most-recently-used LAST (OrderedDict move_to_end)
        self._slot = OrderedDict()
        self._free = list(range(self.capacity))
        self._buf = None           # device (capacity, dim), built lazily
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self._gather = cached_jit(_gather, label=f"{name}.cache.gather")
        # donation: the pre-scatter buffer is dead the moment the updated
        # one exists — without it the fill path holds 2x cache HBM
        self._scatter = cached_jit(_scatter, donate_argnums=(0,),
                                   label=f"{name}.cache.scatter")

    # -- stats ----------------------------------------------------------------
    # scraped through the owning table's `embedding.<name>` producer
    # (ShardedEmbedding.stats() nests this dict under "cache")
    def stats(self):  # mxlint: disable=untracked-stats
        with self._lock:
            total = self.hits + self.misses
            return {"capacity": self.capacity, "rows": len(self._slot),
                    "hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions,
                    "hit_rate": self.hits / total if total else 0.0}

    def program_count(self):
        """Distinct compiled signatures across both cache programs (the
        zero-steady-state-recompile certification reads this)."""
        return (len(self._gather.signatures())
                + len(self._scatter.signatures()))

    # -- internals ------------------------------------------------------------
    def _ensure_buf(self):
        if self._buf is None:
            import jax.numpy as jnp
            self._buf = jnp.zeros((self.capacity, self.dim),
                                  dtype=self.dtype)

    def _take_slots(self, n):
        """Allocate n slots, evicting LRU rows as needed (lock held)."""
        slots = []
        while len(slots) < n:
            if self._free:
                slots.append(self._free.pop())
            else:
                _evicted_id, slot = self._slot.popitem(last=False)
                self.evictions += 1
                slots.append(slot)
        return slots

    # -- API ------------------------------------------------------------------
    def lookup(self, ids, pull_fn):
        """Rows for ``ids`` (np int array) as ONE device array [len, dim].

        Hits gather straight from the device buffer; the unique missing
        ids go through ``pull_fn(miss_ids) -> np [k, dim]`` (the sharded
        pull), are scattered into LRU slots, and the full request then
        gathers.  Returns (device_rows, n_hits, n_misses).  Under heavy
        cross-thread eviction churn the retry is bounded: after a few
        rounds the batch is served uncached (host rows straight from
        ``pull_fn``) rather than hammering the parameter servers."""
        ids = np.asarray(ids, dtype=np.int64).ravel()
        id_list = ids.tolist()
        uniq = list(dict.fromkeys(id_list))
        # guard on the WHOLE batch's distinct ids, not just the misses:
        # when the batch itself cannot fit, the insert would evict the
        # batch's own resident rows, the post-insert check would fail,
        # and the re-pull loop would never converge
        if len(uniq) > self.capacity:
            raise ValueError(
                f"hot-row cache capacity {self.capacity} cannot "
                f"hold the {len(uniq)} distinct rows of one "
                "lookup — raise MXNET_EMBED_CACHE_ROWS past the "
                "per-batch distinct id count")
        self._ensure_buf()
        for _attempt in range(8):
            with self._lock:
                miss_occ = [i for i in id_list if i not in self._slot]
                miss = list(dict.fromkeys(miss_occ))
                n_miss = len(miss_occ)
                n_hit = len(ids) - n_miss
                # pin this batch's resident rows at the MRU end BEFORE
                # the miss insert: its evictions then only ever take
                # rows outside this batch (capacity >= batch distinct)
                for i in id_list:
                    if i in self._slot:
                        self._slot.move_to_end(i)
            if miss:
                rows = np.asarray(
                    pull_fn(np.asarray(miss, dtype=np.int64)),
                    dtype=self.dtype)
                self.insert(miss, rows)
            with self._lock:
                if any(i not in self._slot for i in id_list):
                    continue   # a concurrent lookup evicted us: re-pull
                self.hits += n_hit
                self.misses += n_miss
                slots = np.fromiter((self._slot[i] for i in id_list),
                                    dtype=np.int32, count=len(ids))
                for i in id_list:
                    self._slot.move_to_end(i)
                # dispatch the gather UNDER the lock: a concurrent
                # insert donates self._buf away, so the validated slots
                # and the buffer they index must be captured atomically
                # or the gather can read re-scattered rows
                return self._gathered(slots, len(ids)), n_hit, n_miss
        # eviction churn won this batch every round: serve it uncached
        # (one last pull, no pinning) instead of retrying unboundedly
        rows = np.asarray(pull_fn(np.asarray(uniq, dtype=np.int64)),
                          dtype=self.dtype).reshape(len(uniq), self.dim)
        pos = {i: j for j, i in enumerate(uniq)}
        with self._lock:
            self.hits += n_hit
            self.misses += n_miss
        return rows[[pos[i] for i in id_list]], n_hit, n_miss

    def _gathered(self, slots, n):
        padded = _pad_pow2(n)
        if padded != n:
            slots = np.concatenate(
                [slots, np.zeros(padded - n, dtype=np.int32)])
        return self._gather(self._buf, slots)[:n]

    def insert(self, ids, rows):
        """Pin rows (np [k, dim]) for ids, evicting LRU entries to fit."""
        ids = [int(i) for i in np.asarray(ids).ravel()]
        rows = np.asarray(rows, dtype=self.dtype).reshape(len(ids),
                                                          self.dim)
        self._ensure_buf()
        with self._lock:
            fresh = [(j, i) for j, i in enumerate(ids)
                     if i not in self._slot]
            # rows already resident just refresh their value in place
            upd_slots = [self._slot[i] for i in ids if i in self._slot]
            upd_rows = [rows[j] for j, i in enumerate(ids)
                        if i in self._slot]
            slots = self._take_slots(len(fresh))
            for (j, i), s in zip(fresh, slots):
                self._slot[i] = s
            all_slots = np.asarray(
                slots + upd_slots, dtype=np.int32)
            all_rows = np.concatenate(
                [rows[[j for j, _ in fresh]].reshape(len(fresh), self.dim),
                 np.asarray(upd_rows, dtype=self.dtype).reshape(
                     len(upd_rows), self.dim)], axis=0)
            n = len(all_slots)
            padded = _pad_pow2(n)
            if padded != n:
                # pad by re-writing the first slot with its own row: the
                # scatter stays shape-stable (O(log capacity) signatures)
                # and the duplicate write is a no-op
                all_slots = np.concatenate(
                    [all_slots,
                     np.full(padded - n, all_slots[0], dtype=np.int32)])
                all_rows = np.concatenate(
                    [all_rows,
                     np.broadcast_to(all_rows[0],
                                     (padded - n, self.dim))], axis=0)
            self._buf = self._scatter(self._buf, all_slots, all_rows)

    def refresh(self, ids, rows):
        """Overwrite the cached copies of whichever ``ids`` are resident
        (a training push's post-update rows); non-resident ids are left
        alone — a push must not PIN rows nobody looked up."""
        ids = np.asarray(ids).ravel()
        rows = np.asarray(rows, dtype=self.dtype).reshape(len(ids),
                                                          self.dim)
        with self._lock:
            at = [j for j, i in enumerate(ids.tolist())
                  if int(i) in self._slot]
        if at:
            self.insert(ids[at], rows[at])

    def invalidate(self, ids):
        """Drop rows (a training push made the cached copies stale)."""
        with self._lock:
            for i in np.asarray(ids).ravel().tolist():
                slot = self._slot.pop(int(i), None)
                if slot is not None:
                    self._free.append(slot)
