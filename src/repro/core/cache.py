"""Hot-bucket caching tier for the distributed hash table (DESIGN.md §8).

The paper's zipfian benches concentrate find traffic on a few hot keys;
coalescing (DESIGN.md §6) collapses duplicates *within* a batch but every
batch still pays the wire round trip. This tier keeps the hot buckets
effectively local (the Storm / Active-Access move): each origin rank holds
a small cache of records it has previously fetched, validated by *version
tags* that are bumped — host-side, zero extra exchanges — whenever a write
could touch the bucket.

Coherence protocol
------------------
* `versions` is a per-(owner, slot) monotonic counter. Every cached entry
  stores the version it observed at fill time; a lookup whose stored
  version no longer matches is a *stale eviction* (counted, entry dropped).
* Writers bump versions through two channels:
  - `on_insert_keys` (the authoritative path): the structure layer calls
    it before ANY insert arm executes — AM insert-or-assign included — and
    it bumps the whole probe window [(start+j) % nslots, j < max_probes]
    of every written key (a conservative superset: the exact claimed slot
    only resolves on-device inside the probe loop).
  - `on_publish` (the precision path): eager concrete publish flips
    (`window.rdma_cas_put_publish` / the unfused FXOR publish) notify the
    cache inside `window.cache_scope`, bumping the exact flipped slot.
    Inside `jax.lax.while_loop` probe bodies the offsets are tracers, so
    this channel degrades to a no-op there — which is exactly why
    `on_insert_keys` is the authoritative channel. Double bumps are
    harmless (versions only need to move, not count).
* Tracer keys on the write path (a jitted insert) bump EVERYTHING
  (`invalidate_all`) — correct, never fast.
* Only POSITIVE entries are cached (records found READY with a matching
  key). Negative caching would require invalidating on every claim; the
  publish-based protocol only has to watch value-visibility events.

Deferred fills
--------------
Fill values come back as device arrays; materializing them at fill time
would serialize the §7 pipeline (staging must never read a device value).
Fills are therefore enqueued with a snapshot of the global `write_tick`
and drained later — immediately when not staging inside a pipeline slot,
opportunistically (only already-`is_ready()` arrays) when staging. A fill
whose snapshot tick no longer matches `write_tick` raced with a writer
and is dropped (conservative: a dropped fill is a future miss, never a
stale hit). A fill that survives the tick check saw no intervening write,
so stamping it with the CURRENT version table is exact.

Storage is per-origin and set-associative (`capacity` entries per origin
in `capacity / ways` sets, vectorized numpy — a lookup is a handful of
fancy-indexing ops, not a Python loop). Associativity matters more here
than in a hardware cache: a single persistent conflict miss makes its
batch non-all-hit forever, and a non-all-hit batch pays the FULL probe
phase loop (exchanges are per phase, not per row) — so two hot keys
sharing a direct-mapped line would erase the entire tier's win. With a
few ways, hot keys coexist; colliding cold keys round-robin-evict each
other, which is the right failure mode for the zipfian workloads this
tier exists for.

The cache object is host state, shared by reference — it is NOT part of
any jit-traced pytree. Coherence is guaranteed for writes issued through
the owning `adaptive.AdaptiveEngine` (or any caller disciplined enough to
call `on_insert_keys` before writing); one cache serves exactly ONE
table (attach a fresh cache per DHashTable).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Tuple

import numpy as np

from .types import host_value

# Tag sentinel for an empty line. Keys are int32; the tag array is int64 so
# no valid key can collide with the sentinel.
_EMPTY_TAG = np.int64(1) << 40


def _is_ready(x) -> bool:
    fn = getattr(x, "is_ready", None)
    return True if fn is None else bool(fn())


@dataclass
class CacheLookup:
    """Host-side result of one batch lookup (all numpy)."""

    hit: np.ndarray        # (P, n) bool — fresh positive entry
    vals: np.ndarray       # (P, n, vw) int32 — zeros where miss
    keys: np.ndarray       # (P, n) int32 — the concrete batch keys
    valid: np.ndarray      # (P, n) bool — the concrete valid mask
    tick: int              # write_tick snapshot at lookup time

    @property
    def miss(self) -> np.ndarray:
        return self.valid & ~self.hit

    @property
    def all_hit(self) -> bool:
        return not bool(self.miss.any())

    @property
    def hit_rate(self) -> float:
        nv = int(self.valid.sum())
        return float(self.hit.sum() / nv) if nv else 0.0


class BucketCache:
    """Per-origin set-associative cache of hot hash-table records with
    publish-bumped version tags (see module docstring for the protocol)."""

    def __init__(self, nranks: int, nslots: int, val_words: int,
                 capacity: int = 4096, max_probes: int = 8, ways: int = 4):
        if capacity & (capacity - 1):
            raise ValueError("capacity must be a power of two")
        if ways & (ways - 1) or not 0 < ways <= capacity:
            raise ValueError("ways must be a power of two <= capacity")
        self.nranks = nranks
        self.nslots = nslots
        self.val_words = val_words
        self.rec_w = 2 + val_words
        self.capacity = capacity
        self.ways = ways
        self.sets = capacity // ways
        self.max_probes = max_probes
        self.enabled = True
        # per-(owner, slot) version counters — the invalidation substrate
        self.versions = np.zeros((nranks, nslots), np.int64)
        # global write counter: deferred-fill race detection
        self.write_tick = 0
        self.epoch = 0                       # invalidate_all generations
        # per-origin (sets, ways) store + round-robin victim clock
        self._tag = np.full((nranks, self.sets, ways), _EMPTY_TAG, np.int64)
        self._owner = np.zeros((nranks, self.sets, ways), np.int32)
        self._slot = np.zeros((nranks, self.sets, ways), np.int32)
        self._ver = np.zeros((nranks, self.sets, ways), np.int64)
        self._val = np.zeros((nranks, self.sets, ways, val_words), np.int32)
        self._clock = np.zeros((nranks, self.sets), np.int64)
        self._pending: List[Tuple] = []
        self.last_hit_rate: Optional[float] = None
        self.counters = {"lookups": 0, "hits": 0, "misses": 0, "fills": 0,
                         "stale_evicted": 0, "invalidations": 0,
                         "fill_drops": 0}

    # -- placement -----------------------------------------------------------
    def _index(self, keys: np.ndarray) -> np.ndarray:
        from .hashtable import hash_mix_np
        return (hash_mix_np(keys) % np.uint32(self.sets)).astype(np.int64)

    def _placement(self, keys: np.ndarray):
        from .hashtable import place_np
        return place_np(self.nranks, self.nslots, keys)

    # -- read path -----------------------------------------------------------
    def lookup(self, keys, valid=None,
               max_stale: int = 0) -> Optional[CacheLookup]:
        """Consult the cache for one (P, n) find batch.

        Returns None when the cache cannot be consulted (disabled, or the
        batch is abstract under jit tracing) — callers fall through to the
        normal engine. Stale entries discovered here are evicted.

        max_stale (DESIGN.md §10 graceful degradation): serve entries
        whose probe-window version lags the authoritative version by at
        most this many publishes. 0 (default) is the §8 bit-exact
        behavior — any version mismatch is a miss. Under faults a reader
        that tolerates bounded staleness keeps answering from the local
        cache while the remote owner is quarantined; entries lagging past
        the tolerance are still evicted."""
        if not self.enabled:
            return None
        k = host_value(keys)
        if k is None:
            return None
        if valid is None:
            v = np.ones(k.shape, bool)
        else:
            v = host_value(valid)
            if v is None:
                return None
            v = v.astype(bool)
        self.drain_fills()
        k = k.astype(np.int32)
        P, n = k.shape
        idx = self._index(k)
        pp = np.arange(P)[:, None]
        line_tag = self._tag[pp, idx]                       # (P, n, W)
        tag_hit_w = (line_tag == k.astype(np.int64)[..., None]) \
            & v[..., None]
        owner = self._owner[pp, idx]
        slot = self._slot[pp, idx]
        lag = self.versions[owner, slot] - self._ver[pp, idx]
        fresh = (lag >= 0) & (lag <= int(max_stale))
        hit_w = tag_hit_w & fresh
        stale_w = tag_hit_w & ~fresh
        if stale_w.any():
            rows, cols, wys = np.nonzero(stale_w)
            self._tag[rows, idx[rows, cols], wys] = _EMPTY_TAG
            self.counters["stale_evicted"] += int(rows.size)
        hit = hit_w.any(-1)
        way = np.argmax(hit_w, axis=-1)                     # (P, n)
        vals = np.where(hit[..., None],
                        self._val[pp, idx, way], 0).astype(np.int32)
        nhit, nvalid = int(hit.sum()), int(v.sum())
        self.counters["lookups"] += 1
        self.counters["hits"] += nhit
        self.counters["misses"] += nvalid - nhit
        self.last_hit_rate = nhit / nvalid if nvalid else 0.0
        return CacheLookup(hit=hit, vals=vals, keys=k, valid=v,
                           tick=self.write_tick)

    # -- fill path -----------------------------------------------------------
    def note_fill(self, look: CacheLookup, slot, found, vals) -> None:
        """Enqueue the device results of the miss subset for caching.

        slot/found/vals are (possibly in-flight) device arrays from the
        probe loop: (P, n) hit slot, (P, n) found mask, (P, n, vw) values.
        Tracers are ignored (nothing to cache at trace time)."""
        import jax
        if any(isinstance(a, jax.core.Tracer) for a in (slot, found, vals)):
            return
        if not look.miss.any():
            return
        self._pending.append((look.tick, look.keys, look.miss, slot, found,
                              vals))
        self.drain_fills()

    def drain_fills(self, force: Optional[bool] = None) -> None:
        """Apply pending fills whose device values are available.

        force=None auto-detects: blocking on the device values is safe
        only OUTSIDE the pipelined engine — not just outside a slot scope
        (staging), but also between submits while any pipeline still holds
        an in-flight window (`window.pipeline_inflight`). A host-side
        drain there would materialize the previous batch's not-yet-forced
        outputs and serialize against exactly the overlap the pipeline
        buys (the PR 6 depth-2 regression); those fills stay queued until
        their arrays turn ready on their own or the stream drains. Pass
        force=True to block explicitly (tests, teardown)."""
        if not self._pending:
            return
        if force is None:
            from . import window as win_mod
            force = (win_mod._CURRENT_SLOT is None
                     and not win_mod.pipeline_inflight())
        keep = []
        for rec in self._pending:
            tick, keys, miss, slot, found, vals = rec
            if tick != self.write_tick:
                # raced with a writer: the read may predate the write
                self.counters["fill_drops"] += 1
                continue
            if not (force or all(_is_ready(a) for a in (slot, found, vals))):
                keep.append(rec)
                continue
            self._apply_fill(keys, miss, np.asarray(slot), np.asarray(found),
                             np.asarray(vals))
        self._pending = keep

    def _apply_fill(self, keys, miss, slot, found, vals) -> None:
        mask = miss & found.astype(bool) & (slot >= 0)
        if not mask.any():
            return
        owner, _ = self._placement(keys)
        rows, cols = np.nonzero(mask)
        idx = self._index(keys)
        ci = idx[rows, cols]
        ow, sl = owner[rows, cols], slot[rows, cols]
        key64 = keys[rows, cols].astype(np.int64)
        fvals = vals[rows, cols]
        # dedupe (origin, key): a key's duplicate rows carry identical
        # records, and distinct per-set entries must get distinct ways
        combo = (rows.astype(np.int64) << 32) | key64
        _, first = np.unique(combo, return_index=True)
        rows, ci, ow, sl = rows[first], ci[first], ow[first], sl[first]
        key64, fvals = key64[first], fvals[first]
        # way choice: the key's existing line if present, else an empty
        # way, else the set's round-robin victim
        line_tags = self._tag[rows, ci]                     # (m, W)
        present = line_tags == key64[:, None]
        empty = line_tags == _EMPTY_TAG
        way = np.where(
            present.any(1), present.argmax(1),
            np.where(empty.any(1), empty.argmax(1),
                     self._clock[rows, ci] % self.ways)).astype(np.int64)
        # distinct keys of one batch landing in one set all saw the
        # PRE-fill line state, so they can pick the same way; rotate the
        # (rare) conflicts onto free ways with a short host loop — only
        # when a conflict actually exists
        tgt = (rows * np.int64(self.sets) + ci) * self.ways + way
        _, cnt = np.unique(tgt, return_counts=True)
        if (cnt > 1).any():
            taken: dict = {}
            for i in range(rows.size):
                used = taken.setdefault((int(rows[i]), int(ci[i])), set())
                w = int(way[i])
                if w in used:
                    pick = None
                    for d in range(1, self.ways):
                        w2 = (w + d) % self.ways
                        if w2 in used:
                            continue
                        if pick is None:
                            pick = w2
                        if empty[i, w2]:
                            pick = w2
                            break
                    if pick is not None:
                        w = pick
                used.add(w)
                way[i] = w
        # no write intervened since the read (tick check), so the current
        # version table IS the version the record was read at
        self._tag[rows, ci, way] = key64
        self._owner[rows, ci, way] = ow
        self._slot[rows, ci, way] = sl
        self._ver[rows, ci, way] = self.versions[ow, sl]
        self._val[rows, ci, way] = fvals
        np.add.at(self._clock, (rows, ci), 1)
        self.counters["fills"] += int(rows.size)
        from . import window as win_mod
        win_mod.log_cache_event("cache_fill", {"rows": int(rows.size)})

    # -- write / invalidation path -------------------------------------------
    def on_insert_keys(self, keys, valid=None,
                       max_probes: Optional[int] = None) -> None:
        """Authoritative pre-write invalidation: bump the probe-window
        versions of every key about to be written (any arm — the AM
        insert-or-assign included). Tracer batches invalidate everything."""
        self.write_tick += 1
        k = host_value(keys)
        if k is None:
            self.invalidate_all(bump_tick=False)
            return
        v = None
        if valid is not None:
            v = host_value(valid)
            if v is None:
                self.invalidate_all(bump_tick=False)
                return
        k = k.astype(np.int32).ravel() if v is None else \
            k.astype(np.int32)[v.astype(bool)].ravel()
        if k.size == 0:
            return
        mp = self.max_probes if max_probes is None else max_probes
        owner, start = self._placement(k)
        window_slots = (start[:, None].astype(np.int64)
                        + np.arange(mp)[None, :]) % self.nslots
        np.add.at(self.versions,
                  (np.repeat(owner, mp), window_slots.ravel()), 1)
        self.counters["invalidations"] += int(k.size)
        from . import window as win_mod
        win_mod.log_cache_event("cache_invalidate",
                                {"keys": int(k.size), "probe_window": mp})

    def on_publish(self, dst, off, valid=None) -> None:
        """Precision invalidation from an eager concrete publish flip:
        bump exactly the flipped slot (off is the flag-word offset, so
        slot = off // rec_w). Tracers no-op — `on_insert_keys` is the
        authoritative channel (see module docstring)."""
        d, o = host_value(dst), host_value(off)
        if d is None or o is None:
            return
        self.write_tick += 1
        if valid is not None:
            v = host_value(valid)
            if v is None:
                self.invalidate_all(bump_tick=False)
                return
            sel = v.astype(bool)
            d, o = d[sel], o[sel]
        slots = (o.astype(np.int64) // self.rec_w) % self.nslots
        if d.size:
            np.add.at(self.versions, (d.ravel(), slots.ravel()), 1)

    def invalidate_all(self, bump_tick: bool = True) -> None:
        """Drop every entry and pending fill (conservative full flush)."""
        if bump_tick:
            self.write_tick += 1
        self.epoch += 1
        self._tag.fill(_EMPTY_TAG)
        self.counters["fill_drops"] += len(self._pending)
        self._pending.clear()
        from . import window as win_mod
        win_mod.log_cache_event("cache_invalidate", {"all": True})

    # -- introspection -------------------------------------------------------
    @property
    def hit_rate(self) -> float:
        h, m = self.counters["hits"], self.counters["misses"]
        return h / (h + m) if h + m else 0.0

    def stats(self) -> dict:
        return {**self.counters, "hit_rate": self.hit_rate,
                "epoch": self.epoch, "write_tick": self.write_tick,
                "pending_fills": len(self._pending),
                "capacity": self.capacity, "ways": self.ways,
                "entries": int((self._tag != _EMPTY_TAG).sum())}
