"""Distributed hash table (open addressing, linear probing) — paper §III-B1.

Slot layout (int32 words):   [ flag | key | val_0 .. val_{vw-1} ]

flag word: low 8 bits = state (EMPTY/RESERVED/READY); bits 8+ = reader count
(the paper uses fetch-and-OR read *bits*; an additive reader count has the
same component cost — one A_FAO — without a static reader limit).

Implementations and their best-case costs (paper Table II):

  insert C_RW (rdma):  probes×A_CAS + W + A_FAO   (claim, write, mark-ready)
  insert C_W  (rdma):  probes×A_CAS + W            (barrier supplies the fence)
  find   C_RW (rdma):  A_FAO + R + A_FAO           (read-lock, get, unlock)
  find   C_R  (rdma):  R                           (bare get of the record)
  insert/find (rpc):   one AM round trip + local probe handler

Ownership: owner = mix(key) % P; probing wraps within the owner's local
table so the RDMA and RPC backends have identical placement semantics.

RPC expressivity note (paper §II-B): the RPC insert handler does
insert-or-assign (update on key match) — free extra control flow in a
handler; the RDMA version is insert-only because CAS can only claim EMPTY
slots. This asymmetry is the paper's expressivity argument made concrete.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import am as am_mod
from . import routing
from . import trace
from . import window as win_mod
from .types import (FLAG_EMPTY, FLAG_READY, FLAG_RESERVED, READ_UNIT,
                    STATE_MASK, Backend, Promise, as_backend)
from .window import (Window, rdma_cas, rdma_cas_put, rdma_cas_put_publish,
                     rdma_fao, rdma_fao_get, rdma_get, rdma_put)

Array = jax.Array


def hash_mix(key: Array) -> Array:
    """Deterministic 32-bit integer mix (xorshift-multiply)."""
    k = key.astype(jnp.uint32)
    k = (k ^ (k >> 16)) * jnp.uint32(0x85EBCA6B)
    k = (k ^ (k >> 13)) * jnp.uint32(0xC2B2AE35)
    return (k ^ (k >> 16)).astype(jnp.uint32)


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["win"], meta_fields=["nslots", "val_words"])
@dataclass
class DHashTable:
    win: Window
    nslots: int      # local slots per rank
    val_words: int

    @property
    def nranks(self) -> int:
        return self.win.nranks

    @property
    def rec_w(self) -> int:
        return 2 + self.val_words


def make_hashtable(nranks: int, nslots: int, val_words: int,
                   sharding=None) -> DHashTable:
    """Empty table of `nslots` records per rank; `sharding` places the
    window's rank rows (see `window.make_window`)."""
    rec_w = 2 + val_words
    return DHashTable(win=win_mod.make_window(nranks, nslots * rec_w,
                                              sharding=sharding),
                      nslots=nslots, val_words=val_words)


def _place(ht: DHashTable, keys: Array) -> Tuple[Array, Array]:
    h = hash_mix(keys)
    owner = (h % jnp.uint32(ht.nranks)).astype(jnp.int32)
    start = ((h // jnp.uint32(ht.nranks)) % jnp.uint32(ht.nslots)).astype(
        jnp.int32)
    return owner, start


def hash_mix_np(keys):
    """Host-side (numpy) mirror of `hash_mix` — THE single numpy copy of
    the xorshift-multiply constants (benchmarks/common.py delegates here;
    bit-equality with the jnp version is pinned by tests)."""
    import numpy as np
    k = np.asarray(keys).astype(np.uint32)
    k = (k ^ (k >> 16)) * np.uint32(0x85EBCA6B)
    k = (k ^ (k >> 13)) * np.uint32(0xC2B2AE35)
    return k ^ (k >> 16)


def place_np(nranks: int, nslots: int, keys):
    """Host-side (numpy) mirror of `_place` — bit-identical owner/start.

    The pipelined front-ends (DESIGN.md §7) use this to compute the skew
    and dedup signals on the Python thread at submit time, so staging
    batch k+1 never reads a device value (which would serialize behind
    batch k's in-flight phases and defeat the overlap). Bit-equality with
    the engine placement is pinned by tests/test_pipeline.py."""
    import numpy as np
    h = hash_mix_np(keys)
    owner = (h % np.uint32(nranks)).astype(np.int32)
    start = ((h // np.uint32(nranks)) % np.uint32(nslots)).astype(np.int32)
    return owner, start


# ---------------------------------------------------------------------------
# RDMA backend
# ---------------------------------------------------------------------------
def insert_rdma(ht: DHashTable, keys: Array, vals: Array,
                promise: Promise = Promise.CRW,
                valid: Optional[Array] = None, max_probes: int = 8,
                fused: bool = True, coalesce: bool = False
                ) -> Tuple[DHashTable, Array, Array]:
    """Batched insert. keys (P, n) int32, vals (P, n, vw) int32.

    Returns (table', success (P,n), probe_count (P,n)). Distinct keys per
    batch assumed (open-addressing insert-only, see module docstring).

    fused=True (default, DESIGN.md §2): one RoutePlan per batch + fused
    claim/write(/publish) descriptors — each probe is ONE request phase and
    the trailing W / A_FAO phases disappear. fused=False keeps the unfused
    per-component phases (probes×A_CAS + W [+ A_FAO]); both paths are
    bit-exact equivalent (tests/test_datastructures.py).

    coalesce=True (DESIGN.md §6): duplicate IDENTICAL [key|val] rows in a
    batch are combined sender-side. With fused=True the whole batch uses
    one CoalescedPlan and a duplicate group claims ONE slot: the
    representative's claim satisfies every duplicate (same record lands in
    the table), so duplicates short-circuit instead of claiming sibling
    slots — wire rows and probe phases collapse toward O(distinct keys).
    Visible results (ok flags, subsequent finds) are conformant with the
    uncoalesced engine; the slot-level table state differs only in that
    duplicate side-copies are elided. With fused=False coalescing is
    phase-local (window-level) and fully bit-exact.
    """
    assert promise in (Promise.CRW, Promise.CW)
    if valid is None:
        valid = jnp.ones(keys.shape, dtype=bool)
    dst, start = _place(ht, keys)
    rec_w, nslots = ht.rec_w, ht.nslots
    claim_to = FLAG_RESERVED if promise == Promise.CRW else FLAG_READY

    if fused:
        payload = jnp.concatenate([keys[..., None], vals], axis=-1)
        if coalesce:
            plan = routing.coalesce_plan(dst, start, match=payload,
                                         valid=valid, cap=keys.shape[1],
                                         role="ht_insert")
            co = plan.co
        else:
            plan = routing.make_plan(dst, valid, cap=keys.shape[1],
                                     role="ht_insert")
            co = None
        flip = int(FLAG_RESERVED) ^ int(FLAG_READY)

        def probe_fused(carry):
            j, win, active, claimed, probes = carry
            slot = (start + j) % nslots
            off = slot * rec_w
            if promise == Promise.CRW:
                old, win = rdma_cas_put_publish(
                    win, dst, off, FLAG_EMPTY, claim_to, off + 1, payload,
                    flip, valid=active, plan=plan)
            else:
                old, win = rdma_cas_put(
                    win, dst, off, FLAG_EMPTY, claim_to, off + 1, payload,
                    valid=active, plan=plan)
            if co is not None:
                # the whole duplicate run adopts its representative's
                # outcome: one claim serves every identical [key|val] row
                old = routing.lead(co, old)
            newly = active & (old == FLAG_EMPTY)
            claimed = jnp.where(newly, slot, claimed)
            probes = probes + active.astype(jnp.int32)
            return j + 1, win, active & ~newly, claimed, probes

        # Adaptive termination: once every op has claimed, the remaining
        # probe phases are identities (all-inactive CAS batches change
        # nothing), so skipping them at runtime is bit-exact. The unfused
        # seed path keeps its fixed trip count.
        claimed0 = jnp.full(keys.shape, -1, dtype=jnp.int32)
        probes0 = jnp.zeros(keys.shape, dtype=jnp.int32)
        loop = ("insert_rdma.fused", promise, coalesce)
        with trace.span("probe_loop"):
            trips, win, active, claimed, probes = jax.lax.while_loop(
                lambda c: (c[0] < max_probes) & c[2].any(),
                trace.per_trip(loop, probe_fused),
                (jnp.int32(0), ht.win, valid, claimed0, probes0))
        trace.count("probe_trips", trips)
        trace.trips(loop, trips)
        success = valid & ~active
        return (DHashTable(win=win, nslots=nslots, val_words=ht.val_words),
                success, probes)

    def probe_phase(j, carry):
        win, active, claimed, probes = carry
        slot = (start + j) % nslots
        off = slot * rec_w
        # coalesce is phase-local here (fresh runs per probe): identical
        # CAS rows dedup on the wire, losers reconstruct bit-exactly
        old, win = rdma_cas(win, dst, off, FLAG_EMPTY, claim_to,
                            valid=active, coalesce=coalesce)
        newly = active & (old == FLAG_EMPTY)
        claimed = jnp.where(newly, slot, claimed)
        probes = probes + active.astype(jnp.int32)
        return win, active & ~newly, claimed, probes

    claimed0 = jnp.full(keys.shape, -1, dtype=jnp.int32)
    probes0 = jnp.zeros(keys.shape, dtype=jnp.int32)
    loop = ("insert_rdma", promise, coalesce)
    win, active, claimed, probes = jax.lax.fori_loop(
        0, max_probes, trace.per_trip(loop, probe_phase),
        (ht.win, valid, claimed0, probes0))
    trace.trips(loop, max_probes)
    success = valid & ~active

    # ONE put phase writes [key | val words] for every claimed op.
    payload = jnp.concatenate([keys[..., None], vals], axis=-1)
    win = rdma_put(win, dst, claimed * rec_w + 1, payload, valid=success)

    if promise == Promise.CRW:
        # Flip RESERVED -> READY without touching reader bits: FXOR(1^2).
        # (python-level xor: staging it under jit would make the int() of
        # the module constants a tracer)
        flip = jnp.full(keys.shape, int(FLAG_RESERVED) ^ int(FLAG_READY),
                        dtype=jnp.int32)
        _, win = rdma_fao(win, dst, claimed * rec_w, flip,
                          win_mod.AmoKind.FXOR, valid=success)
    return (DHashTable(win=win, nslots=nslots, val_words=ht.val_words),
            success, probes)


def find_rdma(ht: DHashTable, keys: Array,
              promise: Promise = Promise.CR,
              valid: Optional[Array] = None, max_probes: int = 8,
              fused: bool = True, coalesce: bool = False,
              cache=None, return_slot: bool = False,
              max_stale: int = 0):
    """Batched find. Returns (table', found (P,n), vals (P,n,vw)).

    C_R : one bare get per probe (flag+key+val in a single R).
    C_RW: read-lock (FAA +unit), get, unlock (FAA -unit) per probe.

    fused=True (default): one RoutePlan per batch; for C_RW the read-lock
    and record gather fuse into one A_FAO_GET request/reply pair, cutting a
    probe from 6 exchanges to 4 (lock+get fused = 2, unlock = 2). The
    gathered flag word may predate later locks in the batch, but the C_RW
    hit test uses the lock's fetched state, so results are bit-exact with
    fused=False.

    coalesce=True (DESIGN.md §6): duplicate-key rows probe ONCE and the
    reply fans out — a zipfian find batch ships O(distinct keys) wire
    rows. Bit-exact: a duplicate group always decides (hit / miss /
    continue) identically, and for C_RW the combined read-lock carries the
    summed reader units whose per-op fetched values are reconstructed
    sender-side.

    cache (DESIGN.md §8): an optional core/cache.BucketCache consulted
    BEFORE planning — only for the fused CR find (CRW must hit the owner
    for its read locks) on concrete batches (cache.lookup returns None
    under jit tracing). Cache hits are answered origin-locally: an
    all-hit batch issues ZERO exchanges, a mixed batch plans only the
    miss subset (bit-identical occupancy, `routing.miss_subset_plan`)
    and the probe loop's fresh results are fed back via
    `cache.note_fill`. Bit-exact by the version protocol: a fresh entry
    is exactly the record the wire would return.

    return_slot=True (fused only, incompatible with `cache`): also return
    the per-row hit slot (-1 for misses) as a fourth output — lets a
    caller that manages its own BucketCache under jit (host lookup + one
    jitted miss-subset step, benchmarks/pipeline_bench.py) feed
    `cache.note_fill` without the eager integrated path."""
    assert promise in (Promise.CRW, Promise.CR)
    if return_slot:
        assert fused and cache is None, \
            "return_slot needs fused=True and an external cache"
    if valid is None:
        valid = jnp.ones(keys.shape, dtype=bool)
    dst, start = _place(ht, keys)
    rec_w, nslots, vw = ht.rec_w, ht.nslots, ht.val_words
    look = None
    if cache is not None and fused and promise == Promise.CR:
        # max_stale > 0 (DESIGN.md §10): bounded-staleness read — cached
        # records at most `max_stale` publishes behind still count as
        # hits, trading freshness for availability under quarantine.
        # The default 0 keeps the §8 bit-exact protocol.
        look = cache.lookup(keys, valid, max_stale=max_stale)
    if look is not None and look.all_hit:
        # every valid row served origin-locally: ZERO exchanges
        win_mod.log_cache_event("cache_hit", {
            "hits": int(look.hit.sum()), "misses": 0, "all_hit": True})
        return ht, jnp.asarray(look.hit), jnp.asarray(look.vals)
    eff_valid = valid
    if look is not None:
        eff_valid = valid & jnp.asarray(~look.hit)
    if fused and coalesce:
        if look is not None:
            plan = routing.miss_subset_plan(dst, start,
                                            jnp.asarray(look.hit),
                                            match=keys[..., None],
                                            valid=valid, cap=keys.shape[1],
                                            role="ht_find")
        else:
            plan = routing.coalesce_plan(dst, start, match=keys[..., None],
                                         valid=valid, cap=keys.shape[1],
                                         role="ht_find")
    elif fused:
        plan = routing.make_plan(dst, eff_valid, cap=keys.shape[1],
                                 role="ht_find")
    else:
        plan = None
    loc_coalesce = coalesce and not fused  # phase-local runs (no plan)

    def probe_body(j, win, active, found, out):
        slot = (start + j) % nslots
        off = slot * rec_w
        if promise == Promise.CRW:
            unit = jnp.full(keys.shape, int(READ_UNIT), dtype=jnp.int32)
            if fused:
                old, rec, win = rdma_fao_get(
                    win, dst, off, unit, win_mod.AmoKind.FAA, off, rec_w,
                    valid=active, plan=plan)
                state = old & STATE_MASK
            else:
                old, win = rdma_fao(win, dst, off, unit,
                                    win_mod.AmoKind.FAA, valid=active,
                                    coalesce=loc_coalesce)
                state = old & STATE_MASK
                lockable = active & (state == FLAG_READY)
                rec = rdma_get(win, dst, off, rec_w, valid=lockable,
                               coalesce=loc_coalesce)
            _, win = rdma_fao(win, dst, off, -unit, win_mod.AmoKind.FAA,
                              valid=active, plan=plan,
                              coalesce=loc_coalesce)
            flag_state = state
        else:
            rec = rdma_get(win, dst, off, rec_w, valid=active, plan=plan,
                           coalesce=loc_coalesce)
            flag_state = rec[..., 0] & STATE_MASK
        hit = active & (flag_state == FLAG_READY) & (rec[..., 1] == keys)
        miss_end = active & (flag_state == FLAG_EMPTY)
        out = jnp.where(hit[..., None], rec[..., 2:2 + vw], out)
        found = found | hit
        active = active & ~(hit | miss_end)
        return win, active, found, out

    found0 = jnp.zeros(keys.shape, dtype=bool)
    out0 = jnp.zeros(keys.shape + (vw,), dtype=jnp.int32)
    if fused:
        # Adaptive termination (see insert_rdma): an all-inactive probe is
        # an identity, so stopping when every op resolved is bit-exact.
        # With a cache in play the carry additionally tracks each hit's
        # slot (the fill needs it to stamp versions); the cache-free trace
        # is untouched.
        track = look is not None or return_slot

        def probe_fused(carry):
            if track:
                j, win, active, found, out, hslot = carry
            else:
                j, win, active, found, out = carry
            prev_found = found
            win, active, found, out = probe_body(j, win, active, found, out)
            if track:
                slot = (start + j) % nslots
                hslot = jnp.where(found & ~prev_found, slot, hslot)
                return j + 1, win, active, found, out, hslot
            return j + 1, win, active, found, out

        carry0 = (jnp.int32(0), ht.win, eff_valid, found0, out0)
        if track:
            carry0 = carry0 + (jnp.full(keys.shape, -1, jnp.int32),)
        loop = ("find_rdma.fused", promise, coalesce, track)
        with trace.span("probe_loop"):
            fin = jax.lax.while_loop(
                lambda c: (c[0] < max_probes) & c[2].any(),
                trace.per_trip(loop, probe_fused), carry0)
        trace.count("probe_trips", fin[0])
        trace.trips(loop, fin[0])
        win, found, out = fin[1], fin[3], fin[4]
        if look is not None:
            hitm = jnp.asarray(look.hit)
            found = found | hitm
            out = jnp.where(hitm[..., None], jnp.asarray(look.vals), out)
            cache.note_fill(look, fin[5], found, out)
            win_mod.log_cache_event("cache_hit", {
                "hits": int(look.hit.sum()),
                "misses": int(look.miss.sum())})
        if return_slot:
            return (DHashTable(win=win, nslots=nslots,
                               val_words=ht.val_words), found, out, fin[5])
    else:
        loop = ("find_rdma", promise, coalesce)
        win, _, found, out = jax.lax.fori_loop(
            0, max_probes,
            trace.per_trip(loop, lambda j, c: probe_body(j, *c)),
            (ht.win, valid, found0, out0))
        trace.trips(loop, max_probes)
    return (DHashTable(win=win, nslots=nslots, val_words=ht.val_words),
            found, out)


# ---------------------------------------------------------------------------
# Transactional composite (DESIGN.md §11): atomic key relocation.
# ---------------------------------------------------------------------------
def _probe_np(data, nranks: int, nslots: int, rec_w: int, key: int,
              max_probes: int):
    """Host-side probe mirror for txn staging: walk `key`'s probe window
    over a concrete window image. Returns (owner, found_slot, empty_slot)
    with -1 for absent. RESERVED (tombstone) slots are probed PAST — the
    open-addressing delete convention `move` relies on — and the window
    ends at the first EMPTY slot."""
    import numpy as np
    owner, start = place_np(nranks, nslots, np.asarray([key], np.int32))
    o, st = int(owner[0]), int(start[0])
    found = empty = -1
    for j in range(max_probes):
        s = (st + j) % nslots
        base = s * rec_w
        state = int(data[o, base]) & int(STATE_MASK)
        if state == int(FLAG_READY) and int(data[o, base + 1]) == int(key):
            found = s
            break
        if state == int(FLAG_EMPTY):
            empty = s
            break
    return o, found, empty


def move(ht: DHashTable, k1, k2, engine, arm: str = "rdma_fused",
         valid=None, max_probes: int = 8, max_retries: int = 8):
    """Atomically relocate each rank's record from key k1 to key k2 — the
    paper-style multi-op composite a one-sided component API cannot
    express without the §11 transaction layer.

    One (k1[p], k2[p]) pair per rank (scalars broadcast). Staged as ONE
    transaction per rank on `engine` (a core/txn.TxnEngine): chain guards
    pin k1's slot (flag READY -> RESERVED tombstone, key identity, every
    value word) and claim k2's slot (flag EMPTY -> READY), then puts land
    the key and payload. A failed guard aborts the WHOLE txn — both
    slots untouched, the atomicity pin tests/test_txn.py asserts — and
    the rank re-probes against the fresh table for up to `max_retries`
    attempts. Ranks whose k1 is absent or whose k2 already exists fail
    with moved=False.

    Returns (table', moved (P,) bool, vals (P, val_words) int32 — the
    relocated payloads). The source slot is left RESERVED: finds probe
    past it and it is not reclaimed (the open-addressing tombstone
    trade)."""
    import numpy as np

    from . import txn as txn_mod
    P, vw, rec_w, nslots = ht.nranks, ht.val_words, ht.rec_w, ht.nslots
    k1 = np.ascontiguousarray(np.broadcast_to(np.asarray(k1, np.int32),
                                              (P,)))
    k2 = np.ascontiguousarray(np.broadcast_to(np.asarray(k2, np.int32),
                                              (P,)))
    pending = (np.ones(P, dtype=bool) if valid is None
               else np.asarray(valid, bool).copy())
    moved = np.zeros(P, dtype=bool)
    out_vals = np.zeros((P, vw), dtype=np.int32)
    win = ht.win
    for _ in range(max_retries):
        if not pending.any():
            break
        data = np.asarray(win.data)
        o1 = np.zeros(P, np.int32)
        o2 = np.zeros(P, np.int32)
        s1 = np.full(P, -1, np.int32)
        s2 = np.full(P, -1, np.int32)
        vals = np.zeros((P, vw), np.int32)
        for p in np.nonzero(pending)[0]:
            o1[p], f1, _ = _probe_np(data, P, nslots, rec_w, int(k1[p]),
                                     max_probes)
            o2[p], f2, e2 = _probe_np(data, P, nslots, rec_w, int(k2[p]),
                                      max_probes)
            if f1 < 0 or f2 >= 0 or e2 < 0:
                continue  # k1 absent / k2 present / no room: final failure
            s1[p], s2[p] = f1, e2
            base1 = f1 * rec_w
            vals[p] = data[o1[p], base1 + 2: base1 + 2 + vw]
        stage = pending & (s1 >= 0) & (s2 >= 0)
        pending = stage  # unstageable ranks are final failures
        if not stage.any():
            break
        off1 = np.maximum(s1, 0) * rec_w
        off2 = np.maximum(s2, 0) * rec_w
        t = txn_mod.Txn(P)
        t.cas(o1, off1, int(FLAG_READY), int(FLAG_RESERVED), chain=True,
              valid=stage)
        t.cas(o1, off1 + 1, k1, k1, chain=True, valid=stage)
        for w in range(vw):
            t.cas(o1, off1 + 2 + w, vals[:, w], vals[:, w], chain=True,
                  valid=stage)
        t.cas(o2, off2, int(FLAG_EMPTY), int(FLAG_READY), chain=True,
              valid=stage)
        t.put(o2, off2 + 1, k2, valid=stage)
        for w in range(vw):
            t.put(o2, off2 + 2 + w, vals[:, w], valid=stage)
        res = engine.run(win, t, arm=arm)
        win = res.wins[t.spaces[0]]
        won = stage & res.committed & res.chain_ok
        moved |= won
        out_vals[won] = vals[won]
        pending = stage & ~won  # chain-aborted: re-probe and retry
    ht2 = DHashTable(win=win, nslots=nslots, val_words=vw)
    return ht2, moved, out_vals


# ---------------------------------------------------------------------------
# RPC backend (active messages, paper Fig. 2)
# ---------------------------------------------------------------------------
def _probe_local(local: Array, key: Array, nslots: int, rec_w: int,
                 start: Array, max_probes: int, want_empty: bool):
    """Shared probe loop over a local shard. Returns (slot, kind, probes)
    where kind 0=miss, 1=found key, 2=empty slot (insertable if want_empty)
    and probes is the number of slots examined before the op decided — the
    RPC-side stat comparable with the RDMA CAS-probe count."""

    def body(j, carry):
        slot, kind, probes = carry
        s = (start + j) % nslots
        rec0 = jax.lax.dynamic_slice(local, (s * rec_w,), (2,))
        state = rec0[0] & STATE_MASK
        is_hit = (state == FLAG_READY) & (rec0[1] == key)
        is_empty = state == FLAG_EMPTY
        searching = kind == 0
        take_hit = searching & is_hit
        take_empty = searching & is_empty & want_empty
        stop_empty = searching & is_empty & (not want_empty)
        kind = jnp.where(take_hit, 1, kind)
        kind = jnp.where(take_empty | stop_empty, jnp.where(take_empty, 2, 3),
                         kind)
        slot = jnp.where(take_hit | take_empty, s, slot)
        probes = probes + searching.astype(jnp.int32)
        return slot, kind, probes

    slot0 = jnp.int32(-1)
    kind0 = jnp.int32(0)
    return jax.lax.fori_loop(0, max_probes, body,
                             (slot0, kind0, jnp.int32(0)))


def build_am_handlers(ht: DHashTable, engine: am_mod.AMEngine,
                      max_probes: int = 8):
    """Register insert/find handlers. Handler state = the local slot words.

    The insert handler runs ops *sequentially* (lax.scan) — the target-side
    serial execution of AM handlers; arbitrary control flow costs no extra
    network phases.
    """
    nslots, rec_w, vw = ht.nslots, ht.rec_w, ht.val_words

    def insert_fn(local, payload, mask):
        # payload: (m, 1 + 1 + vw) = [start | key | val...]
        # reply (m, 2) = [ok | probes]
        def one(local, x):
            pay, ok = x
            start, key, val = pay[0], pay[1], pay[2:2 + vw]
            slot, kind, probes = _probe_local(local, key, nslots, rec_w,
                                              start, max_probes,
                                              want_empty=True)
            can = ok & (kind > 0) & (kind < 3)
            rec = jnp.concatenate([jnp.array([int(FLAG_READY), 0],
                                             dtype=jnp.int32), val])
            rec = rec.at[1].set(key)
            base = jnp.where(can, slot * rec_w, 0)
            cur = jax.lax.dynamic_slice(local, (base,), (rec_w,))
            new = jnp.where(can, rec, cur)
            local = jax.lax.dynamic_update_slice(local, new, (base,))
            return local, jnp.stack([can.astype(jnp.int32),
                                     jnp.where(ok, probes, 0)])

        local2, replies = jax.lax.scan(one, local, (payload, mask))
        return local2, replies

    def find_fn(local, payload, mask):
        # payload: (m, 2) = [start | key]; reply (m, 1 + vw) = [found | val]
        def one(pay):
            start, key = pay[0], pay[1]
            slot, kind, _ = _probe_local(local, key, nslots, rec_w, start,
                                         max_probes, want_empty=False)
            hit = kind == 1
            base = jnp.where(hit, slot * rec_w, 0)
            rec = jax.lax.dynamic_slice(local, (base,), (rec_w,))
            val = jnp.where(hit, rec[2:2 + vw], 0)
            return jnp.concatenate([hit.astype(jnp.int32)[None], val])

        replies = jax.vmap(one)(payload)
        replies = jnp.where(mask[:, None], replies, 0)
        return local, replies

    # Pallas-batched handler bodies (kernels/hash_probe.py): same contract,
    # table-resident-in-VMEM hot path. Selected via REPRO_USE_PALLAS=1.
    from ..kernels import ops as kops

    def insert_batched(data, flat, mask):
        ok, probes, data2 = kops.hash_insert(
            data, flat[..., 0], flat[..., 1], flat[..., 2:2 + vw], mask,
            nslots=nslots, rec_w=rec_w, max_probes=max_probes)
        return data2, jnp.stack([ok.astype(jnp.int32), probes], axis=-1)

    def find_batched(data, flat, mask):
        found, vals = kops.hash_find(
            data, flat[..., 0], flat[..., 1], mask,
            nslots=nslots, rec_w=rec_w, max_probes=max_probes)
        reply = jnp.concatenate([found.astype(jnp.int32)[..., None], vals],
                                axis=-1)
        return data, reply

    use_batched = kops.use_pallas_default()
    ins = engine.register("ht_insert", insert_fn, reply_width=2,
                          batched_fn=insert_batched if use_batched else None)
    fnd = engine.register("ht_find", find_fn, reply_width=1 + vw,
                          batched_fn=find_batched if use_batched else None)
    return ins, fnd


def insert_rpc(ht: DHashTable, engine: am_mod.AMEngine, keys: Array,
               vals: Array, valid: Optional[Array] = None,
               coalesce: bool = False
               ) -> Tuple[DHashTable, Array, Array]:
    """Insert-or-assign via ONE AM round trip (cost: am_rt + handler).

    Returns (table', ok, probes): probes is the handler's REAL probe count
    carried in the reply word, so RDMA/RPC probe stats are comparable.
    coalesce=True dedups identical [start|key|val] request rows — safe
    because the handler is insert-or-assign (idempotent for identical
    rows), and its reply fans out to every duplicate."""
    dst, start = _place(ht, keys)
    payload = jnp.concatenate([start[..., None], keys[..., None], vals],
                              axis=-1)
    h = engine.handler("ht_insert")
    data, replies, delivered = engine.dispatch(h, ht.win.data, dst, payload,
                                               valid, coalesce=coalesce)
    ok = delivered & (replies[..., 0] > 0)
    probes = jnp.where(delivered, replies[..., 1], 0)
    return (DHashTable(win=Window(data=data), nslots=ht.nslots,
                       val_words=ht.val_words), ok, probes)


def find_rpc(ht: DHashTable, engine: am_mod.AMEngine, keys: Array,
             valid: Optional[Array] = None,
             coalesce: bool = False) -> Tuple[Array, Array]:
    dst, start = _place(ht, keys)
    payload = jnp.concatenate([start[..., None], keys[..., None]], axis=-1)
    h = engine.handler("ht_find")
    _, replies, delivered = engine.dispatch(h, ht.win.data, dst, payload,
                                            valid, coalesce=coalesce)
    found = delivered & (replies[..., 0] > 0)
    return found, replies[..., 1:]


# ---------------------------------------------------------------------------
# Unified front-end. backend accepts Backend or its string value; the
# default is AUTO — the adaptive layer (core/adaptive.py, DESIGN.md §4)
# picks the cheapest arm per batch. Without an AMEngine the AUTO choice is
# restricted to the one-sided arms (rdma / rdma_fused).
# ---------------------------------------------------------------------------
def insert(ht, keys, vals, *, promise=Promise.CRW, backend=Backend.AUTO,
           engine=None, adaptive=None, **kw):
    """Batched distributed insert — the paper's §III-B1 op, any backend.

    Args:
      ht:      DHashTable.
      keys:    (P, n) int32, distinct per batch for the RDMA arms (RPC is
               insert-or-assign — DESIGN.md §4 conformance domain).
      vals:    (P, n, val_words) int32.
      promise: Promise.CRW (fully atomic) or CW (phasal writes).
      backend: Backend or string — "auto" (default, cost-model arm per
               batch, DESIGN.md §4), "rdma", or "rpc".
      engine:  am.AMEngine for the RPC/AM arms.
      adaptive: explicit AdaptiveEngine (default: cached per-nranks/engine).
      **kw:    valid, max_probes (any backend); stats (AUTO only — the
               chooser's OpStats); fused, coalesce (explicit "rdma" only —
               AUTO picks fusion/coalescing per batch itself).

    Returns (table', ok (P, n) bool, probes (P, n) int32). Visible results
    are bit-identical across every backend on the conformance domain
    (tests/test_conformance.py); tracer-safe — under jit the AUTO choice
    degrades to the static model decision (DESIGN.md §4)."""
    with trace.root("insert"):
        backend = as_backend(backend)
        if backend == Backend.AUTO:
            from . import adaptive as ad
            a = adaptive or ad.default_engine(ht.nranks, am_engine=engine)
            return a.ht_insert(ht, keys, vals, promise=promise, **kw)
        if backend == Backend.RPC:
            return insert_rpc(ht, engine, keys, vals, valid=kw.get("valid"),
                              coalesce=kw.get("coalesce", False))
        return insert_rdma(ht, keys, vals, promise=promise, **kw)


def find(ht, keys, *, promise=Promise.CR, backend=Backend.AUTO, engine=None,
         adaptive=None, **kw):
    """Batched distributed find. Same backend selection as `insert`.

    Args: as `insert` (promise CR = bare get per probe, CRW = read-locked).
    Returns (table', found (P, n) bool, vals (P, n, val_words) int32) —
    vals are zeros where not found. The table is returned because a C_RW
    find mutates reader counts; for CR it is unchanged. Bit-identical
    visible results across backends (tests/test_conformance.py)."""
    with trace.root("find"):
        backend = as_backend(backend)
        if backend == Backend.AUTO:
            from . import adaptive as ad
            a = adaptive or ad.default_engine(ht.nranks, am_engine=engine)
            return a.ht_find(ht, keys, promise=promise, **kw)
        if backend == Backend.RPC:
            found, vals = find_rpc(ht, engine, keys, valid=kw.get("valid"),
                                   coalesce=kw.get("coalesce", False))
            return ht, found, vals
        return find_rdma(ht, keys, promise=promise, **kw)


# ---------------------------------------------------------------------------
# Pipelined (async) front-ends: submit through a core/pipeline.Pipeline
# whose state is the DHashTable; returns a Handle instead of blocking
# (DESIGN.md §7). Bit-exact vs. the synchronous front-ends above — forcing
# immediately (depth=1, or result() right after submit) IS the sync path.
# ---------------------------------------------------------------------------
def _async_stats(ht, keys, valid, stats, depth: int):
    """Fold the host-computable batch signals (skew, dedup via `place_np`)
    and the pipeline depth into the cost-model stats WITHOUT reading any
    device value — staging must never serialize behind in-flight phases."""
    from dataclasses import replace as _rep

    import numpy as np

    from . import adaptive as ad
    from .types import OpStats, host_value
    s = stats or OpStats()
    k = host_value(keys)
    if k is not None:
        # 1.0 doubles as OpStats' "unknown" sentinel: nudge a legitimately
        # computed 1.0 (perfectly uniform / all-distinct batch) off it by
        # an epsilon invisible to the scores, so the stage-time decide()
        # never recomputes the signal from a DEVICE value — which would
        # serialize staging behind the in-flight batch (DESIGN.md §7).
        if s.skew == 1.0:
            owner, _ = place_np(ht.nranks, ht.nslots, k)
            skew = ad.batch_skew(owner, ht.nranks, valid)
            s = _rep(s, skew=skew if skew != 1.0 else 1.0 + 1e-9)
        if s.dedup == 1.0:
            # nudged UP: dedup < 1 would turn coalescing on (DESIGN.md
            # §6) — every consumer clamps at 1.0, so >1 means "known
            # all-distinct"
            dd = ad.batch_dedup(k, valid)
            s = _rep(s, dedup=dd if dd != 1.0 else 1.0 + 1e-9)
    return _rep(s, pipeline_depth=max(1, int(depth)))


def insert_async(pipe, keys, vals, *, promise=Promise.CRW,
                 backend=Backend.AUTO, engine=None, adaptive=None,
                 deferred=None, **kw):
    """Submit one insert batch to a pipeline; returns a `pipeline.Handle`
    resolving to (ok, probes) — the table threads through `pipe.state`.

    Semantics (DESIGN.md §7): the batch stages immediately (eager) unless
    its arm is an active message, in which case it waits in the deferred-
    dispatch queue until the next dispatch point (`deferred` overrides;
    default: explicit backend "rpc", or an AUTO peek via
    `AdaptiveEngine.peek_arm`). Submission order is serialization order,
    so results are bit-exact vs. calling `insert` in the same order —
    including out-of-order `result()` forcing (tests/test_pipeline.py).

    AUTO batches price arms with `stats.pipeline_depth = pipe.depth`
    (the §7 overlap term) and compute skew/dedup host-side via `place_np`
    so staging never blocks on a device value. A `Pipeline(auto_depth=
    True)` additionally lets the chooser retarget the window count per
    batch (`AdaptiveEngine.auto_depth`, DESIGN.md §9)."""
    backend = as_backend(backend)
    eng = engine if engine is not None else pipe.am_engine
    st = pipe.staged_state
    if backend == Backend.AUTO:
        from . import adaptive as ad
        from .costmodel import DSOp
        a = adaptive or ad.default_engine(st.nranks, am_engine=eng)
        stats = _async_stats(st, keys, kw.get("valid"), kw.pop("stats", None),
                             pipe.depth)
        stats = a.auto_depth(pipe, DSOp.HT_INSERT, promise, stats)
        if deferred is None:
            deferred = a.peek_arm(DSOp.HT_INSERT, promise,
                                  a._ht_stats(keys, kw.get("valid"), stats)
                                  ) in ("am", "am_pt")
        kw = dict(kw, stats=stats, adaptive=a)
    elif deferred is None:
        deferred = backend == Backend.RPC

    def op(ht):
        ht2, ok, probes = insert(ht, keys, vals, promise=promise,
                                 backend=backend, engine=eng, **kw)
        return ht2, (ok, probes)

    return pipe.submit(op, deferred=deferred, label="ht_insert")


def find_async(pipe, keys, *, promise=Promise.CR, backend=Backend.AUTO,
               engine=None, adaptive=None, deferred=None, **kw):
    """Submit one find batch to a pipeline; returns a Handle resolving to
    (found, vals). Same staging/deferral semantics as `insert_async`."""
    backend = as_backend(backend)
    eng = engine if engine is not None else pipe.am_engine
    st = pipe.staged_state
    if backend == Backend.AUTO:
        from . import adaptive as ad
        from .costmodel import DSOp
        a = adaptive or ad.default_engine(st.nranks, am_engine=eng)
        stats = _async_stats(st, keys, kw.get("valid"), kw.pop("stats", None),
                             pipe.depth)
        stats = a.auto_depth(pipe, DSOp.HT_FIND, promise, stats)
        if deferred is None:
            deferred = a.peek_arm(DSOp.HT_FIND, promise,
                                  a._ht_stats(keys, kw.get("valid"), stats)
                                  ) in ("am", "am_pt")
        kw = dict(kw, stats=stats, adaptive=a)
    elif deferred is None:
        deferred = backend == Backend.RPC

    def op(ht):
        ht2, found, vals = find(ht, keys, promise=promise, backend=backend,
                                engine=eng, **kw)
        return ht2, (found, vals)

    return pipe.submit(op, deferred=deferred, label="ht_find")
