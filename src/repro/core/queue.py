"""Distributed hosted queue (ring buffer) — paper §III-B2, Table III, Fig. 4.

A ``DQueue`` lives on a single *host* rank but is visible to (and
manipulable by) every rank — the paper's "hosted data structure". It is a
ring buffer with four control words followed by the data region:

    word 0: tail          (reserve frontier for pushes, advanced by FAA)
    word 1: tail_ready    (publish frontier: data below this is readable)
    word 2: head          (reserve frontier for pops)
    word 3: head_ready    (release frontier: space below this is reusable)

Implementations and their best-case costs (paper Table III):

  push C_RW (rdma):      A_FAO + W + A_CAS-P   (reserve, write, publish)
                         The publish step is a *persistent* CAS: it may only
                         advance tail_ready to its own end offset once every
                         earlier reservation has published — the inherent
                         serialization the paper identifies as the reason
                         C_RW push under-performs its model prediction.
  push C_W  (rdma):      A_FAO + W             (barrier supplies the fence)
  push checksum C_RW:    A_FAO + W             (ready-CAS replaced by an
                         in-payload checksum word verified by the reader)
  pop  C_RW (rdma):      A_FAO + R + A_CAS-P
  pop  C_R  (rdma):      A_FAO + R
  push/pop C_L:          local vector ops, zero network phases
  push/pop (rpc):        one AM round trip + local handler

Batched SPMD semantics: each rank contributes up to ``n`` ops per step; the
RDMA backend issues the component phases for all ranks' batches together
(each component = one routed exchange phase, DESIGN.md §2).
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
from .types import AmoKind, Backend, Promise, as_backend
from .window import Window, rdma_cas, rdma_fao, rdma_get, rdma_put

Array = jax.Array

TAIL, TAIL_READY, HEAD, HEAD_READY = 0, 1, 2, 3
CTRL_WORDS = 4


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["win"],
                   meta_fields=["host", "capacity", "val_words", "checksum"])
@dataclass
class DQueue:
    """Hosted ring buffer. Slot i of the data region starts at word
    CTRL_WORDS + (i % capacity) * slot_w."""

    win: Window
    host: int
    capacity: int      # slots
    val_words: int     # payload words per slot
    checksum: bool = False  # slots carry a trailing checksum word

    @property
    def nranks(self) -> int:
        return self.win.nranks

    @property
    def slot_w(self) -> int:
        return self.val_words + (1 if self.checksum else 0)


def make_queue(nranks: int, host: int, capacity: int, val_words: int,
               checksum: bool = False) -> DQueue:
    slot_w = val_words + (1 if checksum else 0)
    win = win_mod.make_window(nranks, CTRL_WORDS + capacity * slot_w)
    return DQueue(win=win, host=host, capacity=capacity,
                  val_words=val_words, checksum=checksum)


def _csum(vals: Array) -> Array:
    """Checksum over the payload words of one slot: mixed XOR-rotate, nonzero
    by construction (0 marks an unwritten slot)."""
    def body(c, v):
        c = (c ^ v) * jnp.int32(0x01000193)
        return c, None
    seed = jnp.asarray(0x811C9DC5, dtype=jnp.uint32).astype(jnp.int32)
    c, _ = jax.lax.scan(body, seed, vals)
    return jnp.where(c == 0, jnp.int32(1), c)


def _host_dst(q: DQueue, shape) -> Array:
    return jnp.full(shape, q.host, dtype=jnp.int32)


# ---------------------------------------------------------------------------
# RDMA backend — push
# ---------------------------------------------------------------------------
def push_rdma(q: DQueue, vals: Array, promise: Promise = Promise.CRW,
              valid: Optional[Array] = None, max_cas_rounds: int = 8,
              planned: bool = True, coalesce: bool = False
              ) -> Tuple[DQueue, Array]:
    """Batched push of vals (P, n, vw) onto the hosted ring buffer.

    Returns (queue', pushed (P, n) bool). Ops that would overflow the ring
    (reservation >= head_ready + capacity) are aborted by *returning* their
    reservation... which plain FAA cannot do — so, faithfully to BCL, the
    caller must size the ring; overflow slots wrap and are flagged failed.

    planned=True (default): every component phase of the push — reserve
    FAO, failure-return FAO, payload W, and the max_cas_rounds publish
    CASes — reuses ONE RoutePlan (the host destination never changes), so
    the whole op costs one routing sort instead of `max_cas_rounds + 3`.

    coalesce=True (DESIGN.md §6): the reserve and failure-return FAO
    phases combine each origin's n ticket increments into ONE wire row per
    origin (every push targets the same (host, TAIL) word — the extreme
    duplicate case); per-op tickets are reconstructed sender-side from the
    base ticket + each op's prefix, bit-exactly. The payload write and the
    publish CAS rounds target distinct words and are left alone.
    """
    assert promise in (Promise.CRW, Promise.CW)
    if valid is None:
        valid = jnp.ones(vals.shape[:-1], dtype=bool)
    P, n, vw = vals.shape
    assert vw == q.val_words
    dst = _host_dst(q, (P, n))
    use_csum = q.checksum and promise == Promise.CRW
    slot_w = q.slot_w
    plan = (routing.make_plan(dst, valid, cap=n, role="q_push")
            if planned else None)

    # Phase 1 — A_FAO: reserve space by advancing `tail`.
    one = jnp.ones((P, n), dtype=jnp.int32)
    off_tail = jnp.zeros((P, n), dtype=jnp.int32) + TAIL
    ticket, win = rdma_fao(q.win, dst, off_tail, one, AmoKind.FAA,
                           valid=valid, plan=plan, coalesce=coalesce)

    # Ring-capacity check against head_ready (read is free at the host in
    # BCL's implementation via a cached local bound; we read our own cached
    # copy — conservative: a full ring fails the push).
    head_ready = win.data[q.host, HEAD_READY]
    ok = valid & (ticket - head_ready < q.capacity)
    # Failed reservations return their tickets (they are exactly the top
    # of the reserved range, so a bulk decrement restores tail to the
    # last successful ticket + 1). One extra A_FAO on the failure path.
    neg = jnp.where(valid & ~ok, -1, 0)
    _, win = rdma_fao(win, dst, off_tail, neg, AmoKind.FAA,
                      valid=valid & ~ok, plan=plan, coalesce=coalesce)

    # Phase 2 — W: write the payload into the reserved slot.
    slot = ticket % q.capacity
    base = CTRL_WORDS + slot * slot_w
    if use_csum:
        csums = jax.vmap(jax.vmap(_csum))(vals)
        payload = jnp.concatenate([vals, csums[..., None]], axis=-1)
    elif q.checksum:
        # checksum layout but phasal promise: write a zero checksum word
        payload = jnp.concatenate([vals, jnp.zeros((P, n, 1), jnp.int32)],
                                  axis=-1)
    else:
        payload = vals
    win = rdma_put(win, dst, base, payload, valid=ok, plan=plan)

    if promise == Promise.CRW and not use_csum:
        # Phase 3 — persistent CAS: advance tail_ready ticket -> ticket+1.
        # Each op may only publish once every earlier ticket has published:
        # the inherent serialization of Fig. 4's C_RW push.
        off_tr = jnp.zeros((P, n), dtype=jnp.int32) + TAIL_READY
        pending = ok

        def round_(i, carry):
            win, pending = carry
            old, win = rdma_cas(win, dst, off_tr, ticket, ticket + 1,
                                valid=pending, plan=plan)
            done = pending & (old == ticket)
            return win, pending & ~done

        loop = ("push_rdma.publish", planned)
        win, pending = jax.lax.fori_loop(0, max_cas_rounds,
                                         trace.per_trip(loop, round_),
                                         (win, pending))
        trace.trips(loop, max_cas_rounds)
        ok = ok & ~pending  # unpublished pushes report failure
    return (DQueue(win=win, host=q.host, capacity=q.capacity,
                   val_words=q.val_words, checksum=q.checksum), ok)


# ---------------------------------------------------------------------------
# RDMA backend — pop
# ---------------------------------------------------------------------------
def pop_rdma(q: DQueue, n: int, promise: Promise = Promise.CR,
             valid: Optional[Array] = None, max_cas_rounds: int = 8,
             planned: bool = True, coalesce: bool = False
             ) -> Tuple[DQueue, Array, Array]:
    """Batched pop of up to n values per rank. Returns (q', got (P,n), vals).

    C_R : A_FAO (reserve head) + R (read slot). A barrier separates pops
          from pushes, so tail_ready == tail and no release CAS is needed.
    C_RW: A_FAO + R + persistent CAS advancing head_ready (release), and the
          reservation is validated against tail_ready.

    planned=True: one RoutePlan shared by every phase (see push_rdma).
    coalesce=True combines the head-reservation (and failure-return) FAOs
    into one wire row per origin, tickets reconstructed sender-side
    (bit-exact; see push_rdma).
    """
    assert promise in (Promise.CRW, Promise.CR)
    P = q.nranks
    if valid is None:
        valid = jnp.ones((P, n), dtype=bool)
    dst = _host_dst(q, (P, n))
    slot_w = q.slot_w
    plan = (routing.make_plan(dst, valid, cap=n, role="q_pop")
            if planned else None)

    one = jnp.ones((P, n), dtype=jnp.int32)
    off_head = jnp.zeros((P, n), dtype=jnp.int32) + HEAD
    ticket, win = rdma_fao(q.win, dst, off_head, one, AmoKind.FAA,
                           valid=valid, plan=plan, coalesce=coalesce)

    # Bound check: may only read below the publish frontier. Checksum
    # queues read optimistically below `tail` and validate the in-payload
    # checksum instead (that is the point of the design: no publish CAS).
    use_ready = promise == Promise.CRW and not q.checksum
    frontier = win.data[q.host, TAIL_READY if use_ready else TAIL]
    got = valid & (ticket < frontier)
    # Return failed reservations (top of the range) so unread elements are
    # not skipped by later pops.
    neg = jnp.where(valid & ~got, -1, 0)
    _, win = rdma_fao(win, dst, off_head, neg, AmoKind.FAA,
                      valid=valid & ~got, plan=plan, coalesce=coalesce)

    slot = ticket % q.capacity
    base = CTRL_WORDS + slot * slot_w
    rec = rdma_get(win, dst, base, slot_w, valid=got, plan=plan)
    vals = rec[..., :q.val_words]

    if q.checksum and promise == Promise.CRW:
        # Verify the in-payload checksum instead of trusting tail_ready.
        want = jax.vmap(jax.vmap(_csum))(vals)
        got = got & (rec[..., -1] == want)

    if promise == Promise.CRW:
        off_hr = jnp.zeros((P, n), dtype=jnp.int32) + HEAD_READY
        pending = got

        def round_(i, carry):
            win, pending = carry
            old, win = rdma_cas(win, dst, off_hr, ticket, ticket + 1,
                                valid=pending, plan=plan)
            done = pending & (old == ticket)
            return win, pending & ~done

        loop = ("pop_rdma.release", planned)
        win, _ = jax.lax.fori_loop(0, max_cas_rounds,
                                   trace.per_trip(loop, round_),
                                   (win, pending))
        trace.trips(loop, max_cas_rounds)
    # Failed pops report zeros, not routing garbage: the reply words of
    # undelivered ops are garbage by contract in the unplanned engine, and
    # the adaptive layer swaps backends per batch — visible results must be
    # bit-identical across every backend (tests/test_conformance.py).
    vals = jnp.where(got[..., None], vals, 0)
    return (DQueue(win=win, host=q.host, capacity=q.capacity,
                   val_words=q.val_words, checksum=q.checksum), got, vals)


# ---------------------------------------------------------------------------
# Transactional composite (DESIGN.md §11): atomic pop -> hash-table insert.
# ---------------------------------------------------------------------------
def pop_then_insert(q: DQueue, ht, engine, arm: str = "rdma_fused",
                    valid=None, max_probes: int = 8,
                    max_retries: Optional[int] = None):
    """Each participating rank atomically pops ONE item from the hosted
    queue and inserts it into the hash table keyed by its first payload
    word — the cross-structure composite that motivates the multi-space
    transaction engine (`engine` is a core/txn.TxnEngine).

    Staging: every pending rank snapshots (HEAD=h, slot payload v) and
    guards the SAME hot head word with a chain-CAS(h -> h+1) — plus the
    HEAD_READY release frontier, the slot's value words, and the hash
    table's claim CAS (flag EMPTY -> READY) — then puts land key=v[0] and
    the payload. Exactly one rank per round wins the head word (the
    serialized lock phase hands it to the lowest stager); every loser
    aborts cleanly — the item stays popped-or-not ATOMICALLY with its
    table insert — and retries against a fresh snapshot. This hot-word
    contention is the abort-rate generator benchmarks/components.py
    measures. Deliberately NOT guarded: TAIL_READY — a concurrent
    transactional push extends the readable region without invalidating
    an in-flight pop of an older slot, so guarding it would abort
    needlessly.

    Assumes txn-only pops (HEAD and HEAD_READY advance in lockstep), an
    un-checksummed queue, and q.val_words == ht.val_words. A head item
    whose key already sits in the table (or whose probe window is full)
    stalls the composite: it is left at the head un-popped and the
    remaining ranks give up (tests use distinct keys).

    Returns (q', ht', popped (P,) bool, vals (P, val_words) int32)."""
    import numpy as np

    from . import hashtable as ht_mod
    from . import txn as txn_mod
    from .types import FLAG_EMPTY, FLAG_READY
    P, vw = q.nranks, q.val_words
    assert not q.checksum, "pop_then_insert needs an un-checksummed queue"
    assert vw == ht.val_words, "queue payload must match table record"
    pending = (np.ones(P, dtype=bool) if valid is None
               else np.asarray(valid, bool).copy())
    popped = np.zeros(P, dtype=bool)
    out_vals = np.zeros((P, vw), dtype=np.int32)
    wins = {"q": q.win, "ht": ht.win}
    if max_retries is None:
        max_retries = 2 * P + 8
    for _ in range(max_retries):
        if not pending.any():
            break
        qdata = np.asarray(wins["q"].data)
        h = int(qdata[q.host, HEAD])
        tr = int(qdata[q.host, TAIL_READY])
        if h >= tr:
            break  # queue drained: remaining ranks fail
        base = CTRL_WORDS + (h % q.capacity) * q.slot_w
        v = qdata[q.host, base: base + vw].astype(np.int32)
        key = int(v[0])
        hdata = np.asarray(wins["ht"].data)
        o2, f2, e2 = ht_mod._probe_np(hdata, P, ht.nslots, ht.rec_w, key,
                                      max_probes)
        if f2 >= 0 or e2 < 0:
            break  # head item uninsertable: composite cannot progress
        off2 = e2 * ht.rec_w
        t = txn_mod.Txn(P)
        t.cas(q.host, HEAD, h, h + 1, space="q", chain=True, valid=pending)
        t.cas(q.host, HEAD_READY, h, h + 1, space="q", chain=True,
              valid=pending)
        for w in range(vw):
            t.cas(q.host, base + w, int(v[w]), int(v[w]), space="q",
                  chain=True, valid=pending)
        t.cas(o2, off2, int(FLAG_EMPTY), int(FLAG_READY), space="ht",
              chain=True, valid=pending)
        t.put(o2, off2 + 1, key, space="ht", valid=pending)
        for w in range(vw):
            t.put(o2, off2 + 2 + w, int(v[w]), space="ht", valid=pending)
        res = engine.run(wins, t, arm=arm)
        wins = res.wins
        won = pending & res.committed & res.chain_ok
        popped |= won
        out_vals[won] = v
        pending &= ~won  # losers chain-aborted: fresh snapshot next round
    q2 = DQueue(win=wins["q"], host=q.host, capacity=q.capacity,
                val_words=vw, checksum=q.checksum)
    from .hashtable import DHashTable
    ht2 = DHashTable(win=wins["ht"], nslots=ht.nslots, val_words=vw)
    return q2, ht2, popped, out_vals


# ---------------------------------------------------------------------------
# C_L: local push/pop — the host manipulates its own ring, no network.
# ---------------------------------------------------------------------------
def push_local(q: DQueue, vals: Array, valid: Optional[Array] = None
               ) -> Tuple[DQueue, Array]:
    """Host-local batched push: vals (n, vw) appended at tail. Zero phases."""
    n, vw = vals.shape
    if valid is None:
        valid = jnp.ones((n,), dtype=bool)
    data = q.win.data
    local = data[q.host]
    tail = local[TAIL]
    head_ready = local[HEAD_READY]
    rank = jnp.cumsum(valid.astype(jnp.int32)) - 1
    ticket = tail + rank
    ok = valid & (ticket - head_ready < q.capacity)
    slot = ticket % q.capacity
    base = CTRL_WORDS + slot * q.slot_w
    cols = base[:, None] + jnp.arange(vw)[None, :]
    safe_cols = jnp.where(ok[:, None], cols, q.win.local_size)
    local = local.at[safe_cols].set(vals, mode="drop")
    if q.checksum:
        csums = jax.vmap(_csum)(vals)
        local = local.at[jnp.where(ok, base + vw, q.win.local_size)].set(
            csums, mode="drop")
    new_tail = tail + jnp.sum(ok)
    local = local.at[TAIL].set(new_tail).at[TAIL_READY].set(new_tail)
    data = data.at[q.host].set(local)
    return (DQueue(win=Window(data=data), host=q.host, capacity=q.capacity,
                   val_words=q.val_words, checksum=q.checksum), ok)


def pop_local(q: DQueue, n: int) -> Tuple[DQueue, Array, Array]:
    """Host-local batched pop of up to n values. Zero network phases."""
    data = q.win.data
    local = data[q.host]
    head, tail_ready = local[HEAD], local[TAIL_READY]
    ticket = head + jnp.arange(n, dtype=jnp.int32)
    got = ticket < tail_ready
    slot = ticket % q.capacity
    base = CTRL_WORDS + slot * q.slot_w
    cols = base[:, None] + jnp.arange(q.val_words)[None, :]
    vals = local.at[cols].get(mode="fill", fill_value=0)
    vals = jnp.where(got[:, None], vals, 0)
    new_head = head + jnp.sum(got)
    local = local.at[HEAD].set(new_head).at[HEAD_READY].set(new_head)
    data = data.at[q.host].set(local)
    return (DQueue(win=Window(data=data), host=q.host, capacity=q.capacity,
                   val_words=q.val_words, checksum=q.checksum), got, vals)


# ---------------------------------------------------------------------------
# RPC backend (paper Fig. 2 applied to the queue)
# ---------------------------------------------------------------------------
def build_am_handlers(q: DQueue, engine: am_mod.AMEngine):
    """push/pop handlers running sequentially at the host — arbitrary control
    flow (bounds checks, wraparound, publish) in ONE round trip."""
    vw, slot_w, cap = q.val_words, q.slot_w, q.capacity

    def push_fn(local, payload, mask):
        # payload: (m, vw)
        def one(local, x):
            vals, ok = x
            tail = local[TAIL]
            head_ready = local[HEAD_READY]
            can = ok & (tail - head_ready < cap)
            base = CTRL_WORDS + (tail % cap) * slot_w
            cur = jax.lax.dynamic_slice(local, (jnp.where(can, base, 0),),
                                        (vw,))
            new = jnp.where(can, vals, cur)
            local = jax.lax.dynamic_update_slice(
                local, new, (jnp.where(can, base, 0),))
            if q.checksum:
                c = jnp.where(can, _csum(vals),
                              local[jnp.where(can, base + vw, 0)])
                local = local.at[jnp.where(can, base + vw, 0)].set(c)
            adv = can.astype(jnp.int32)
            local = local.at[TAIL].add(adv).at[TAIL_READY].add(adv)
            return local, adv[None]

        local2, replies = jax.lax.scan(one, local, (payload, mask))
        return local2, replies

    def pop_fn(local, payload, mask):
        # payload ignored; reply (m, 1 + vw) = [got | vals]
        def one(local, ok):
            head, tail_ready = local[HEAD], local[TAIL_READY]
            can = ok & (head < tail_ready)
            base = CTRL_WORDS + (head % cap) * slot_w
            rec = jax.lax.dynamic_slice(local, (jnp.where(can, base, 0),),
                                        (vw,))
            rec = jnp.where(can, rec, 0)
            adv = can.astype(jnp.int32)
            local = local.at[HEAD].add(adv).at[HEAD_READY].add(adv)
            return local, jnp.concatenate([adv[None], rec])

        local2, replies = jax.lax.scan(one, local, mask)
        return local2, replies

    # Vectorized batched handler bodies: the sequential scan semantics are
    # reproducible with prefix ranks (a failed op never consumes a ticket,
    # and capacity failures are a contiguous suffix of the valid ops), so
    # the owner can service its whole request grid in one vector step —
    # the emulation analogue of a cheap GASNet handler.
    def push_batched(data, payload, mask):
        def one(local, vals, ok):
            m = ok.shape[0]
            tail, head_ready = local[TAIL], local[HEAD_READY]
            rank = jnp.cumsum(ok.astype(jnp.int32)) - 1
            ticket = tail + rank
            can = ok & (ticket - head_ready < cap)
            base = CTRL_WORDS + (ticket % cap) * slot_w
            cols = base[:, None] + jnp.arange(vw)[None, :]
            safe = jnp.where(can[:, None], cols, local.shape[0])
            local = local.at[safe].set(vals[:, :vw], mode="drop")
            if q.checksum:
                cs = jax.vmap(_csum)(vals[:, :vw])
                local = local.at[jnp.where(can, base + vw,
                                           local.shape[0])].set(
                    cs, mode="drop")
            adv = jnp.sum(can)
            local = local.at[TAIL].add(adv).at[TAIL_READY].add(adv)
            return local, can.astype(jnp.int32)[:, None]

        return jax.vmap(one)(data, payload, mask)

    def pop_batched(data, payload, mask):
        def one(local, _, ok):
            head, tail_ready = local[HEAD], local[TAIL_READY]
            rank = jnp.cumsum(ok.astype(jnp.int32)) - 1
            ticket = head + rank
            can = ok & (ticket < tail_ready)
            base = CTRL_WORDS + (ticket % cap) * slot_w
            cols = base[:, None] + jnp.arange(vw)[None, :]
            rec = local.at[cols].get(mode="fill", fill_value=0)
            rec = jnp.where(can[:, None], rec, 0)
            adv = jnp.sum(can)
            local = local.at[HEAD].add(adv).at[HEAD_READY].add(adv)
            return local, jnp.concatenate(
                [can.astype(jnp.int32)[:, None], rec], axis=-1)

        return jax.vmap(one)(data, payload, mask)

    push_h = engine.register("q_push", push_fn, reply_width=1,
                             batched_fn=push_batched)
    pop_h = engine.register("q_pop", pop_fn, reply_width=1 + vw,
                            batched_fn=pop_batched)
    return push_h, pop_h


def push_rpc(q: DQueue, engine: am_mod.AMEngine, vals: Array,
             valid: Optional[Array] = None) -> Tuple[DQueue, Array]:
    """Push via ONE AM round trip."""
    P, n, _ = vals.shape
    dst = _host_dst(q, (P, n))
    h = engine.handler("q_push")
    data, replies, delivered = engine.dispatch(h, q.win.data, dst, vals,
                                               valid)
    ok = delivered & (replies[..., 0] > 0)
    return (DQueue(win=Window(data=data), host=q.host, capacity=q.capacity,
                   val_words=q.val_words, checksum=q.checksum), ok)


def pop_rpc(q: DQueue, engine: am_mod.AMEngine, n: int,
            valid: Optional[Array] = None) -> Tuple[DQueue, Array, Array]:
    P = q.nranks
    dst = _host_dst(q, (P, n))
    payload = jnp.zeros((P, n, 1), dtype=jnp.int32)
    h = engine.handler("q_pop")
    data, replies, delivered = engine.dispatch(h, q.win.data, dst, payload,
                                               valid)
    got = delivered & (replies[..., 0] > 0)
    vals = jnp.where(got[..., None], replies[..., 1:], 0)
    return (DQueue(win=Window(data=data), host=q.host, capacity=q.capacity,
                   val_words=q.val_words, checksum=q.checksum), got, vals)


# ---------------------------------------------------------------------------
# Unified front-end. backend accepts Backend or its string value; default
# AUTO routes through the adaptive layer (core/adaptive.py, DESIGN.md §4).
# C_L short-circuits before any backend decision (zero network phases).
# ---------------------------------------------------------------------------
def push(q, vals, *, promise=Promise.CRW, backend=Backend.AUTO, engine=None,
         adaptive=None, **kw):
    """Batched push onto the hosted ring buffer — paper §III-B2, any backend.

    Args:
      q:       DQueue.
      vals:    (P, n, val_words) int32 — up to n pushes per rank per step.
      promise: CRW (reserve+write+publish), CW (barrier-fenced), or CL
               (host-local, zero network phases — short-circuits before any
               backend decision; vals is (n, val_words) there).
      backend: "auto" (default, DESIGN.md §4) / "rdma" / "rpc".
      engine:  am.AMEngine for the RPC/AM arms.
      adaptive: explicit AdaptiveEngine (default: cached).
      **kw:    valid, max_cas_rounds (any backend); stats (AUTO only);
               planned, coalesce (explicit "rdma" only — AUTO picks the
               planned/coalesced engine per batch itself).

    Returns (queue', pushed (P, n) bool). Bit-identical visible results
    across backends (tests/test_conformance.py); tracer-safe (the hosted
    queue's skew is `nranks` by construction, so AUTO needs no host read)."""
    with trace.root("push"):
        if promise == Promise.CL:
            return push_local(q, vals, **kw)
        backend = as_backend(backend)
        if backend == Backend.AUTO:
            from . import adaptive as ad
            a = adaptive or ad.default_engine(q.nranks, am_engine=engine)
            return a.q_push(q, vals, promise=promise, **kw)
        if backend == Backend.RPC:
            return push_rpc(q, engine, vals, valid=kw.get("valid"))
        return push_rdma(q, vals, promise=promise, **kw)


def pop(q, n, *, promise=Promise.CR, backend=Backend.AUTO, engine=None,
        adaptive=None, **kw):
    """Batched pop of up to n values per rank. Backends as in `push`.

    Returns (queue', got (P, n) bool, vals (P, n, val_words) int32) — vals
    are zeros where got is False (the cross-backend contract pinned by
    tests/test_conformance.py)."""
    with trace.root("pop"):
        if promise == Promise.CL:
            return pop_local(q, n)
        backend = as_backend(backend)
        if backend == Backend.AUTO:
            from . import adaptive as ad
            a = adaptive or ad.default_engine(q.nranks, am_engine=engine)
            return a.q_pop(q, n, promise=promise, **kw)
        if backend == Backend.RPC:
            return pop_rpc(q, engine, n, valid=kw.get("valid"))
        return pop_rdma(q, n, promise=promise, **kw)


# ---------------------------------------------------------------------------
# Pipelined (async) front-ends (DESIGN.md §7): submit through a
# core/pipeline.Pipeline whose state is the DQueue. Bit-exact vs. the
# synchronous front-ends — submission order is serialization order.
# ---------------------------------------------------------------------------
def _q_async_stats(stats, depth: int):
    from dataclasses import replace as _rep

    from .types import OpStats
    return _rep(stats or OpStats(), pipeline_depth=max(1, int(depth)))


def push_async(pipe, vals, *, promise=Promise.CRW, backend=Backend.AUTO,
               engine=None, adaptive=None, deferred=None, **kw):
    """Submit one push batch to a pipeline; returns a Handle resolving to
    `pushed` — the queue threads through `pipe.state`.

    AM-arm batches go through the deferred-dispatch queue and stage at the
    next dispatch point (`deferred` overrides — see
    `hashtable.insert_async` for the §7 semantics); AUTO batches price
    arms with `stats.pipeline_depth = pipe.depth`. CL pushes are always
    eager (they are local compute — there is nothing to overlap)."""
    backend = as_backend(backend)
    eng = engine if engine is not None else pipe.am_engine
    q0 = pipe.staged_state
    if promise != Promise.CL and backend == Backend.AUTO:
        from . import adaptive as ad
        from .costmodel import DSOp
        a = adaptive or ad.default_engine(q0.nranks, am_engine=eng)
        stats = _q_async_stats(kw.pop("stats", None), pipe.depth)
        stats = a.auto_depth(pipe, DSOp.Q_PUSH, promise,
                             a._host_stats(stats))
        if deferred is None:
            deferred = a.peek_arm(DSOp.Q_PUSH, promise,
                                  a._host_stats(stats)) in ("am", "am_pt")
        kw = dict(kw, stats=stats, adaptive=a)
    elif deferred is None:
        deferred = promise != Promise.CL and backend == Backend.RPC

    def op(q):
        q2, ok = push(q, vals, promise=promise, backend=backend, engine=eng,
                      **kw)
        return q2, ok

    return pipe.submit(op, deferred=deferred, label="q_push")


def pop_async(pipe, n, *, promise=Promise.CR, backend=Backend.AUTO,
              engine=None, adaptive=None, deferred=None, **kw):
    """Submit one pop batch to a pipeline; returns a Handle resolving to
    (got, vals). Same staging/deferral semantics as `push_async`."""
    backend = as_backend(backend)
    eng = engine if engine is not None else pipe.am_engine
    q0 = pipe.staged_state
    if promise != Promise.CL and backend == Backend.AUTO:
        from . import adaptive as ad
        from .costmodel import DSOp
        a = adaptive or ad.default_engine(q0.nranks, am_engine=eng)
        stats = _q_async_stats(kw.pop("stats", None), pipe.depth)
        stats = a.auto_depth(pipe, DSOp.Q_POP, promise,
                             a._host_stats(stats))
        if deferred is None:
            deferred = a.peek_arm(DSOp.Q_POP, promise,
                                  a._host_stats(stats)) in ("am", "am_pt")
        kw = dict(kw, stats=stats, adaptive=a)
    elif deferred is None:
        deferred = promise != Promise.CL and backend == Backend.RPC

    def op(q):
        q2, got, vals = pop(q, n, promise=promise, backend=backend,
                            engine=eng, **kw)
        return q2, (got, vals)

    return pipe.submit(op, deferred=deferred, label="q_pop")
