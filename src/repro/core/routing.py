"""Owner-routing engine: bucket a batch of ops by destination rank and
exchange the buckets across ranks.

This is the *one network phase* primitive out of which both backends are
built:

- an RDMA component op (put/get/CAS/FAO) is exactly one routed phase
  (plus one reply phase when it fetches something), with NO target-side
  control flow other than the fixed-function AMO apply;
- an RPC dispatch is one routed request phase, an arbitrary local handler,
  and one routed reply phase.

Representation: every participant ("virtual rank") owns row `r` of a
`(P, ...)` array. The leading P axis is mapped onto physical mesh axes by
the launch layer via `sharding_hint`; on a single CPU device everything is
local and the exchange is a transpose. When P is sharded over a mesh axis,
`exchange` lowers to an XLA all-to-all — one per network phase, which is
what the roofline collective counter sees.
"""
from __future__ import annotations

import contextlib
import functools
from dataclasses import dataclass
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels.scan import segmented_scan
from . import trace

# ---------------------------------------------------------------------------
# Sharding hook: the launch layer installs a constraint function so that the
# P axis stays pinned to its mesh axes across phases (forcing all_to_all
# lowering instead of gather/slice chains). Default is identity (single dev).
# ---------------------------------------------------------------------------
_SHARD_HOOK: Callable[[jax.Array, str], jax.Array] = lambda x, role: x


def set_sharding_hook(fn: Optional[Callable[[jax.Array, str], jax.Array]]):
    global _SHARD_HOOK
    _SHARD_HOOK = fn if fn is not None else (lambda x, role: x)


@contextlib.contextmanager
def sharding_hook(fn):
    global _SHARD_HOOK
    prev = _SHARD_HOOK
    _SHARD_HOOK = fn
    try:
        yield
    finally:
        _SHARD_HOOK = prev


def current_sharding_hook() -> Callable[[jax.Array, str], jax.Array]:
    """The hook in force: a program traced now reads this one, so a cache
    of traced programs keys on its identity (`am.AMEngine`)."""
    return _SHARD_HOOK


def _hint(x: jax.Array, role: str) -> jax.Array:
    return _SHARD_HOOK(x, role)


# ---------------------------------------------------------------------------
# Binning: per-origin scatter of ops into per-destination capacity slots.
# ---------------------------------------------------------------------------
@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["buf", "mask", "op_slot", "op_ok", "dropped"],
                   meta_fields=[])
@dataclass
class Binned:
    """Result of binning one origin's op batch by destination rank.

    buf:      (nranks, cap, W) payload words routed to each destination
    mask:     (nranks, cap)    slot occupancy
    op_slot:  (n,)             slot index assigned to each original op
    op_ok:    (n,)             op was delivered (not dropped by capacity)
    dropped:  ()               number of ops dropped (capacity overflow)
    """

    buf: jax.Array
    mask: jax.Array
    op_slot: jax.Array
    op_ok: jax.Array
    dropped: jax.Array


def bin_by_dest(dst: jax.Array, payload: jax.Array, nranks: int, cap: int,
                valid: Optional[jax.Array] = None) -> Binned:
    """Bucket `n` ops (single origin) by destination rank.

    dst:     (n,) int32 destination rank per op
    payload: (n, W) payload words per op (W static)
    cap:     per-destination slot capacity. cap >= n is always lossless.
    """
    n = dst.shape[0]
    if valid is None:
        valid = jnp.ones((n,), dtype=bool)
    # Invalid ops route to a sentinel rank (dropped by out-of-bounds scatter).
    dst_eff = jnp.where(valid, dst, nranks)
    order = jnp.argsort(dst_eff, stable=True)
    dst_sorted = dst_eff[order]
    # Position of each op within its destination group.
    group_start = jnp.searchsorted(dst_sorted, dst_sorted, side="left")
    pos_sorted = jnp.arange(n, dtype=jnp.int32) - group_start.astype(jnp.int32)
    payload_sorted = payload[order]

    buf = jnp.zeros((nranks, cap) + payload.shape[1:], dtype=payload.dtype)
    # mode="drop" silently drops dst==nranks (invalid) and pos>=cap (overflow)
    buf = buf.at[dst_sorted, pos_sorted].set(payload_sorted, mode="drop")
    mask = jnp.zeros((nranks, cap), dtype=bool)
    ok_sorted = (pos_sorted < cap) & (dst_sorted < nranks)
    mask = mask.at[dst_sorted, pos_sorted].set(ok_sorted, mode="drop")

    # Scatter slot assignments back to original op order.
    op_slot = jnp.zeros((n,), dtype=jnp.int32).at[order].set(pos_sorted)
    op_ok = jnp.zeros((n,), dtype=bool).at[order].set(ok_sorted)
    dropped = jnp.sum(valid) - jnp.sum(ok_sorted & (dst_sorted < nranks))
    return Binned(buf=buf, mask=mask, op_slot=op_slot, op_ok=op_ok,
                  dropped=dropped.astype(jnp.int32))


# ---------------------------------------------------------------------------
# Exchange: the network phase. (P_src, P_dst, ...) -> (P_dst, P_src, ...)
# ---------------------------------------------------------------------------
def exchange(x: jax.Array, role: str = "exchange") -> jax.Array:
    """Transpose the (src, dst) leading axes: each rank receives the buckets
    addressed to it. With the leading axis sharded over the owner mesh axes
    this lowers to a single all-to-all; on one device it is a transpose.

    Counted as `exchanges` (one per eager call, per trip inside a traced
    loop body: `trace.per_trip`); its device ops carry the named scope
    `ds.exchange`.
    """
    trace.count("exchanges", of=x)
    with jax.named_scope("ds.exchange"):
        x = _hint(x, role + "_pre")
        out = jnp.swapaxes(x, 0, 1)
        return _hint(out, role + "_post")


# ---------------------------------------------------------------------------
# Full routed phases, vmapped over all P origins.
# ---------------------------------------------------------------------------
@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["at_owner", "mask", "op_slot", "op_ok",
                                "dropped"],
                   meta_fields=[])
@dataclass
class Routed:
    """A request batch delivered to owners.

    at_owner: (P_owner, P_src, cap, W) payloads as seen by each owner
    mask:     (P_owner, P_src, cap)
    op_slot:  (P_src, n) slot index of each original op
    op_ok:    (P_src, n)
    dropped:  (P_src,)
    """

    at_owner: jax.Array
    mask: jax.Array
    op_slot: jax.Array
    op_ok: jax.Array
    dropped: jax.Array


# ---------------------------------------------------------------------------
# Route plans: the routing computation (stable argsort + slot binning + the
# owner-side occupancy exchange) factored out of the per-phase data path so
# that a probe loop issuing `max_probes + 2` phases to the SAME destinations
# pays for ONE sort instead of one per phase (DESIGN.md §2).
# ---------------------------------------------------------------------------
@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["dst_eff", "op_slot", "op_ok", "mask",
                                "dropped"],
                   meta_fields=["cap"])
@dataclass
class RoutePlan:
    """A reusable (dst, slot) assignment for a batch of ops.

    dst_eff: (P, n)  destination per op, invalid ops -> sentinel `nranks`
    op_slot: (P, n)  slot within the destination bucket (raw rank in group;
                     may be >= cap for capacity-dropped ops)
    op_ok:   (P, n)  op was delivered (valid, in-capacity)
    mask:    (P_owner, P_src, cap) owner-side occupancy, exchanged ONCE at
             plan time; reused phases exchange only payload words
    dropped: (P,)    per-origin capacity drops
    cap:     static per-destination slot capacity
    """

    dst_eff: jax.Array
    op_slot: jax.Array
    op_ok: jax.Array
    mask: jax.Array
    dropped: jax.Array
    cap: int

    @property
    def nranks(self) -> int:
        return self.dst_eff.shape[0]


def make_plan(dst: jax.Array, valid: Optional[jax.Array] = None,
              cap: Optional[int] = None, role: str = "plan") -> RoutePlan:
    """Compute the routing assignment for a batch (ONE stable argsort) and
    exchange the occupancy mask (ONE exchange). Payload-only phases are then
    issued against the plan with `route_with_plan`.

    The binning is bin_by_dest itself (run with a zero-width payload), so
    plan slots are bit-identical to route()'s by construction."""
    nranks, n = dst.shape
    cap = n if cap is None else cap
    with trace.span("plan"), jax.named_scope("ds.plan"):
        if valid is None:
            valid = jnp.ones(dst.shape, dtype=bool)
        empty = jnp.zeros(dst.shape + (0,), dtype=jnp.int32)
        binned = jax.vmap(lambda d, p, v: bin_by_dest(d, p, nranks, cap, v))(
            dst, empty, valid)
        dst_eff = jnp.where(valid, dst, nranks)
        mask_at_owner = exchange(binned.mask, role + "_mask")
    return RoutePlan(dst_eff=dst_eff, op_slot=binned.op_slot,
                     op_ok=binned.op_ok, mask=mask_at_owner,
                     dropped=binned.dropped, cap=cap)


def make_plan_np(dst, valid=None, cap: Optional[int] = None,
                 role: str = "plan") -> RoutePlan:
    """Host-side (numpy) mirror of `make_plan` — bit-identical slot
    assignment, computed on the Python thread instead of the device stream.

    This is how the pipeline engine (core/pipeline.py, DESIGN.md §7) takes
    plan construction off the critical path: batch *k+1*'s stable argsort
    and slot binning run on the host while the device is still executing
    batch *k*'s phases. The occupancy mask still crosses the network as ONE
    `exchange` (same PLAN_EXCHANGES accounting as `make_plan` — only the
    sort moved to the host), so phase counts are unchanged.

    dst/valid must be host-concrete (numpy or non-tracer jax arrays);
    under jit tracing use `make_plan`. Bit-equality with `make_plan` is
    pinned by tests/test_pipeline.py.
    """
    import numpy as np
    with trace.span("plan"), jax.named_scope("ds.plan"):
        dst = np.asarray(dst)
        nranks, n = dst.shape
        cap = n if cap is None else cap
        valid = (np.ones(dst.shape, dtype=bool) if valid is None
                 else np.asarray(valid).astype(bool))
        dst_eff = np.where(valid, dst, nranks).astype(np.int32)
        op_slot = np.zeros((nranks, n), np.int32)
        op_ok = np.zeros((nranks, n), bool)
        mask = np.zeros((nranks, nranks, cap), bool)
        dropped = np.zeros((nranks,), np.int32)
        for r in range(nranks):
            order = np.argsort(dst_eff[r], kind="stable")
            dst_s = dst_eff[r][order]
            group_start = np.searchsorted(dst_s, dst_s, side="left")
            pos = (np.arange(n) - group_start).astype(np.int32)
            ok = (pos < cap) & (dst_s < nranks)
            sel = ok
            mask[r][dst_s[sel], pos[sel]] = True
            op_slot[r][order] = pos
            op_ok[r][order] = ok
            dropped[r] = int(valid[r].sum()) - int(ok.sum())
        mask_at_owner = exchange(jnp.asarray(mask), role + "_mask")
    return RoutePlan(dst_eff=jnp.asarray(dst_eff),
                     op_slot=jnp.asarray(op_slot),
                     op_ok=jnp.asarray(op_ok), mask=mask_at_owner,
                     dropped=jnp.asarray(dropped), cap=cap)


def owner_loads(plan: RoutePlan) -> jax.Array:
    """Delivered ops per owner rank, from the plan's occupancy mask —
    the (P,) histogram behind the adaptive layer's skew statistic."""
    return plan.mask.sum(axis=(1, 2)).astype(jnp.int32)


def plan_skew(plan: RoutePlan) -> jax.Array:
    """Batch skew statistic: max owner load / mean owner load (over all P
    owners). 1.0 = perfectly uniform; P = single hot owner. High skew
    serializes RDMA atomics in one owner's apply lane (DESIGN.md §4);
    `adaptive.batch_skew` computes the same statistic host-side from `dst`
    without paying the plan's occupancy exchange."""
    loads = owner_loads(plan).astype(jnp.float32)
    total = jnp.maximum(loads.sum(), 1.0)
    return loads.max() * loads.shape[0] / total


def route_with_plan(plan: RoutePlan, payload: jax.Array,
                    active: Optional[jax.Array] = None,
                    role: str = "req") -> Routed:
    """Issue one payload phase against a precomputed plan: a pure scatter
    (no sort) + ONE exchange.

    active, when given, must be a subset of the plan's valid mask; it is
    ANDed into the plan's occupancy by riding along as one extra payload
    word, so a shrinking probe-loop mask costs no extra exchange. Slot
    assignments are the plan's: inactive ops leave holes instead of
    compacting, which preserves the (src_rank, slot) serialization order of
    the surviving ops — reuse is bit-exact (DESIGN.md §2).
    """
    nranks, n = plan.dst_eff.shape
    cap = plan.cap
    if active is not None:
        payload = jnp.concatenate(
            [payload, active.astype(payload.dtype)[..., None]], axis=-1)

    def scatter_one(dst_eff_r, slot_r, pay_r):
        buf = jnp.zeros((nranks, cap) + pay_r.shape[1:], dtype=pay_r.dtype)
        # mode="drop" discards invalid (dst==nranks) and overflow (slot>=cap)
        return buf.at[dst_eff_r, slot_r].set(pay_r, mode="drop")

    buf = jax.vmap(scatter_one)(plan.dst_eff, plan.op_slot, payload)
    at_owner = exchange(buf, role)                 # (P_owner, P_src, cap, W')
    if active is not None:
        mask = plan.mask & (at_owner[..., -1] != 0)
        at_owner = at_owner[..., :-1]
        op_ok = plan.op_ok & active
    else:
        mask = plan.mask
        op_ok = plan.op_ok
    return Routed(at_owner=at_owner, mask=mask, op_slot=plan.op_slot,
                  op_ok=op_ok, dropped=plan.dropped)


def route(dst: jax.Array, payload: jax.Array, cap: int,
          valid: Optional[jax.Array] = None, role: str = "req") -> Routed:
    """Route op batches from all P origins to their owners (one phase).

    dst:     (P, n) destination ranks
    payload: (P, n, W) payload words
    valid:   (P, n) optional mask

    One-shot path: plan + payload phase fused (the plan is not returned).
    Loops issuing several phases to the same destinations should call
    `make_plan` once and `route_with_plan` per phase instead.
    """
    nranks = dst.shape[0]

    def one(dst_r, pay_r, val_r):
        return bin_by_dest(dst_r, pay_r, nranks, cap, val_r)

    if valid is None:
        valid = jnp.ones(dst.shape, dtype=bool)
    binned = jax.vmap(one)(dst, payload, valid)
    at_owner = exchange(binned.buf, role)          # (P_owner, P_src, cap, W)
    mask = exchange(binned.mask, role + "_mask")   # (P_owner, P_src, cap)
    return Routed(at_owner=at_owner, mask=mask, op_slot=binned.op_slot,
                  op_ok=binned.op_ok, dropped=binned.dropped)


def route_replies(routed: Routed, replies: jax.Array, dst: jax.Array,
                  role: str = "rep") -> jax.Array:
    """Return replies to origins and align them with the original op order.

    replies: (P_owner, P_src, cap, W) — owner-side, aligned with routed.at_owner
    dst:     (P, n) original destination ranks
    returns: (P, n, W) reply words per original op (garbage where ~op_ok)
    """
    with jax.named_scope("ds.reply"):
        back = exchange(replies, role)             # (P_origin, P_owner, cap, W)

        def gather_one(back_r, dst_r, slot_r):
            return back_r[dst_r, slot_r]           # (n, W)

        return jax.vmap(gather_one)(back, dst, routed.op_slot)


# ---------------------------------------------------------------------------
# Sender-side coalescing (DESIGN.md §6): dedup duplicate (dst, off)
# descriptor rows per origin BEFORE the exchange, so the request exchange,
# the owner apply lanes, and the reply exchange all operate on *distinct*
# rows. The structure is computed locally (one lexsort, ZERO extra
# exchanges); replies fan back out to every duplicate requester via `lead`.
#
# A *run* is a maximal group of ops from one origin that (a) target the
# same (dst, off), (b) agree on every `match` column, and (c) are
# consecutive once the batch is stably sorted by (dst, off). Ops of the
# same origin at the same (dst, off) occupy consecutive serialization
# slots at the owner (interleaved only with commuting other-offset ops),
# so combining a run and shipping one representative row preserves the
# (src_rank, slot) serialization contract bit-exactly; per-op replies are
# reconstructed sender-side (operand prefix for FAOs, the chained-CAS
# formula, the leader's reply for gets/puts).
# ---------------------------------------------------------------------------
def _suffix_min(x: jax.Array) -> jax.Array:
    return jax.lax.cummin(x, reverse=True)


def _prefix_max(x: jax.Array) -> jax.Array:
    return jax.lax.cummax(x)


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["rep", "leader", "pos", "order", "run_first",
                                "rows_in", "rows_out"],
                   meta_fields=[])
@dataclass
class Coalescing:
    """Duplicate-run structure for one batch (per-origin, sender-side).

    rep:       (P, n) op is its run's representative (first in op order)
    leader:    (P, n) op index (within n) of each op's representative
    pos:       (P, n) rank of the op within its run (0 == rep)
    order:     (P, n) the (dst, off)-stable sort permutation runs live in
    run_first: (P, n) run boundaries, in sorted space
    rows_in:   (P,)   valid rows before combining
    rows_out:  (P,)   representative rows after combining
    """

    rep: jax.Array
    leader: jax.Array
    pos: jax.Array
    order: jax.Array
    run_first: jax.Array
    rows_in: jax.Array
    rows_out: jax.Array

    def dedup_ratio(self) -> jax.Array:
        """Distinct-row fraction rows_out / rows_in over all origins."""
        tot = jnp.maximum(self.rows_in.sum(), 1)
        return self.rows_out.sum().astype(jnp.float32) / tot


def coalesce(dst: jax.Array, off: jax.Array,
             match: Optional[jax.Array] = None,
             valid: Optional[jax.Array] = None) -> Coalescing:
    """Find duplicate runs in a batch of (dst, off[, match]) descriptors.

    dst, off: (P, n) int32; match: optional (P, n, K) extra descriptor
    words that must ALL agree for two rows to share a run (CAS cmp/new,
    fused-descriptor payload words, ...). Invalid ops never join a run.
    Pure local compute — no exchange, one lexsort per origin.
    """
    nranks, n = dst.shape
    if valid is None:
        valid = jnp.ones(dst.shape, dtype=bool)

    def one(dst_r, off_r, match_r, valid_r):
        seq = jnp.arange(n, dtype=jnp.int32)
        dst_eff = jnp.where(valid_r, dst_r, nranks)
        off_eff = jnp.where(valid_r, off_r, -1)
        order = jnp.lexsort((seq, off_eff, dst_eff)).astype(jnp.int32)
        d_s, o_s, v_s = dst_eff[order], off_eff[order], valid_r[order]
        same = ((d_s[1:] == d_s[:-1]) & (o_s[1:] == o_s[:-1])
                & v_s[1:] & v_s[:-1])
        if match_r is not None:
            m_s = match_r[order]
            same = same & jnp.all(m_s[1:] == m_s[:-1], axis=-1)
        run_first = jnp.concatenate([jnp.array([True]), ~same])
        idx = jnp.arange(n, dtype=jnp.int32)
        run_start = _prefix_max(jnp.where(run_first, idx, -1))
        pos_s = idx - run_start
        leader_s = order[run_start]
        rep_s = run_first & v_s
        leader = jnp.zeros((n,), jnp.int32).at[order].set(leader_s)
        pos = jnp.zeros((n,), jnp.int32).at[order].set(pos_s)
        rep = jnp.zeros((n,), bool).at[order].set(rep_s)
        return rep, leader, pos, order, run_first

    if match is None:
        rep, leader, pos, order, run_first = jax.vmap(
            lambda d, o, v: one(d, o, None, v))(dst, off, valid)
    else:
        rep, leader, pos, order, run_first = jax.vmap(one)(
            dst, off, match, valid)
    return Coalescing(rep=rep, leader=leader, pos=pos, order=order,
                      run_first=run_first,
                      rows_in=valid.sum(axis=1).astype(jnp.int32),
                      rows_out=rep.sum(axis=1).astype(jnp.int32))


def lead(co: Coalescing, x: jax.Array) -> jax.Array:
    """Reply fan-out: every op reads its run representative's row of `x`.

    x: (P, n, ...) per-op values (meaningful at representative rows).
    Representatives read their own row; duplicates read their leader's.
    """
    return jax.vmap(lambda xr, lr: xr[lr])(x, co.leader)


def coalesce_fold(co: Coalescing, operand: jax.Array, binop,
                  identity) -> Tuple[jax.Array, jax.Array]:
    """Associative-fold duplicate runs of `operand` (P, n).

    Returns (combined, prefix): `combined` carries each run's total fold at
    its representative row (other rows unchanged — they are never shipped);
    `prefix[i]` is the exclusive fold of the op's EARLIER run members
    (identity at representatives), so per-op old values reconstruct as
    binop(owner_old_at_rep, prefix) — exactly the value each duplicate
    would have fetched had it been shipped separately.
    """
    n = operand.shape[1]

    def one(order, run_first, op_r):
        op_s = op_r[order]
        incl = segmented_scan(binop, run_first, op_s)
        ident = jnp.full_like(op_s, identity)
        excl = jnp.where(run_first, ident, jnp.roll(incl, 1))
        idx = jnp.arange(n, dtype=jnp.int32)
        run_last = jnp.concatenate([run_first[1:], jnp.array([True])])
        end = _suffix_min(jnp.where(run_last, idx, n - 1))
        combined_s = jnp.where(run_first, incl[end], op_s)
        combined = jnp.zeros_like(op_r).at[order].set(combined_s)
        prefix = jnp.zeros_like(op_r).at[order].set(excl)
        return combined, prefix

    return jax.vmap(one)(co.order, co.run_first, operand)


def coalesce_last(co: Coalescing, vals: jax.Array) -> jax.Array:
    """Last-writer-wins combine for put payloads: each representative row
    is replaced by the LAST value of its run (what the serialized owner
    apply would have left); other rows are unchanged (never shipped)."""
    n = vals.shape[1]

    def one(order, run_first, vals_r):
        idx = jnp.arange(n, dtype=jnp.int32)
        run_last = jnp.concatenate([run_first[1:], jnp.array([True])])
        end = _suffix_min(jnp.where(run_last, idx, n - 1))
        vals_s = vals_r[order]
        out_s = jnp.where(run_first[:, None], vals_s[end], vals_s)
        return jnp.zeros_like(vals_r).at[order].set(out_s)

    return jax.vmap(one)(co.order, co.run_first, vals)


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["plan", "co"], meta_fields=[])
@dataclass
class CoalescedPlan:
    """A RoutePlan whose occupancy covers only duplicate-run
    representatives, plus the Coalescing structure that maps every op to
    its representative. Built ONCE per batch (one plan argsort + one
    coalescing lexsort, still ONE occupancy exchange — coalescing adds
    zero exchanges); probe loops pass their shrinking active mask per
    phase exactly as with a plain plan.

    Contract for reuse across phases: the caller's per-phase active mask
    must be RUN-UNIFORM (a run deactivates as a whole — e.g. the
    hash-table loops, where duplicates adopt their representative's
    outcome). Phase-local coalescing (`coalesce=True` on a window op
    without a CoalescedPlan) recomputes the runs per call and has no such
    requirement.
    """

    plan: RoutePlan
    co: Coalescing

    @property
    def cap(self) -> int:
        return self.plan.cap


def coalesce_plan(dst: jax.Array, off: jax.Array,
                  match: Optional[jax.Array] = None,
                  valid: Optional[jax.Array] = None,
                  cap: Optional[int] = None,
                  role: str = "plan") -> CoalescedPlan:
    """Coalescing + route plan for a batch: runs found on one local
    lexsort, plan occupancy exchanged ONCE for the representative rows
    only — the wire and the owner lanes see distinct rows from the first
    phase on."""
    with trace.span("plan"), jax.named_scope("ds.plan"):
        co = coalesce(dst, off, match=match, valid=valid)
        plan = make_plan(dst, valid=co.rep, cap=cap, role=role)
    return CoalescedPlan(plan=plan, co=co)


def miss_subset_plan(dst: jax.Array, off: jax.Array, hit: Optional[jax.Array],
                     match: Optional[jax.Array] = None,
                     valid: Optional[jax.Array] = None,
                     cap: Optional[int] = None,
                     role: str = "plan") -> CoalescedPlan:
    """`coalesce_plan` restricted to the cache-miss subset (DESIGN.md §8).

    `hit` is the origin-local hot-bucket cache's hit mask for the batch
    (None = no cache consulted — degenerates to `coalesce_plan` exactly).
    Cache hits are carved out of the plan's validity BEFORE the occupancy
    exchange, so the wire and owner lanes see only the misses; because
    `make_plan` shapes its occupancy by the valid mask, the resulting plan
    is bit-identical to one built for a batch that never contained the hit
    rows. Still ONE occupancy exchange; all-hit batches should skip the
    plan entirely (zero exchanges) — the caller's job, since building any
    plan costs the occupancy exchange."""
    with trace.span("plan"), jax.named_scope("ds.plan"):
        if hit is not None:
            hit = jnp.asarray(hit)
            valid = ~hit if valid is None else (jnp.asarray(valid) & ~hit)
        return coalesce_plan(dst, off, match=match, valid=valid, cap=cap,
                             role=role)


def flatten_owner_view(routed: Routed):
    """Flatten an owner's (P_src, cap) request grid into a serialized op list.

    The serialization order (src_rank, slot) is the deterministic order in
    which the owner's "NIC lane" applies conflicting atomics — the analogue
    of NIC arrival-order serialization on Aries.

    returns payload (P_owner, m, W), mask (P_owner, m) with m = P_src*cap.
    """
    p, s, c = routed.mask.shape
    flat = routed.at_owner.reshape(p, s * c, *routed.at_owner.shape[3:])
    mask = routed.mask.reshape(p, s * c)
    return flat, mask


def unflatten_owner_view(flat: jax.Array, p_src: int, cap: int) -> jax.Array:
    p = flat.shape[0]
    return flat.reshape(p, p_src, cap, *flat.shape[2:])
