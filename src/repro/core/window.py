"""PGAS symmetric window + one-sided (RDMA-style) component operations.

A `Window` is the TPU-native analogue of a registered RDMA memory region:
every rank owns row `r` of a `(P, L)` word array. Component ops are batched
per step (see DESIGN.md §2) and each op is ONE network phase:

    rdma_put   — 1 exchange  (origin → owner scatter; completion at phase end)
    rdma_get   — 2 exchanges (request → owner gather → reply)
    rdma_cas   — 2 exchanges (request → serialized apply → old values back)
    rdma_fao   — 2 exchanges (FAA / FOR / FAND / FXOR)

Conflicting atomics at an owner are applied in deterministic (src_rank, slot)
order — the analogue of NIC arrival-order serialization. The vectorized
appliers below implement that order exactly; `kernels/amo_apply.py` is the
TPU hot-path implementation of the same contract and `kernels/ref.py` is the
independently written sequential oracle both are tested against.

Guarantees shared by every `rdma_*` op (the public one-sided API):

- tracer-safe: pure JAX on the array arguments — stage freely under
  `jax.jit` / `vmap` / `scan` (the diagnostic phase log records at trace
  time only, and coalescing stats degrade gracefully under tracing);
- plan reuse (`plan=`, DESIGN.md §2) and sender-side coalescing
  (`coalesce=True`, §6) are bit-exact vs. the plain phase — same
  serialization positions, same visible replies, same window state;
- reply words of invalid/undelivered ops are garbage by contract (callers
  mask with their own valid/delivered flags — the data-structure layer
  converts this to the zeros-when-failed contract of
  tests/test_conformance.py); put completion is phase-end (flush model).
"""
from __future__ import annotations

import contextlib
import functools
import weakref
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import faults as flt
from . import routing
from . import trace
from ..kernels.scan import segmented_scan
from .trace import drain_phase_log  # noqa: F401  (the log's public name)
from .types import AmoKind

Array = jax.Array

# ---------------------------------------------------------------------------
# Decision tagging: the adaptive layer (core/adaptive.py) wraps the RDMA
# arms it executes in `decision_scope(dec)`; every routed phase issued
# inside the scope is logged as (role, decision) so benchmarks can attribute
# phases to the arm that issued them. Logging happens at trace time — the
# adaptive layer dispatches arms at the Python level, once per batch. The
# log is `trace`'s bounded phase-log ring, drained by `drain_phase_log`.
# ---------------------------------------------------------------------------
_CURRENT_DECISION = None
# Pipeline slot tagging (DESIGN.md §7): core/pipeline.py wraps each staged
# batch in `slot_scope(slot, seq)` so every routed phase is attributable to
# the in-flight window slot that issued it.
_CURRENT_SLOT: Optional[Tuple[int, int]] = None
# Hot-bucket cache tagging (DESIGN.md §8): the adaptive layer wraps cached
# table ops in `cache_scope(cache)`; publish-capable phases notify the
# active cache of concrete publish flips (precision invalidation) and the
# cache logs fill/invalidate events into the same phase log.
_CURRENT_CACHE = None
# Pipelines with unforced in-flight batches (DESIGN.md §7/§9): while any
# exist, host-side cache maintenance between submits must stay
# opportunistic — core/cache.BucketCache.drain_fills consults this so a
# deferred-fill drain never blocks on an in-flight window's device values
# (which would serialize the very overlap the pipeline exists to create).
# WeakSet: an abandoned pipeline can never wedge the drain into
# non-blocking mode forever.
_INFLIGHT_PIPES: "weakref.WeakSet" = weakref.WeakSet()


def note_pipeline_inflight(pipe, active: bool) -> None:
    """Record whether `pipe` currently holds unforced in-flight batches
    (called by core/pipeline.Pipeline on every in-flight transition)."""
    if active:
        _INFLIGHT_PIPES.add(pipe)
    else:
        _INFLIGHT_PIPES.discard(pipe)


def pipeline_inflight() -> bool:
    """True while ANY pipeline holds unforced in-flight batches."""
    return len(_INFLIGHT_PIPES) > 0


@contextlib.contextmanager
def decision_scope(decision):
    global _CURRENT_DECISION
    prev = _CURRENT_DECISION
    _CURRENT_DECISION = decision
    try:
        yield
    finally:
        _CURRENT_DECISION = prev


@contextlib.contextmanager
def slot_scope(slot: int, seq: int):
    """Tag every phase issued inside the scope with its pipeline slot.

    `slot` is the in-flight window slot (0 .. depth-1, double-buffered at
    the default depth 2); `seq` is the submission sequence number of the
    batch. Entries land in the same bounded phase log as `decision_scope`
    with {"slot", "seq"} merged into the info dict — trace-time only, like
    decision tagging (a jitted batch logs on its first trace)."""
    global _CURRENT_SLOT
    prev = _CURRENT_SLOT
    _CURRENT_SLOT = (int(slot), int(seq))
    try:
        yield
    finally:
        _CURRENT_SLOT = prev


@contextlib.contextmanager
def cache_scope(cache):
    """Make `cache` (core/cache.BucketCache) the active hot-bucket cache.

    Inside the scope, publish-capable phases (`rdma_cas_put_publish`,
    `rdma_cas_put`, FXOR `rdma_fao`) forward concrete (dst, off) flips to
    `cache.on_publish` — the precision invalidation channel; the cache
    itself logs cache_fill / cache_hit / cache_invalidate events into the
    phase log via `log_cache_event`. Cache hits issue NO phases — the
    zero-exchange property tests/test_cache.py pins."""
    global _CURRENT_CACHE
    prev = _CURRENT_CACHE
    _CURRENT_CACHE = cache
    try:
        yield
    finally:
        _CURRENT_CACHE = prev


def log_cache_event(role: str, info: Optional[dict] = None) -> None:
    """Log one cache event into the phase log (same tagging rules as
    `_route_phase`: only while a decision/slot scope is active). Cache
    events are NOT network phases — diagnostics count exchanges by the
    routing hook, so these entries never inflate phase counts."""
    if _CURRENT_DECISION is None and _CURRENT_SLOT is None:
        return
    merged = dict(info or {})
    if _CURRENT_SLOT is not None:
        merged["slot"], merged["seq"] = _CURRENT_SLOT
    trace.log_phase(role, _CURRENT_DECISION, merged or None)


def _notify_publish(dst: Array, off: Array,
                    valid: Optional[Array]) -> None:
    """Forward a publish flip to the active cache (no-op without one).
    Tracer args degrade inside on_publish to the conservative channel."""
    if _CURRENT_CACHE is not None:
        _CURRENT_CACHE.on_publish(dst, off, valid)


def _coalesce_info(co: Optional[routing.Coalescing]) -> Optional[dict]:
    """Coalescing stats of one phase, kept on the device: the log reads
    them at `drain_phase_log`, never on the hot path."""
    if co is None:
        return None
    if isinstance(co.rows_in, jax.core.Tracer):  # stats stay in the trace
        return {"coalesced": True}
    return {"coalesced": True, "rows_in": co.rows_in,
            "rows_out": co.rows_out}


@functools.partial(jax.tree_util.register_dataclass, data_fields=["data"],
                   meta_fields=[])
@dataclass
class Window:
    """Symmetric PGAS window: rank r owns data[r]. Word-addressed."""

    data: Array  # (P, L)

    @property
    def nranks(self) -> int:
        return self.data.shape[0]

    @property
    def local_size(self) -> int:
        return self.data.shape[1]


def make_window(nranks: int, local_size: int, dtype=jnp.int32,
                fill=0, sharding=None) -> Window:
    """A (nranks, local_size) window. `sharding` places the rank rows
    across devices (e.g. a NamedSharding over a mesh axis); by default
    the window sits on the default device."""
    return Window(data=jnp.full((nranks, local_size), fill, dtype=dtype,
                                device=sharding))


# ---------------------------------------------------------------------------
# Owner-side appliers (shard-local, vmapped over owners).
# All take a *serialized* op list: ops earlier in the list happen first.
# ---------------------------------------------------------------------------
def _segmented_combine(off_sorted, vals_sorted, init_vals, binop, identity):
    """Segmented exclusive scan over same-offset groups (sorted by offset).

    Returns (old_per_op_sorted, final_value_per_group_positions, is_last).
    old_i = init ⊕ (operands of earlier ops at the same offset).
    """
    n = off_sorted.shape[0]
    is_first = jnp.concatenate([jnp.array([True]),
                                off_sorted[1:] != off_sorted[:-1]])
    is_last = jnp.concatenate([off_sorted[1:] != off_sorted[:-1],
                               jnp.array([True])])

    incl = segmented_scan(binop, is_first, vals_sorted)
    ident = jnp.full_like(vals_sorted, identity)
    excl = jnp.where(is_first, ident, jnp.roll(incl, 1))
    old = binop(init_vals, excl)
    final = binop(init_vals, incl)
    return old, final, is_last


_FAO_BINOPS = {
    int(AmoKind.FAA): (lambda a, b: a + b, 0),
    int(AmoKind.FOR): (lambda a, b: a | b, 0),
    int(AmoKind.FAND): (lambda a, b: a & b, -1),
    int(AmoKind.FXOR): (lambda a, b: a ^ b, 0),
}


def apply_fao_local(local: Array, off: Array, operand: Array, mask: Array,
                    kind: int) -> Tuple[Array, Array]:
    """Apply a homogeneous batch of fetch-and-op atomics to a local shard.

    local: (L,), off/operand/mask: (m,) in serialized order.
    Returns (old_per_op, new_local). Masked ops are no-ops returning 0.
    """
    L = local.shape[0]
    binop, identity = _FAO_BINOPS[int(kind)]
    ident = jnp.asarray(identity, dtype=local.dtype)
    off_eff = jnp.where(mask, off, L)  # sentinel → dropped by scatter
    operand_eff = jnp.where(mask, operand, ident)
    seq = jnp.arange(off.shape[0])
    order = jnp.lexsort((seq, off_eff))
    off_s, op_s = off_eff[order], operand_eff[order]
    init_vals = local.at[off_s].get(mode="fill", fill_value=0)
    old_s, final_s, is_last = _segmented_combine(off_s, op_s, init_vals,
                                                 binop, ident)
    new_local = local.at[jnp.where(is_last, off_s, L)].set(final_s,
                                                           mode="drop")
    old = jnp.zeros_like(old_s).at[order].set(old_s)
    return jnp.where(mask, old, 0), new_local


def apply_cas_local(local: Array, off: Array, cmp: Array, new: Array,
                    mask: Array) -> Tuple[Array, Array]:
    """Serialized batch of CAS ops against a local shard.

    Exact chained semantics (op k sees the value left by ops <k at the same
    offset) via a length-m sequential scan — the XLA analogue of the NIC's
    serialized AMO pipeline. m is small (P*cap); the TPU hot path is the
    amo_apply Pallas kernel.
    """
    L = local.shape[0]
    m = off.shape[0]
    off_eff = jnp.where(mask, off, L)
    seq = jnp.arange(m)
    order = jnp.lexsort((seq, off_eff))
    off_s, cmp_s, new_s = off_eff[order], cmp[order], new[order]
    is_first = jnp.concatenate([jnp.array([True]), off_s[1:] != off_s[:-1]])
    init_vals = local.at[off_s].get(mode="fill", fill_value=0)

    def step(carry, x):
        prev_val = carry
        first, init_v, c, nw = x
        cur = jnp.where(first, init_v, prev_val)
        nxt = jnp.where(cur == c, nw, cur)
        return nxt, (cur, nxt)

    _, (old_s, val_s) = jax.lax.scan(step, jnp.zeros((), local.dtype),
                                     (is_first, init_vals, cmp_s, new_s))
    is_last = jnp.concatenate([off_s[1:] != off_s[:-1], jnp.array([True])])
    new_local = local.at[jnp.where(is_last, off_s, L)].set(val_s, mode="drop")
    old = jnp.zeros_like(old_s).at[order].set(old_s)
    return jnp.where(mask, old, 0), new_local


def apply_put_local(local: Array, off: Array, vals: Array,
                    mask: Array) -> Array:
    """Last-writer-wins vector puts. off addresses word 0 of a V-word row."""
    L = local.shape[0]
    m, V = vals.shape
    off_eff = jnp.where(mask, off, L)
    seq = jnp.arange(m)
    order = jnp.lexsort((seq, off_eff))
    off_s, vals_s = off_eff[order], vals[order]
    is_last = jnp.concatenate([off_s[1:] != off_s[:-1], jnp.array([True])])
    row = jnp.where(is_last, off_s, L)[:, None] + jnp.arange(V)[None, :]
    return local.at[row].set(vals_s, mode="drop")


def gather_local(local: Array, off: Array, width: int) -> Array:
    idx = off[:, None] + jnp.arange(width)[None, :]
    return local.at[idx].get(mode="fill", fill_value=0)


# ---------------------------------------------------------------------------
# One-sided phases (the public RDMA-style API).
#
# Every phase accepts an optional precomputed RoutePlan (routing.make_plan):
# probe loops that issue `max_probes + 2` phases to fixed destinations build
# ONE plan per batch and each phase becomes a pure scatter + one exchange,
# with the (possibly shrinking) `valid` mask ANDed into the plan occupancy —
# bit-exact reuse (DESIGN.md §2).
# ---------------------------------------------------------------------------
def _default_cap(dst: Array, cap: Optional[int]) -> int:
    return dst.shape[1] if cap is None else cap


def _phase_info(co: Optional[routing.Coalescing]) -> Optional[dict]:
    """Info dict for one logged phase: coalescing stats + pipeline slot."""
    info = _coalesce_info(co)
    if _CURRENT_SLOT is not None:
        info = dict(info or {})
        info["slot"], info["seq"] = _CURRENT_SLOT
    return info


def _route_phase(dst: Array, payload: Array, cap: int,
                 valid: Optional[Array],
                 plan: Optional[routing.RoutePlan],
                 role: str,
                 co: Optional[routing.Coalescing] = None) -> routing.Routed:
    if _CURRENT_DECISION is not None or _CURRENT_SLOT is not None:
        trace.log_phase(role, _CURRENT_DECISION, _phase_info(co))
    plane = flt.active_plane()
    if plane is not None:
        # DESIGN.md §10: the fault plane simulates wire loss + origin
        # retransmit + owner dedup INSIDE this phase; rows that never
        # deliver are masked out of the effective valid (`valid` comes
        # back unchanged when every row survives — the common case).
        valid = plane.inject_phase(role, dst, valid)
    if plan is None:
        return routing.route(dst, payload, cap, valid, role=role)
    # valid=None -> active=None: reuse the plan occupancy as-is instead of
    # shipping an all-ones activity word
    return routing.route_with_plan(plan, payload, active=valid, role=role)


def _coalesce_for(plan, coalesce: bool, dst: Array, off: Array,
                  match: Optional[Array], valid: Optional[Array]):
    """Resolve the coalescing structure for one phase (DESIGN.md §6).

    plan may be a RoutePlan, a CoalescedPlan (its precomputed runs are
    reused — caller guarantees the active mask is run-uniform), or None.
    coalesce=True without a CoalescedPlan computes fresh runs for THIS
    phase (one local lexsort, zero exchanges) — exact under any mask.
    Returns (base_plan, co, eff_valid) where eff_valid restricts the
    phase to representative rows."""
    if isinstance(plan, routing.CoalescedPlan):
        co, plan = plan.co, plan.plan
    elif coalesce:
        co = routing.coalesce(dst, off, match=match, valid=valid)
    else:
        return plan, None, valid
    eff = co.rep if valid is None else (valid & co.rep)
    return plan, co, eff


def rdma_put(win: Window, dst: Array, off: Array, vals: Array,
             valid: Optional[Array] = None, cap: Optional[int] = None,
             plan: Optional[routing.RoutePlan] = None,
             coalesce: bool = False) -> Window:
    """One-sided put: vals (P, n, V) written at word offsets off on rank dst.

    ONE network phase. Completion semantics: remote-complete at phase end
    (the paper's put is likewise only guaranteed complete at the next flush).
    coalesce=True dedups duplicate (dst, off) rows sender-side
    (last-writer-wins — bit-exact, DESIGN.md §6).
    """
    plan, co, eff_valid = _coalesce_for(plan, coalesce, dst, off, None,
                                        valid)
    cap = plan.cap if plan is not None else _default_cap(dst, cap)
    V = vals.shape[-1]
    vals = vals.astype(jnp.int32)
    if co is not None:
        vals = routing.coalesce_last(co, vals)
    payload = jnp.concatenate([off[..., None].astype(jnp.int32), vals],
                              axis=-1)
    routed = _route_phase(dst, payload, cap, eff_valid, plan, role="put",
                          co=co)
    flat, mask = routing.flatten_owner_view(routed)
    offs, vwords = flat[..., 0], flat[..., 1:1 + V]
    with jax.named_scope("ds.owner_apply"):
        new_data = jax.vmap(apply_put_local)(win.data, offs, vwords, mask)
    return Window(data=new_data)


def rdma_get(win: Window, dst: Array, off: Array, width: int,
             valid: Optional[Array] = None, cap: Optional[int] = None,
             plan: Optional[routing.RoutePlan] = None,
             coalesce: bool = False) -> Array:
    """One-sided get of `width` words: TWO exchanges (request, data back).

    coalesce=True probes each duplicate (dst, off) ONCE and fans the reply
    out to every duplicate requester (bit-exact, DESIGN.md §6)."""
    plan, co, eff_valid = _coalesce_for(plan, coalesce, dst, off, None,
                                        valid)
    cap = plan.cap if plan is not None else _default_cap(dst, cap)
    payload = off[..., None].astype(jnp.int32)
    routed = _route_phase(dst, payload, cap, eff_valid, plan, role="get",
                          co=co)
    flat, mask = routing.flatten_owner_view(routed)

    def owner_gather(local, offs, m):
        vals = gather_local(local, offs, width)
        return jnp.where(m[:, None], vals, 0)

    with jax.named_scope("ds.owner_apply"):
        vals = jax.vmap(owner_gather)(win.data, flat[..., 0], mask)
    replies = routing.unflatten_owner_view(vals, win.nranks, cap)
    out = routing.route_replies(routed, replies, dst, role="get_rep")
    if co is not None:
        out = routing.lead(co, out)
    return out


def _use_kernel_lane() -> bool:
    """Route the owner-side AMO apply through the Pallas `amo_apply` kernel
    (the TPU hot path) instead of the vectorized XLA appliers above. Both
    implement the same serialized contract; tests assert equivalence."""
    from .. import kernels  # local import: kernels never imports core
    return kernels.ops.use_pallas_default()


def _kernel_amo(data: Array, flat: Array, mask: Array, kind: int,
                a_col: int, b_col: Optional[int]) -> Tuple[Array, Array]:
    from ..kernels import ops as kops
    m = flat.shape[1]
    zeros = jnp.zeros((data.shape[0], m), jnp.int32)
    ops_arr = jnp.stack(
        [flat[..., 0],
         jnp.full_like(zeros, int(kind)),
         flat[..., a_col],
         flat[..., b_col] if b_col is not None else zeros], axis=-1)
    return kops.amo_apply(data, ops_arr, mask, use_pallas=True)


def rdma_fao(win: Window, dst: Array, off: Array, operand: Array,
             kind: AmoKind, valid: Optional[Array] = None,
             cap: Optional[int] = None,
             plan: Optional[routing.RoutePlan] = None,
             coalesce: bool = False) -> Tuple[Array, Window]:
    """Fetch-and-op (FAA/FOR/FAND/FXOR): TWO exchanges, serialized apply.

    coalesce=True combines duplicate (dst, off) runs sender-side
    (operand fold) and reconstructs each duplicate's fetched value from
    the representative's reply plus its exclusive operand prefix —
    bit-exact with the uncoalesced serialized apply (DESIGN.md §6)."""
    if int(kind) == int(AmoKind.FXOR):
        _notify_publish(dst, off, valid)
    operand = jnp.broadcast_to(jnp.asarray(operand, jnp.int32), off.shape)
    plan, co, eff_valid = _coalesce_for(plan, coalesce, dst, off, None,
                                        valid)
    cap = plan.cap if plan is not None else _default_cap(dst, cap)
    binop, identity = _FAO_BINOPS[int(kind)]
    if co is not None:
        operand_wire, prefix = routing.coalesce_fold(co, operand, binop,
                                                     identity)
    else:
        operand_wire = operand
    payload = jnp.stack([off.astype(jnp.int32), operand_wire], axis=-1)
    routed = _route_phase(dst, payload, cap, eff_valid, plan, role="fao",
                          co=co)
    flat, mask = routing.flatten_owner_view(routed)

    def owner_apply(local, p, m):
        return apply_fao_local(local, p[:, 0], p[:, 1], m, int(kind))

    with jax.named_scope("ds.owner_apply"):
        if _use_kernel_lane():
            old_flat, new_data = _kernel_amo(win.data, flat, mask, int(kind),
                                             a_col=1, b_col=None)
        else:
            old_flat, new_data = jax.vmap(owner_apply)(win.data, flat, mask)
    replies = routing.unflatten_owner_view(old_flat[..., None], win.nranks,
                                           cap)
    old = routing.route_replies(routed, replies, dst, role="fao_rep")[..., 0]
    if co is not None:
        old = binop(routing.lead(co, old), prefix)
    return old, Window(data=new_data)


def rdma_cas(win: Window, dst: Array, off: Array, cmp: Array, new: Array,
             valid: Optional[Array] = None, cap: Optional[int] = None,
             plan: Optional[routing.RoutePlan] = None,
             coalesce: bool = False) -> Tuple[Array, Window]:
    """Compare-and-swap: TWO exchanges, serialized chained apply.

    coalesce=True ships one representative per run of IDENTICAL
    (dst, off, cmp, new) rows; duplicates short-circuit sender-side with
    the chained outcome (rep won -> they see `new`; rep lost -> they see
    the same old) — bit-exact (DESIGN.md §6)."""
    cmp = jnp.broadcast_to(jnp.asarray(cmp, jnp.int32), off.shape)
    new = jnp.broadcast_to(jnp.asarray(new, jnp.int32), off.shape)
    match = jnp.stack([cmp, new], axis=-1)
    plan, co, eff_valid = _coalesce_for(plan, coalesce, dst, off, match,
                                        valid)
    cap = plan.cap if plan is not None else _default_cap(dst, cap)
    payload = jnp.stack([off.astype(jnp.int32), cmp, new], axis=-1)
    routed = _route_phase(dst, payload, cap, eff_valid, plan, role="cas",
                          co=co)
    flat, mask = routing.flatten_owner_view(routed)

    def owner_apply(local, p, m):
        return apply_cas_local(local, p[:, 0], p[:, 1], p[:, 2], m)

    with jax.named_scope("ds.owner_apply"):
        if _use_kernel_lane():
            old_flat, new_data = _kernel_amo(win.data, flat, mask,
                                             int(AmoKind.CAS), a_col=1,
                                             b_col=2)
        else:
            old_flat, new_data = jax.vmap(owner_apply)(win.data, flat, mask)
    replies = routing.unflatten_owner_view(old_flat[..., None], win.nranks,
                                           cap)
    old = routing.route_replies(routed, replies, dst, role="cas_rep")[..., 0]
    if co is not None:
        old_l = routing.lead(co, old)
        old = jnp.where(co.pos == 0, old_l,
                        jnp.where(old_l == cmp, new, old_l))
    return old, Window(data=new_data)


# ---------------------------------------------------------------------------
# Fused component phases (DESIGN.md §2): composite one-phase remote ops in
# the style of Storm's composite RTTs / Active Access compound descriptors.
# Descriptor layout [off | kind | a | b | aux0 | aux1 | vals...]. The owner
# applies the batch in SUB-PHASE order — atomics, compound puts, publish
# flips, phase-end gathers, each serialized in (src_rank, slot) order —
# i.e. exactly the order the unfused engine's separate phases would apply,
# so fusion saves exchanges without changing observable state. The XLA lane
# below composes the existing vectorized appliers per sub-phase; the Pallas
# lane (kernels/ops.fused_apply) implements the same spec.
# ---------------------------------------------------------------------------
def _scatter_rows(local: Array, base: Array, vals: Array,
                  mask: Array) -> Array:
    """Scatter V-word rows at `base`, dropped whole when out of range.
    Rows must be mutually disjoint (the caller's contract) — with no
    overlaps a plain scatter IS the serialized last-writer-wins apply."""
    L = local.shape[0]
    V = vals.shape[-1]
    ok = mask & (base >= 0) & (base <= L - V)
    row = jnp.where(ok, base, L)[:, None] + jnp.arange(V)[None, :]
    return local.at[row].set(vals, mode="drop")


def apply_cas_put_local(local: Array, off: Array, cmp: Array, new: Array,
                        put_off: Array, vals: Array, flip: Array,
                        mask: Array) -> Tuple[Array, Array]:
    """Vectorized owner apply for a CAS_PUT / CAS_PUT_PUB batch — the fused
    hot path, ONE stable sort total (the seed path pays one per sub-phase):

      1. chained CAS sub-phase in serialized order (sorted-segment scan);
      2. winners' puts as one disjoint-row scatter (dropped whole when out
         of range);
      3. publish flips folded into the flag scatter: the post-CAS value at
         each offset XOR the winners' flips (XOR order is immaterial).

    flip=0 rows are plain CAS_PUT. Returns (old, local').

    Preconditions (engine batches satisfy them by construction: new != cmp
    so at most one winner per offset, winners claim distinct slots, put
    rows are record words while CAS/flip targets are flag words): winners'
    put rows are mutually disjoint and never cover other descriptors'
    `off` words. The generic lanes (kernels/ref.fused_apply, the Pallas
    kernel) are the spec for adversarial overlaps."""
    L, V = local.shape[0], vals.shape[-1]
    m = off.shape[0]
    off_eff = jnp.where(mask, off, L)
    order = jnp.argsort(off_eff, stable=True)
    off_s, cmp_s, new_s = off_eff[order], cmp[order], new[order]
    is_first = jnp.concatenate([jnp.array([True]), off_s[1:] != off_s[:-1]])
    is_last = jnp.concatenate([off_s[1:] != off_s[:-1], jnp.array([True])])
    init_vals = local.at[off_s].get(mode="fill", fill_value=0)

    def step(carry, x):
        prev_val = carry
        first, init_v, c, nw = x
        cur = jnp.where(first, init_v, prev_val)
        nxt = jnp.where(cur == c, nw, cur)
        return nxt, (cur, nxt)

    _, (old_s, val_s) = jax.lax.scan(step, jnp.zeros((), local.dtype),
                                     (is_first, init_vals, cmp_s, new_s))
    win_s = old_s == cmp_s

    # publish flips: segmented XOR of winners' flips, folded into the final
    # flag value at each offset's last slot
    flip_contrib = jnp.where(win_s, flip[order], 0)

    xor_incl = segmented_scan(jnp.bitwise_xor, is_first,
                                      flip_contrib)
    flag_final = val_s ^ xor_incl
    new_local = local.at[jnp.where(is_last, off_s, L)].set(flag_final,
                                                           mode="drop")

    old = jnp.zeros_like(old_s).at[order].set(old_s)
    old = jnp.where(mask, old, 0)
    win = mask & (old == cmp)
    new_local = _scatter_rows(new_local, put_off, vals, win)
    return old, new_local


def apply_fao_get_local(local: Array, off: Array, operand: Array, kind: int,
                        get_off: Array, width: int, mask: Array
                        ) -> Tuple[Array, Array, Array]:
    """Vectorized owner apply for a FAO_GET batch: serialized fetch-and-op
    sub-phase (one stable sort + segmented combine), then a phase-end
    gather of `width` words from get_off.
    Returns (old, gathered (m, width), local')."""
    L = local.shape[0]
    binop, identity = _FAO_BINOPS[int(kind)]
    ident = jnp.asarray(identity, dtype=local.dtype)
    off_eff = jnp.where(mask, off, L)
    operand_eff = jnp.where(mask, operand, ident)
    order = jnp.argsort(off_eff, stable=True)
    off_s, op_s = off_eff[order], operand_eff[order]
    init_vals = local.at[off_s].get(mode="fill", fill_value=0)
    old_s, final_s, is_last = _segmented_combine(off_s, op_s, init_vals,
                                                 binop, ident)
    new_local = local.at[jnp.where(is_last, off_s, L)].set(final_s,
                                                           mode="drop")
    old = jnp.zeros_like(old_s).at[order].set(old_s)
    rec = gather_local(new_local, get_off, width)
    return (jnp.where(mask, old, 0), jnp.where(mask[:, None], rec, 0),
            new_local)


def _fused_phase(win: Window, dst: Array, desc: Array, reply_width: int,
                 valid: Optional[Array], cap: Optional[int],
                 plan: Optional[routing.RoutePlan], role: str,
                 xla_apply,
                 co: Optional[routing.Coalescing] = None
                 ) -> Tuple[Array, Window]:
    """Route one fused-descriptor phase and apply it at the owners.

    xla_apply(data, flat, mask) -> (reply_flat, data') is the vectorized
    XLA owner lane for this (homogeneous) descriptor batch; the Pallas lane
    goes through the generic kernels/ops.fused_apply. When `co` is given,
    `valid` must already be restricted to representative rows; the raw
    reply is fanned out to every duplicate requester (per-op fixups are
    the caller's job)."""
    cap = plan.cap if plan is not None else _default_cap(dst, cap)
    routed = _route_phase(dst, desc, cap, valid, plan, role=role, co=co)
    flat, mask = routing.flatten_owner_view(routed)
    with jax.named_scope("ds.owner_apply"):
        if _use_kernel_lane():
            from ..kernels import ops as kops
            reply_flat, new_data = kops.fused_apply(
                win.data, flat, mask, reply_width=reply_width,
                use_pallas=True)
        else:
            reply_flat, new_data = xla_apply(win.data, flat, mask)
    replies = routing.unflatten_owner_view(reply_flat, win.nranks, cap)
    out = routing.route_replies(routed, replies, dst, role=role + "_rep")
    if co is not None:
        out = routing.lead(co, out)
    return out, Window(data=new_data)


def _desc(off: Array, kind: int, a: Array, b: Array, aux0: Array,
          aux1: Array, vals: Optional[Array]) -> Array:
    cols = [off.astype(jnp.int32),
            jnp.full(off.shape, int(kind), jnp.int32),
            jnp.broadcast_to(jnp.asarray(a, jnp.int32), off.shape),
            jnp.broadcast_to(jnp.asarray(b, jnp.int32), off.shape),
            jnp.broadcast_to(jnp.asarray(aux0, jnp.int32), off.shape),
            jnp.broadcast_to(jnp.asarray(aux1, jnp.int32), off.shape)]
    head = jnp.stack(cols, axis=-1)
    if vals is None:
        return head
    return jnp.concatenate([head, vals.astype(jnp.int32)], axis=-1)


def _cas_put_xla_apply(data, flat, mask):
    V = flat.shape[-1] - 6

    def one(local, p, m):
        old, local2 = apply_cas_put_local(
            local, p[:, 0], p[:, 2], p[:, 3], p[:, 4], p[:, 6:6 + V],
            p[:, 5], m)
        return old[:, None], local2

    return jax.vmap(one)(data, flat, mask)


def rdma_cas_put(win: Window, dst: Array, off: Array, cmp: Array, new: Array,
                 put_off: Array, vals: Array,
                 valid: Optional[Array] = None, cap: Optional[int] = None,
                 plan: Optional[routing.RoutePlan] = None,
                 coalesce: bool = False) -> Tuple[Array, Window]:
    """Fused claim + record write: CAS(cmp->new) at `off`; on success the
    V-word `vals` row lands at `put_off` — ONE request phase + reply (the
    C_W insert's probes×A_CAS + W collapsed into probes×A_CAS_PUT).
    Returns (old-at-off, win').

    coalesce=True dedups runs of IDENTICAL descriptors (first-wins: one
    claim ships, duplicates short-circuit with the chained outcome)."""
    _notify_publish(dst, off, valid)
    desc = _desc(off, AmoKind.CAS_PUT, cmp, new, put_off, 0, vals)
    plan, co, eff_valid = _coalesce_for(plan, coalesce, dst, off,
                                        desc[..., 2:], valid)
    old, win2 = _fused_phase(win, dst, desc, 1, eff_valid, cap, plan,
                             role="cas_put", xla_apply=_cas_put_xla_apply,
                             co=co)
    old = old[..., 0]
    if co is not None:
        old = jnp.where(co.pos == 0, old,
                        jnp.where(old == desc[..., 2], desc[..., 3], old))
    return old, win2


def rdma_cas_put_publish(win: Window, dst: Array, off: Array, cmp: Array,
                         new: Array, put_off: Array, vals: Array,
                         flip: Array, valid: Optional[Array] = None,
                         cap: Optional[int] = None,
                         plan: Optional[routing.RoutePlan] = None,
                         coalesce: bool = False) -> Tuple[Array, Window]:
    """Fused claim + record write + publish: CAS(cmp->new) at `off`; on
    success write `vals` at `put_off` and flip mem[off] ^= `flip` — the
    C_RW insert's three logical ops (A_CAS + W + A_FAO) in TWO exchanges.
    Returns (old-at-off, win').

    coalesce=True dedups runs of IDENTICAL descriptors: one claim (and one
    publish flip) ships per run, duplicates short-circuit with the chained
    outcome sender-side (DESIGN.md §6)."""
    _notify_publish(dst, off, valid)
    desc = _desc(off, AmoKind.CAS_PUT_PUB, cmp, new, put_off, flip, vals)
    plan, co, eff_valid = _coalesce_for(plan, coalesce, dst, off,
                                        desc[..., 2:], valid)
    old, win2 = _fused_phase(win, dst, desc, 1, eff_valid, cap, plan,
                             role="cas_put_pub",
                             xla_apply=_cas_put_xla_apply, co=co)
    old = old[..., 0]
    if co is not None:
        old = jnp.where(co.pos == 0, old,
                        jnp.where(old == desc[..., 2], desc[..., 3], old))
    return old, win2


def rdma_txn_commit(win: Window, dst: Array, desc: Array,
                    valid: Optional[Array] = None,
                    cap: Optional[int] = None,
                    plan: Optional[routing.RoutePlan] = None
                    ) -> Tuple[Array, Window]:
    """Transactional commit phase (DESIGN.md §11): ship per-txn groups of
    primitive ops and apply them all-or-nothing at the owners.

    desc (P, n, 6) int32 rows [off | code | a | b | gid | chain]: codes
    0-6 as in the AMO lane, gid is the committing source rank (the group
    id — `flatten_owner_view`'s (src_rank, slot) order keeps groups
    contiguous at every owner), and chain != 0 marks an OP_CAS row as a
    chain guard whose compare failure aborts the whole group owner-side
    (the abort-mask lane of kernels/ops.txn_group_apply). TWO exchanges:
    request + [old-at-apply | applied] replies — intra-txn
    read-after-write is visible in the reply words because they come from
    the serialized apply itself, not from an earlier read phase.

    NEVER coalesced: like the queue-push payload phase, every commit row
    must apply exactly once (rows of different txns are distinct by gid,
    so there is nothing bit-exact to combine).
    """
    cap = plan.cap if plan is not None else _default_cap(dst, cap)
    routed = _route_phase(dst, desc.astype(jnp.int32), cap, valid, plan,
                          role="txn_commit")
    flat, mask = routing.flatten_owner_view(routed)
    from ..kernels import ops as kops
    with jax.named_scope("ds.owner_apply"):
        reply_flat, new_data = kops.txn_group_apply(win.data, flat, mask,
                                                    ngroups=win.nranks)
    replies = routing.unflatten_owner_view(reply_flat, win.nranks, cap)
    out = routing.route_replies(routed, replies, dst, role="txn_commit_rep")
    return out, Window(data=new_data)


def rdma_fao_get(win: Window, dst: Array, off: Array, operand: Array,
                 kind: AmoKind, get_off: Array, width: int,
                 valid: Optional[Array] = None, cap: Optional[int] = None,
                 plan: Optional[routing.RoutePlan] = None,
                 coalesce: bool = False) -> Tuple[Array, Array, Window]:
    """Fused fetch-and-op + gather: apply FAO(`operand`, `kind`) at `off`
    and return `width` words from `get_off` in the SAME request/reply pair —
    the C_RW find's read-lock + record get (A_FAO + R, 4 exchanges) in 2.
    The gather is a phase-end snapshot (it observes every atomic in the
    batch, like the unfused engine's trailing get phase would).
    Returns (old-at-off, gathered (P, n, width), win').

    coalesce=True combines duplicate (dst, off, get_off) runs: the shipped
    descriptor carries the folded operand, duplicates reconstruct their
    fetched value from the representative's reply + their operand prefix
    and share the (phase-end) gathered record — bit-exact."""
    assert int(kind) in (int(AmoKind.FAA), int(AmoKind.FOR),
                         int(AmoKind.FAND), int(AmoKind.FXOR))
    if int(kind) == int(AmoKind.FXOR):
        _notify_publish(dst, off, valid)
    operand = jnp.broadcast_to(jnp.asarray(operand, jnp.int32), off.shape)
    get_off_b = jnp.broadcast_to(jnp.asarray(get_off, jnp.int32), off.shape)
    match = get_off_b[..., None]
    plan, co, eff_valid = _coalesce_for(plan, coalesce, dst, off, match,
                                        valid)
    binop, identity = _FAO_BINOPS[int(kind)]
    if co is not None:
        operand_wire, prefix = routing.coalesce_fold(co, operand, binop,
                                                     identity)
    else:
        operand_wire = operand
    desc = _desc(off, AmoKind.FAO_GET, operand_wire, int(kind), get_off, 0,
                 None)

    def xla_apply(data, flat, mask):
        def one(local, p, m):
            old, rec, local2 = apply_fao_get_local(
                local, p[:, 0], p[:, 2], int(kind), p[:, 4], width, m)
            return jnp.concatenate([old[:, None], rec], axis=1), local2

        return jax.vmap(one)(data, flat, mask)

    reply, win2 = _fused_phase(win, dst, desc, 1 + width, eff_valid, cap,
                               plan, role="fao_get", xla_apply=xla_apply,
                               co=co)
    old = reply[..., 0]
    if co is not None:
        old = binop(old, prefix)
    return old, reply[..., 1:], win2
