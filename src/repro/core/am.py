"""Active-message (RPC) engine: aggregated request routing + local handlers.

The TPU-native realization of GASNet-EX style active messages (DESIGN.md §2):

- `dispatch` = ONE request exchange + arbitrary shard-local handler + ONE
  reply exchange. The number of network phases is *independent of the
  handler's control flow* — the paper's central RPC property. Routing,
  handler and reply run as one jitted program, compiled once per handler,
  shapes and flags and kept on the engine.
- Handlers obey the paper's AM restrictions by construction: they are pure
  shard-local JAX functions, so they cannot send further messages or touch
  the network.
- Attentiveness: an owner services requests only when its SPMD program
  reaches a dispatch point. The latency penalty of infrequent dispatch
  points is modeled in `costmodel.attentiveness_delay` and measured by the
  Fig. 6 benchmark; the engine itself is oblivious (as is GASNet's API).
"""
from __future__ import annotations

import collections
from dataclasses import dataclass
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from . import faults as flt
from . import routing
from . import trace

Array = jax.Array

# handler(state_row, payload (m, W) int32, mask (m,)) -> (state_row', reply (m, RW) int32)
HandlerFn = Callable[[Any, Array, Array], Tuple[Any, Array]]


@dataclass(frozen=True)
class Handler:
    """A registered active-message handler (paper Fig. 2's insert_handler).

    `batched_fn`, when provided, processes all owners' request grids at once
    — signature (state (P,...), payload (P,m,W), mask (P,m)) -> (state',
    replies (P,m,RW)) — and is the hook through which Pallas handler
    kernels (kernels/hash_probe.py) replace the vmapped per-row path.
    """

    name: str
    fn: HandlerFn
    reply_width: int  # int32 words returned per op (0 => no-reply AM)
    batched_fn: Optional[Callable[[Any, Array, Array],
                                  Tuple[Any, Array]]] = None


@dataclass(eq=False)
class _Program:
    """One cached dispatch program: the jitted core, the sharding hook it
    is traced under (held so that the hook's id in the key stays unique)
    and the number of times its Python body has been traced."""

    hook: Callable
    fn: Optional[Callable] = None
    traces: int = 0

    @property
    def count_key(self) -> tuple:
        """Key of the counts the core makes while traced (`trace.per_trip`).
        A program always traces before its first count is read, so an id
        reused after this program is gone is overwritten before use."""
        return ("am.dispatch", id(self))


def _first_tracer(tree) -> Optional[Array]:
    """The first jit tracer among the leaves of `tree`, or None: a
    dispatch called inside an outer trace is traced there, not run."""
    for x in jax.tree_util.tree_leaves(tree):
        if isinstance(x, jax.core.Tracer):
            return x
    return None


class AMEngine:
    """Handler registry + dispatch. One engine per distributed structure."""

    def __init__(self, nranks: int):
        self.nranks = nranks
        self._handlers: dict[str, Handler] = {}
        # Deferred-dispatch queue (DESIGN.md §7): AM batches submitted
        # through the pipeline engine wait here until the next *dispatch
        # point* — the paper's attentiveness, made an explicit queue. The
        # pipeline drains it whenever it enters the engine (an eager
        # submit, a Handle.result(), a flush), so AM service latency is
        # exactly the time to the next overlap window.
        self._pending: collections.deque = collections.deque()
        # dispatch points entered (drains, including empty ones): together
        # with the inter-submit busy_wait knob this makes attentiveness a
        # measurable quantity (benchmarks/pipeline_bench.py).
        self.dispatch_points = 0
        # compiled dispatch programs, by `_program`'s key
        self._programs: dict[tuple, _Program] = {}

    # -- deferred dispatch (pipeline integration, DESIGN.md §7) ------------
    @property
    def pending_dispatches(self) -> int:
        """Queued dispatch thunks awaiting the next dispatch point."""
        return len(self._pending)

    def queue_dispatch(self, thunk) -> None:
        """Enqueue a zero-arg dispatch thunk for the next dispatch point.

        Thunks run FIFO at `drain_dispatch_queue`; the engine stays
        oblivious to what they do (they typically call `dispatch` and stash
        the replies — see core/pipeline.py). Queueing models the paper's
        attentiveness liability: remote progress happens only when the
        target enters the runtime."""
        self._pending.append(thunk)

    def drain_dispatch_queue(self) -> int:
        """Enter a dispatch point: service every queued dispatch, FIFO.

        Returns the number of dispatches serviced. Counted in
        `dispatch_points` whether or not anything was pending (an attentive
        target polls on every entry).

        Under an active FaultPlan (DESIGN.md §10) each call is one AM
        service opportunity: the plane's round clock ticks, and while the
        plan stalls the queue (`stall_rounds` / `stall_forever` — the
        paper's inattentive owner taken to its limit) the queue does NOT
        drain and no dispatch point is counted (the owner never entered
        the runtime)."""
        plane = flt.active_plane()
        if plane is not None:
            stalled = plane.queue_stalled()
            plane.tick()
            if stalled:
                plane.stall_hits += 1
                return 0
        self.dispatch_points += 1
        count = len(self._pending)
        while self._pending:
            self._pending.popleft()()
        return count

    def register(self, name: str, fn: HandlerFn, reply_width: int,
                 batched_fn=None) -> Handler:
        if name in self._handlers:
            raise ValueError(f"handler {name!r} already registered")
        h = Handler(name=name, fn=fn, reply_width=reply_width,
                    batched_fn=batched_fn)
        self._handlers[name] = h
        return h

    def has_handler(self, name: str) -> bool:
        """Registration check for layers that lazily install handlers on a
        shared engine (e.g. core/txn.TxnEngine) — `register` raises on
        duplicates by design."""
        return name in self._handlers

    def handler(self, name: str) -> Handler:
        return self._handlers[name]

    def dispatch(self, handler: Handler, state: Any, dst: Array,
                 payload: Array, valid: Optional[Array] = None,
                 cap: Optional[int] = None,
                 plan: Optional[routing.RoutePlan] = None,
                 coalesce: bool = False
                 ) -> Tuple[Any, Array, Array]:
        """Issue one aggregated AM phase for a batch of requests.

        state:   pytree whose leaves have leading axis P (owner rows)
        dst:     (P, n) target ranks
        payload: (P, n, W) int32 request words
        plan:    optional precomputed RoutePlan (routing.make_plan) — callers
                 issuing repeated dispatches to fixed destinations reuse one
                 plan per batch and skip the per-dispatch routing sort
        coalesce: dedup IDENTICAL request rows to the same destination
                 sender-side — the handler sees one combined row per
                 duplicate run and its reply fans out to every duplicate
                 requester (DESIGN.md §6). Only valid for handlers that are
                 idempotent across identical requests (the hash-table
                 insert-or-assign and find handlers are; a queue push is
                 NOT — each identical push must land separately).
        returns (state', replies (P, n, RW), delivered (P, n)).

        Exactly two network phases regardless of handler complexity; for
        reply_width == 0 a single phase (the origin-side completion counter
        is derivable locally from `delivered`, matching the paper's
        counter-increment reply elision).

        The fault plane acts on the host at every call; the rest (routing,
        handler, reply) is one jitted program per (handler, cap, coalesce,
        plan given, valid given, sharding hook), kept on the engine and
        reused for every batch of the same shapes. Traced as the span
        `ds.am.dispatch`; counts `am_dispatches` per call run and
        `am_dispatch_traces` per call that traced its program (neither
        inside an outer jit trace, where the outer program is what runs);
        the program's device ops carry the named scopes `ds.exchange`,
        `ds.owner_apply` and `ds.reply`.
        """
        with trace.span("am.dispatch"):
            plane = flt.active_plane()
            if plane is not None:
                # DESIGN.md §10, applied pre-coalescing at op-row
                # granularity: rows to a dead/stalled owner are masked
                # undelivered (and recorded for the adaptive layer's
                # one-sided failover); live rows go through wire-loss
                # retransmit + dedup simulation.
                valid = plane.inject_am(dst, valid)
                plane.tick()
            if plan is not None:
                cap = plan.cap
            elif cap is None:
                cap = dst.shape[1]
            prog = self._program(handler, cap, coalesce, plan is not None,
                                 valid is not None)
            traces = prog.traces
            out = prog.fn(state, dst, payload, valid, plan)
            outer = _first_tracer((state, dst, payload, valid, plan))
            trace.count("am_dispatches", of=outer)
            if prog.traces != traces:
                trace.count("am_dispatch_traces", of=outer)
            trace.trips(prog.count_key, 1, of=outer)
            return out

    def _program(self, handler: Handler, cap: int, coalesce: bool,
                 planned: bool, has_valid: bool) -> _Program:
        """The cached dispatch program for these static choices, built on
        first use. The sharding hook is read while the program is traced,
        so its identity is part of the key, and the entry keeps the hook
        alive so that its id is not reused."""
        hook = routing.current_sharding_hook()
        key = (handler, cap, coalesce, planned, has_valid, id(hook))
        prog = self._programs.get(key)
        if prog is None:
            prog = self._programs[key] = _Program(hook=hook)
            prog.fn = jax.jit(trace.per_trip(
                prog.count_key, self._core(handler, cap, coalesce, prog)))
        return prog

    def _core(self, handler: Handler, cap: int, coalesce: bool,
              prog: _Program):
        """The pure part of a dispatch: request routing (coalesced when
        asked), the handler, reply routing and fan-out."""
        nranks = self.nranks

        def core(state, dst, payload, valid, plan):
            prog.traces += 1
            co = None
            eff_valid = valid
            if coalesce:
                co = routing.coalesce(dst, payload[..., 0], match=payload,
                                      valid=valid)
                eff_valid = co.rep if valid is None else (valid & co.rep)
            if plan is not None:
                routed = routing.route_with_plan(plan, payload,
                                                 active=eff_valid,
                                                 role="am_req")
            else:
                routed = routing.route(dst, payload, cap, eff_valid,
                                       role="am_req")
            flat, mask = routing.flatten_owner_view(routed)
            with jax.named_scope("ds.owner_apply"):
                if handler.batched_fn is not None:
                    state2, reply_flat = handler.batched_fn(state, flat, mask)
                else:
                    state2, reply_flat = jax.vmap(handler.fn)(state, flat,
                                                              mask)
            delivered = routed.op_ok
            if co is not None:
                # reply fan-out: duplicates are delivered iff their
                # representative was
                delivered = routing.lead(co, delivered)
                if valid is not None:
                    delivered = delivered & valid
            if handler.reply_width == 0:
                replies = jnp.zeros(dst.shape + (0,), dtype=jnp.int32)
                return state2, replies, delivered
            replies_o = routing.unflatten_owner_view(reply_flat, nranks, cap)
            replies = routing.route_replies(routed, replies_o, dst,
                                            role="am_rep")
            if co is not None:
                replies = routing.lead(co, replies)
            return state2, replies, delivered

        core.__name__ = f"am_{handler.name}"     # the device program's name
        return core

    def dispatch_local(self, handler: Handler, state: Any, payload: Array,
                       valid: Optional[Array] = None
                       ) -> Tuple[Any, Array]:
        """Run the handler against the caller's own shard (C_l level):
        zero network phases, used by hosted structures when origin == owner
        and by tests."""
        if valid is None:
            valid = jnp.ones(payload.shape[:-1], dtype=bool)
        return jax.vmap(handler.fn)(state, payload, valid)
