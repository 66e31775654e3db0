"""Pipelined in-flight batch engine: futures-style op handles over
double-buffered exchange windows (DESIGN.md §7).

The paper's central RPC liability is *attentiveness*: remote progress only
happens when the target enters the runtime, so un-overlapped round trips
dominate. The seed engine had the same shape — one op batch ran
synchronously end-to-end, leaving the owner-apply lane idle while the next
batch's descriptors were still being routed. This module closes that gap:

    pipe = Pipeline(ht, depth=2)               # two in-flight windows
    h1 = hashtable.insert_async(pipe, k1, v1)  # batch 0: staged, in flight
    h2 = hashtable.find_async(pipe, k2)        # batch 1 routes while batch
    ok, probes = h1.result()                   #   0's owner lane applies
    ht = pipe.flush()                          # force everything, get state

`submit` stages a batch — the routing/coalescing/plan construction and the
send exchange are *dispatched* immediately — and returns a `Handle`
without waiting for the owner-apply and reply exchange to complete.
`Handle.result()` forces completion. `depth` counts exchange windows,
INCLUDING the one being staged: with `depth >= 2` the engine keeps
windows in flight across submits, so batch *k+1*'s route+send (and the
caller's interspersed compute) overlaps batch *k*'s apply+reply;
`depth=1` is the single-window lock-step engine — every submit completes
its own batch before returning, bit-exactly the synchronous path.

How the overlap is realized in this emulation: each batch is a chain of
JAX computations dispatched asynchronously — the Python thread returns as
soon as the work is enqueued, and batch *k+1*'s staging (the adaptive
decision, `routing.make_plan_np`'s host-side argsort, descriptor
construction, jit-cache dispatch) runs while the device executes batch
*k*. State threads through the pipeline functionally: batch *k+1* is
staged against batch *k*'s not-yet-materialized output window — the
dependency resolves on the device, never on the host. The two (at depth 2)
live windows are physically distinct device buffers: functional updates
ARE the double buffering.

Deferred (AM) batches and attentiveness: ops whose chosen arm is an active
message are submitted with `deferred=True`. They wait in the
`AMEngine` dispatch queue and drain at the next *dispatch point* — the
next eager submit, a `result()`, or a `flush()` (`AMEngine.
drain_dispatch_queue`, DESIGN.md §7). Their service latency is therefore
exactly the time to the next overlap window, which makes the paper's
attentiveness a tunable, measurable quantity: `benchmarks/
pipeline_bench.py` sweeps the inter-submit `busy_wait` knob against it.

Ordering contract: submission order IS serialization order. Deferred
batches are drained before any later eager batch stages, so the state
each batch observes is identical to the synchronous engine's — the
conformance suite (tests/test_pipeline.py) pins async == sync == oracle
on randomized interleaved submit streams with out-of-order `result()`
forcing.
"""
from __future__ import annotations

import collections
from typing import Any, Callable, List, Optional, Tuple

import jax

from . import faults as flt
from . import window as win_mod
from .types import device_wait

# An op stages one batch against the current structure state and returns
# (state', outputs). Outputs are what Handle.result() yields.
OpFn = Callable[[Any], Tuple[Any, Any]]


class Handle:
    """Future for one submitted op batch (DESIGN.md §7).

    A Handle is created by `Pipeline.submit` and resolves to the batch's
    outputs — e.g. `(ok, probes)` for a hash-table insert. Handles may be
    forced in any order; forcing never changes values (results are
    deterministic — the conformance suite pins out-of-order forcing).
    """

    __slots__ = ("seq", "label", "deferred", "_pipe", "_op", "_outputs",
                 "_staged", "_forced", "_error")

    def __init__(self, pipe: "Pipeline", seq: int, label: Optional[str],
                 deferred: bool):
        self.seq = seq
        self.label = label
        self.deferred = deferred
        self._pipe = pipe
        self._op: Optional[OpFn] = None
        self._outputs: Any = None
        self._staged = False
        self._forced = False
        self._error: Optional[BaseException] = None

    def done(self) -> bool:
        """True when the batch's outputs are materialized on the device.

        Never blocks: a deferred batch still waiting for a dispatch point
        reports False, as does a staged batch whose device work is in
        flight. A device error surfaces from `is_ready` (or from
        `result()`), never as a finished batch."""
        if self._forced or self._error is not None:
            return True
        if not self._staged:
            return False
        return all(x.is_ready() for x in jax.tree_util.tree_leaves(
            self._outputs) if hasattr(x, "is_ready"))

    def result(self, timeout: Optional[int] = None) -> Any:
        """Force completion and return the batch's outputs.

        Blocks until the device work is done; drains the deferred-dispatch
        queue first if this batch (or an earlier one) is still waiting for
        a dispatch point. Idempotent — repeated calls return the same
        values.

        timeout (DESIGN.md §10): under an active `faults.FaultPlan`, the
        maximum number of simulated dispatch rounds to wait for a stalled
        deferred-AM queue before raising `faults.RemoteTimeout` (default:
        the plan's `RetryPolicy.deadline`) — a permanently dead owner
        raises immediately instead of spinning. Without a plan the engine
        cannot stall, so the value is accepted but unused. A timed-out
        Handle stays failed: repeated `result()` re-raises the same
        RemoteTimeout even if the owner later wakes (the classic
        ambiguity of a timed-out RPC — the op may or may not have run;
        here it is guaranteed dropped, see `Pipeline.close`)."""
        self._pipe._force(self, timeout=timeout)
        return self._outputs


class Pipeline:
    """In-flight op-batch manager over a functionally threaded state.

    state:     the structure being operated on (e.g. a `DHashTable` or
               `DQueue` — any value the submitted ops thread through).
    depth:     exchange windows, including the one being staged.
               1 = synchronous lock-step (each submit completes its own
               batch before returning — bit-exact with the direct engine
               calls); 2 = double-buffered (the default): one window
               stages/sends while the previous one applies/replies, so
               at most one batch is left in flight when submit returns.
    am_engine: optional `am.AMEngine`. Deferred (AM-arm) submissions queue
               on it and drain at dispatch points; without one the
               pipeline keeps its own FIFO with the same semantics.
    auto_depth: make the window count a CHOOSER decision (DESIGN.md §9):
               the async front-ends ask their AdaptiveEngine's
               `choose_depth` before each submit and retarget the window
               count via `set_depth`. The constructor `depth` becomes the
               CAP — the chooser may shrink the window but never exceeds
               the caller's budget.

    `Pipeline.state` is the latest *staged* state — its device values may
    still be in flight; `flush()` forces everything and returns it.
    """

    def __init__(self, state: Any, depth: int = 2, am_engine=None,
                 auto_depth: bool = False):
        if depth < 1:
            raise ValueError(f"pipeline depth must be >= 1, got {depth}")
        self._state = state
        self.depth = depth
        self.am_engine = am_engine
        self.auto_depth = auto_depth
        self.max_depth = depth
        self._inflight: collections.deque = collections.deque()
        self._own_queue: collections.deque = collections.deque()
        self._seq = 0
        self._closed = False

    # -- context manager (DESIGN.md §10: teardown never strands batches) ----
    def __enter__(self) -> "Pipeline":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        if exc_type is None:
            # clean exit: a full dispatch point — deferred batches drain,
            # every handle forces; failures (RemoteTimeout on a stalled
            # queue) propagate to the caller
            self.flush()
        else:
            # exception path: best-effort teardown that never masks the
            # in-flight exception
            self.close()
        return False

    def close(self) -> None:
        """Teardown that strands no batch: drain the deferred queue so no
        dispatch thunk is stranded, force every stageable Handle, and fail
        the rest with `faults.RemoteTimeout`. It raises nothing (this is
        the exception path of the context manager): an error met on the
        way is kept on its Handle, whose `result()` raises it. Queued
        thunks of this pipeline become no-ops, so a later engine drain by
        another user cannot resurrect a batch the caller was told had
        failed."""
        drain_error = None
        try:
            self._drain_deferred()
        except Exception as e:  # kept on the unserviced handles below
            drain_error = e
        for h in list(self._inflight):
            if h._staged:
                try:
                    self._force(h)
                    continue
                except Exception as e:  # result() raises it
                    h._error = e
            else:
                h._error = flt.RemoteTimeout(
                    f"pipeline closed with batch seq={h.seq} "
                    f"({h.label or 'op'}) never serviced")
                h._error.__cause__ = drain_error
            try:
                self._inflight.remove(h)
            except ValueError:
                pass
        self._closed = True
        self._own_queue.clear()
        self._note_inflight()

    def set_depth(self, depth: int) -> None:
        """Retarget the in-flight window count (the §9 auto-depth hook).

        Clamped to [1, max_depth]. Shrinking forces the oldest batches
        immediately so the at-most-`depth - 1`-in-flight invariant holds
        before the next submit; growing just admits more windows. Safe to
        call between any two submits — ordering is untouched."""
        d = max(1, min(int(depth), self.max_depth))
        self.depth = d
        while len(self._inflight) > d - 1:
            self._force(self._inflight[0])

    def _note_inflight(self) -> None:
        win_mod.note_pipeline_inflight(self, bool(self._inflight))

    # -- introspection ------------------------------------------------------
    @property
    def staged_state(self) -> Any:
        """The raw staged state, WITHOUT draining deferred batches.

        For metadata reads at submit time (e.g. a `DHashTable`'s static
        `nranks`/`nslots`, which never change across the pipeline) — the
        async front-ends use this so peeking never forces a dispatch
        point. Use `state` for a value reflecting every submission."""
        return self._state

    @property
    def state(self) -> Any:
        """Latest staged state (drains any pending deferred batches so the
        value reflects every submission; device work may still be in
        flight — this property never blocks on it)."""
        self._drain_deferred()
        return self._state

    @property
    def in_flight(self) -> int:
        """Unforced batches currently tracked (staged + deferred)."""
        return len(self._inflight)

    @property
    def pending_deferred(self) -> int:
        """Deferred batches still waiting for a dispatch point."""
        if self.am_engine is not None:
            return self.am_engine.pending_dispatches
        return len(self._own_queue)

    # -- submission ---------------------------------------------------------
    def submit(self, op: OpFn, deferred: bool = False,
               label: Optional[str] = None) -> Handle:
        """Stage one op batch; returns its Handle immediately.

        op: callable `state -> (state', outputs)`. Eager ops run now (their
        device work is dispatched asynchronously — the host does not wait);
        `deferred=True` queues the op for the next dispatch point (the AM
        attentiveness model — see the module docstring). Before returning,
        the oldest batches are forced until at most `depth - 1` remain in
        flight: depth=1 therefore completes the submitted batch itself
        (the lock-step engine), depth=2 leaves exactly this batch in
        flight while the caller stages the next one."""
        h = Handle(self, self._seq, label, deferred)
        self._seq += 1
        if not deferred:
            self._drain_deferred()
            if self.pending_deferred:
                # an inattentive owner (§10 queue stall) still holds
                # earlier deferred batches: this submission must queue
                # behind them — submission order IS serialization order,
                # with or without faults
                deferred = h.deferred = True
        if deferred:
            h._op = op
            self._enqueue(h)
        else:
            self._run(h, op)
        self._inflight.append(h)
        self._note_inflight()
        while len(self._inflight) > self.depth - 1:
            self._force(self._inflight[0])
        return h

    def flush(self) -> Any:
        """Force every in-flight batch (a dispatch point) and return the
        fully materialized state."""
        self._drain_deferred()
        while self._inflight:
            self._force(self._inflight[0])
        device_wait(jax.tree_util.tree_leaves(self._state))
        return self._state

    # -- internals ----------------------------------------------------------
    def _enqueue(self, h: Handle) -> None:
        def thunk():
            if self._closed or h._error is not None:
                return  # failed/closed batches are guaranteed dropped
            self._run(h, h._op)

        if self.am_engine is not None:
            self.am_engine.queue_dispatch(thunk)
        else:
            self._own_queue.append(thunk)

    def _run(self, h: Handle, op: OpFn) -> None:
        """Stage one batch: run the op against the current state inside the
        batch's slot scope (per-slot phase logs, DESIGN.md §7)."""
        with win_mod.slot_scope(h.seq % self.depth, h.seq):
            state, outputs = op(self._state)
        self._state = state
        h._outputs = outputs
        h._staged = True

    def _drain_deferred(self) -> None:
        """Enter a dispatch point: run every queued deferred batch FIFO.

        Deferred batches are always a suffix of the submission order (an
        eager submit drains them first), so draining preserves the
        synchronous engine's serialization."""
        if self.am_engine is not None:
            self.am_engine.drain_dispatch_queue()
        else:
            while self._own_queue:
                self._own_queue.popleft()()

    def _force(self, h: Handle, timeout: Optional[int] = None) -> None:
        if h._error is not None:
            raise h._error
        if h._forced:
            return
        if not h._staged:
            self._drain_deferred()
        if not h._staged:
            # DESIGN.md §10: the deferred queue refused to drain — an
            # inattentive owner. Keep offering service opportunities
            # (each drain attempt advances the plane's round clock) up to
            # `timeout` simulated rounds, then fail typed instead of
            # hanging; a permanently dead owner fails without spinning.
            plane = flt.active_plane()
            if plane is not None:
                rounds = int(timeout if timeout is not None
                             else plane.retry.deadline)
                for _ in range(rounds):
                    if plane.queue_dead():
                        break
                    self._drain_deferred()
                    if h._staged:
                        break
                if not h._staged:
                    why = ("permanently dead" if plane.queue_dead()
                           else f"stalled past {rounds} rounds")
                    err = flt.RemoteTimeout(
                        f"batch seq={h.seq} ({h.label or 'op'}) not "
                        f"serviced: deferred-AM queue {why}")
                    h._error = err
                    try:
                        self._inflight.remove(h)
                    except ValueError:
                        pass
                    self._note_inflight()
                    raise err
        assert h._staged, "deferred batch did not stage at dispatch point"
        device_wait(jax.tree_util.tree_leaves(h._outputs))
        h._forced = True
        try:
            self._inflight.remove(h)
        except ValueError:
            pass
        self._note_inflight()


def submit_many(pipe: Pipeline, ops: List[OpFn]) -> List[Handle]:
    """Convenience: submit a list of ops in order, returning their handles."""
    return [pipe.submit(op) for op in ops]
