"""In-program tracing: spans and counters at the layer boundaries of the
hot path, on the profiler's clock. Off by default.

    trace.enable()
    ...                      # front-end calls
    totals = trace.take()    # {"counts", "spans", "self_s"}, then cleared
    trace.disable()

`span(name, **args)` opens a `jax.profiler.TraceAnnotation` named
`ds.<name>`, so a profiler trace shows it beside the device planes, and
adds its self time (its duration less that of the `ds.` spans nested in
it, by `time.perf_counter_ns`) to a total per name. The outermost span of
a front-end call (`root`) carries `batch=<seq>`, a per-process batch
sequence number; nesting on the thread gives the causing span.

`count(name, n)` adds `n` to a counter. `n` may be a device scalar: it is
kept by reference and read only in `take()`, so counting never waits for
the device. A loop traced once and run for a trip count known only on the
device counts through `per_trip` and `trips`: the counts its body makes
while it is traced are kept per trip under a key, and each run adds them
times its trip count. The kept counts outlive the trace, so a loop whose
trace is cached still counts every run. A jitted program that is not a
loop counts the same way, with one trip per call (`am.AMEngine.dispatch`).

When tracing is off every `span`, `count` and `trips` call is one bool
test that returns at once, and no annotation object is built.

The module also keeps the phase log: the bounded ring of (role, decision,
info) entries that `core/window.py` records for phases issued inside a
decision or slot scope.
"""
from __future__ import annotations

import contextlib
import functools
import gc
import threading
import time
from typing import Callable, Dict, Hashable, List, Optional, Tuple

import jax
import numpy as np

PREFIX = "ds."

_on = False
# _on, or a loop body is being traced: count() records while either holds
_live = False
_local = threading.local()          # .stack: open spans of this thread
_counts: Dict[str, int] = {}
_pending: Dict[str, List[Tuple[object, object]]] = {}  # (n, times) on device
_spans: Dict[str, int] = {}
_self_ns: Dict[str, int] = {}
_bodies: List[Dict[str, int]] = []  # per-trip counts of bodies being traced
_per_trip: Dict[Hashable, Dict[str, int]] = {}
_batch = 0
_gc_span: Optional["_Span"] = None
_gc_hooked = False
_NULL = contextlib.nullcontext()
# device scalars kept per counter before they are folded into one
PENDING_MAX = 1024


def enable() -> None:
    """Start recording spans and counts (and hook the garbage collector
    once, as the span `ds.gc`)."""
    global _on, _live, _gc_hooked
    _on = _live = True
    if not _gc_hooked:
        gc.callbacks.append(_on_gc)
        _gc_hooked = True


def disable() -> None:
    global _on, _live
    _on = False
    _live = bool(_bodies)


def _stack() -> list:
    st = getattr(_local, "stack", None)
    if st is None:
        st = _local.stack = []
    return st


class _Span:
    __slots__ = ("name", "ann", "t0", "child")

    def __init__(self, name: str, args: dict):
        self.name = name
        self.ann = jax.profiler.TraceAnnotation(PREFIX + name, **args)
        self.child = 0

    def __enter__(self):
        _stack().append(self)
        self.ann.__enter__()
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter_ns() - self.t0
        self.ann.__exit__(*exc)
        st = _stack()
        if st and st[-1] is self:
            st.pop()
        elif self in st:
            st.remove(self)
        if st:
            st[-1].child += dt
        _spans[self.name] = _spans.get(self.name, 0) + 1
        _self_ns[self.name] = _self_ns.get(self.name, 0) + dt - self.child
        return False


def span(name: str, **args):
    """Context manager: the span `ds.<name>` while tracing is on. A span
    opened directly inside a span of the same name merges into it."""
    if not _on:
        return _NULL
    st = _stack()
    if st and st[-1].name == name:
        return _NULL
    return _Span(name, args)


def root(name: str):
    """`span(name)` for a front-end call: where no span is open on the
    thread it starts a batch and carries its sequence number."""
    global _batch
    if not _on:
        return _NULL
    if _stack():
        return span(name)
    _batch += 1
    return _Span(name, {"batch": _batch})


def _on_gc(phase: str, info: dict) -> None:
    global _gc_span
    if phase == "start":
        if _on:
            _gc_span = _Span("gc", {"generation": info.get("generation", -1)})
            _gc_span.__enter__()
    elif _gc_span is not None:
        s, _gc_span = _gc_span, None
        s.__exit__(None, None, None)


def count(name: str, n=1, times=1, of=None) -> None:
    """Add `n * times` to counter `name`. Either factor may be a device
    scalar. Inside a loop body being traced (`per_trip`) the count is one
    trip's, and only Python ints count there. `of`, when given, is the
    value the counted work acts on: outside a loop body a jit tracer in
    `of` or in a factor means the work is being traced rather than run,
    and it is not counted."""
    if not _live:
        return
    ints = isinstance(n, int) and isinstance(times, int)
    if _bodies:
        if ints:
            body = _bodies[-1]
            body[name] = body.get(name, 0) + n * times
        return
    if not _on or any(isinstance(x, jax.core.Tracer) for x in (n, times, of)):
        return
    if ints:
        _counts[name] = _counts.get(name, 0) + n * times
        return
    lst = _pending.setdefault(name, [])
    lst.append((n, times))
    if len(lst) >= PENDING_MAX:
        # fold on the device (no host read) to bound what is kept
        import jax.numpy as jnp
        a = jnp.stack([jnp.asarray(x, jnp.int32) for x, _ in lst])
        b = jnp.stack([jnp.asarray(y, jnp.int32) for _, y in lst])
        lst[:] = [(jnp.sum(a * b), 1)]


def per_trip(key: Hashable, body: Callable) -> Callable:
    """`body` of a loop, wrapped so that the counts made while it is
    traced are kept as one trip's counts under `key`. `key` names the
    loop and every static choice that changes what its body counts."""
    @functools.wraps(body)
    def traced(*args):
        global _live
        _bodies.append({})
        _live = True
        try:
            out = body(*args)
        finally:
            got = _bodies.pop()
            _live = _on or bool(_bodies)
        _per_trip[key] = got
        return out
    return traced


def trips(key: Hashable, n, of=None) -> None:
    """The loop kept under `key` ran `n` trips (an int or a device
    scalar): add its per-trip counts times `n`. `of` is as in `count`."""
    if not _live:
        return
    for name, k in _per_trip.get(key, {}).items():
        count(name, k, n, of=of)


def take() -> dict:
    """Return and clear the totals since the last `take`: counts (device
    scalars read now), span counts and self seconds per span name."""
    global _counts, _pending, _spans, _self_ns
    counts, pending = _counts, _pending
    spans, self_ns = _spans, _self_ns
    _counts, _pending, _spans, _self_ns = {}, {}, {}, {}
    for name, lst in pending.items():
        counts[name] = counts.get(name, 0) + sum(
            int(a) * int(b) for a, b in jax.device_get(lst))
    return {"counts": counts, "spans": spans,
            "self_s": {k: v * 1e-9 for k, v in self_ns.items()}}


# ---------------------------------------------------------------------------
# The phase log: a bounded ring that library callers never drain, so
# entries beyond PHASE_LOG_MAX are dropped oldest-first.
# ---------------------------------------------------------------------------
PHASE_LOG_MAX = 4096
_PHASE_LOG: List[Tuple[str, object, Optional[dict]]] = []


def log_phase(role: str, decision, info: Optional[dict]) -> None:
    _PHASE_LOG.append((role, decision, info))
    if len(_PHASE_LOG) > PHASE_LOG_MAX:
        del _PHASE_LOG[:-PHASE_LOG_MAX]


def _settle(info: Optional[dict]) -> Optional[dict]:
    """A coalesced phase keeps its per-origin `rows_in` / `rows_out` on
    the device; they are summed on the host here, not on the hot path."""
    if not info or "rows_in" not in info:
        return info
    ri = int(np.asarray(info["rows_in"]).sum())
    ro = int(np.asarray(info["rows_out"]).sum())
    return dict(info, rows_in=ri, rows_out=ro, dedup_ratio=ro / max(ri, 1))


def drain_phase_log() -> List[Tuple[str, object, Optional[dict]]]:
    """Return and clear the (role, decision, info) log of tagged phases.

    Phases are logged while a `window.decision_scope` and/or a
    `window.slot_scope` is active. `info` is None for plain uncoalesced
    phases; coalesced phases record the sender-side combining stats
    {"coalesced": True, "rows_in", "rows_out", "dedup_ratio"} when the
    batch is concrete (host ints; under jit tracing only
    {"coalesced": True}), and phases issued inside a pipeline slot also
    carry {"slot": int, "seq": int} (DESIGN.md §7)."""
    out = [(role, dec, _settle(info)) for role, dec, info in _PHASE_LOG]
    _PHASE_LOG.clear()
    return out
