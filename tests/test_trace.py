"""In-program tracing (`core/trace.py`): off it records nothing and builds
no annotation; on it counts exchanges, host reads and probe trips as
computed by hand, twice for a batch run twice, and reads no device value
before `take()`."""
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import adaptive, trace, window
from repro.core import am as am_mod
from repro.core import hashtable as ht_mod
from repro.core import queue as q_mod
from repro.core.types import FLAG_EMPTY, FLAG_READY, STATE_MASK

P, N, NSLOTS = 8, 16, 64


@pytest.fixture
def traced():
    """Tracing on for one test, with empty totals before and after."""
    trace.take()
    trace.enable()
    yield
    trace.disable()
    trace.take()


class Recorder:
    """Stands in for `jax.profiler.TraceAnnotation`: records every
    annotation built, with its arguments."""
    made = []

    def __init__(self, name, **args):
        Recorder.made.append((name, args))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


@pytest.fixture
def recorder(monkeypatch):
    Recorder.made = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    return Recorder.made


def _keys(seed, shape=(P, N)):
    rng = np.random.default_rng(seed)
    k = rng.choice(np.arange(1, 1 << 20), size=int(np.prod(shape)),
                   replace=False)
    return jnp.asarray(k.reshape(shape), jnp.int32)


def _table(keys):
    ht = ht_mod.make_hashtable(P, NSLOTS, 1)
    ht, ok, _ = ht_mod.insert(ht, keys, keys[..., None] + 1, backend="rdma")
    assert bool(ok.all())
    return ht


def _find_trips(ht, keys, max_probes=8):
    """Trips of the fused find loop, by a host walk of each key's probe
    window: it runs until every key hits its key or an empty slot."""
    data = np.asarray(ht.win.data)
    owner, start = ht_mod.place_np(P, NSLOTS, np.asarray(keys))
    most = 0
    for o, s0, k in zip(owner.ravel(), start.ravel(),
                        np.asarray(keys).ravel()):
        for j in range(max_probes):
            base = ((s0 + j) % NSLOTS) * ht.rec_w
            state = int(data[o, base]) & int(STATE_MASK)
            if (state == int(FLAG_READY) and data[o, base + 1] == k) or \
                    state == int(FLAG_EMPTY):
                break
        most = max(most, j + 1)
    return most


def _batches(seed):
    """One AM insert, one fused coalesced find and a queue push and pop
    at P=8, and the counts each makes, computed by hand."""
    keys = _keys(seed)
    ht = _table(keys)
    dup = jnp.concatenate([keys[:, :N // 2], keys[:, :N // 2]], axis=1)
    trips = _find_trips(ht, dup)
    eng = am_mod.AMEngine(P)
    ad = adaptive.AdaptiveEngine(P, am_engine=eng)
    ad.force_arm = "am"
    q = q_mod.make_queue(P, host=0, capacity=1024, val_words=1)

    def insert():    # request, occupancy mask, reply
        ht_mod.insert(ht, _keys(seed + 1), keys[..., None], adaptive=ad)

    def find():      # one plan mask, then a request and a reply per trip
        ht_mod.find(ht, dup, backend="rdma", fused=True, coalesce=True)

    def push_pop():  # request, mask, reply for each
        q2, _ = q_mod.push(q, keys[..., None], adaptive=ad)
        q_mod.pop(q2, N, adaptive=ad)

    trace.take()                             # leave out the set-up
    return [(insert, {"exchanges": 3, "host_reads": 2, "am_dispatches": 1}),
            (find, {"exchanges": 1 + 2 * trips, "probe_trips": trips}),
            (push_pop, {"exchanges": 6, "am_dispatches": 2})]


def _first_run(want):
    """The counts of a batch's first run on a fresh engine: each AM
    dispatch traces its program once, later runs reuse it."""
    n = want.get("am_dispatches", 0)
    return dict(want, am_dispatch_traces=n) if n else want


def test_off_records_nothing_and_builds_no_annotation(recorder):
    trace.disable()
    trace.take()
    for run, _ in _batches(1):
        run()
    gc.collect()
    assert recorder == []
    assert trace.take() == {"counts": {}, "spans": {}, "self_s": {}}


@pytest.mark.parametrize("which", [0, 1, 2], ids=["am_insert", "fused_find",
                                                  "queue_push_pop"])
def test_counts_by_hand(traced, which):
    run, want = _batches(2)[which]
    run()
    assert trace.take()["counts"] == _first_run(want)


def _no_gc(spans):
    return {k: v for k, v in spans.items() if k != "gc"}


def test_same_batch_twice_counts_twice(traced):
    for run, want in _batches(3):
        run()
        once = trace.take()
        run()
        run()
        twice = trace.take()
        assert once["counts"] == _first_run(want)
        assert twice["counts"] == {k: 2 * v for k, v in want.items()}
        assert _no_gc(twice["spans"]) == {
            k: 2 * v for k, v in _no_gc(once["spans"]).items()}


def test_auto_insert_reads_keys_and_destinations(traced):
    """The chooser's two host reads: the keys for the dedup ratio and the
    destinations for the skew; a pre-set skew and dedup read nothing."""
    keys = _keys(4)
    ht = ht_mod.make_hashtable(P, NSLOTS, 1)
    ht, _, _ = ht_mod.insert(ht, keys, keys[..., None])
    got = trace.take()
    assert got["counts"]["host_reads"] == 2
    assert got["spans"]["host_read"] == 2
    assert got["spans"]["chooser.decide"] == 1
    from repro.core.types import OpStats
    ht_mod.insert(ht, _keys(5), keys[..., None],
                  stats=OpStats(skew=1.5, dedup=0.9))
    assert "host_reads" not in trace.take()["counts"]


def test_probe_trips_match_the_loop(traced):
    """The fused insert loop runs until every op has claimed: its trip
    count is the largest per-op probe count it returns."""
    keys = _keys(6, (P, 40))                 # load 0.625: probes collide
    ht = ht_mod.make_hashtable(P, NSLOTS, 1)
    _, ok, probes = ht_mod.insert(ht, keys, keys[..., None],
                                  backend="rdma")
    counts = trace.take()["counts"]
    assert counts["probe_trips"] == int(np.asarray(probes).max()) > 1
    assert counts["exchanges"] == 1 + 2 * counts["probe_trips"]


def test_take_reads_no_device_value_before_it_is_called(traced):
    """Counting keeps device scalars by reference, and the phase log keeps
    coalescing stats as device arrays: they are read in `take()` and
    `drain_phase_log()`, not where they were recorded."""
    keys = _keys(7)
    ht = _table(keys)
    win = window.make_window(P, 32)
    dst = jnp.zeros((P, 6), jnp.int32)
    window.drain_phase_log()
    trace.take()
    ht_mod.find(ht, keys, backend="rdma", fused=True, coalesce=True)
    with window.decision_scope("dec"):
        window.rdma_fao(win, dst, dst, 1, window.AmoKind.FAA, coalesce=True)
    pending = trace._pending["probe_trips"]
    assert all(isinstance(n, jax.Array) for n, _ in pending)
    (_, _, info), = trace._PHASE_LOG
    assert isinstance(info["rows_in"], jax.Array)
    assert trace.take()["counts"]["probe_trips"] == _find_trips(ht, keys)
    (_, _, info), = window.drain_phase_log()
    assert info["rows_in"] == P * 6 and info["rows_out"] == P


def test_cached_loop_keeps_its_per_trip_count():
    """A loop whose trace is cached, even one first traced with tracing
    off, still counts its body per trip on every run."""
    def body(c):
        trace.count("work", of=c)
        trace.count("work", of=c)
        return c + 1

    loop = jax.jit(lambda c: jax.lax.while_loop(
        lambda c: c < 3, trace.per_trip("test.loop", body), c))
    trace.disable()
    loop(jnp.int32(0))                       # traced here, with tracing off
    trace.take()
    trace.enable()
    try:
        for _ in range(2):
            out = loop(jnp.int32(0))         # cached: the body is not traced
            trace.trips("test.loop", out)
        assert trace.take()["counts"] == {"work": 12}
    finally:
        trace.disable()


def test_spans_self_time_batch_number_and_gc(traced, recorder):
    """Self time leaves out nested spans; a root span opened outside any
    other carries the next batch number; a collection is a `gc` span."""
    import time
    with trace.root("insert"):
        time.sleep(0.02)
        with trace.span("plan"):
            with trace.span("plan"):           # merges into its parent
                time.sleep(0.05)
        with trace.root("find"):               # nested: no new batch
            pass
    with trace.root("find"):
        gc.collect()
    got = trace.take()
    assert got["spans"]["insert"] == 1 and got["spans"]["plan"] == 1
    assert got["spans"]["find"] == 2 and got["spans"]["gc"] >= 1
    assert 0.05 <= got["self_s"]["plan"] < 0.2
    assert 0.02 <= got["self_s"]["insert"] < got["self_s"]["plan"]
    names = [(n, a) for n, a in recorder if n != "ds.gc"]
    first = names[0][1]["batch"]
    assert names == [("ds.insert", {"batch": first}), ("ds.plan", {}),
                     ("ds.find", {}), ("ds.find", {"batch": first + 1})]
