"""The compiled AM dispatch (`am.AMEngine.dispatch`): one jitted program
per handler, shapes and flags, bit-identical to the same call run op by
op under `jax.disable_jit()`, traced once and reused, with the fault
plane still acting on every call."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import am as am_mod
from repro.core import faults as flt
from repro.core import hashtable as ht_mod
from repro.core import queue as q_mod
from repro.core import routing, trace
from repro.core import txn as txn_mod
from repro.core import window as win_mod

P, N, NSLOTS = 4, 8, 32


def _keys(rng):
    """(P, N) keys, each origin's second half repeating its first, so
    coalescing finds duplicate runs."""
    k = rng.choice(np.arange(1, 1 << 20), size=(P, N // 2), replace=False)
    return jnp.asarray(np.concatenate([k, k], axis=1), jnp.int32)


def _ht(eng, rng, name):
    ht = ht_mod.make_hashtable(P, NSLOTS, 1)
    # two probes: op by op, each probe of the insert's serial scan is a
    # Python step
    ht_mod.build_am_handlers(ht, eng, max_probes=2)
    pre = _keys(rng)
    ht, ok, _ = ht_mod.insert(ht, pre[:, :N // 2], pre[:, :N // 2, None] + 1,
                              backend="rdma")
    assert bool(ok.all())
    keys = jnp.concatenate([pre[:, :N // 4], _keys(rng)[:, N // 4:]], axis=1)
    dst, start = ht_mod._place(ht, keys)
    if name == "ht_insert":
        payload = jnp.stack([start, keys, keys + 7], axis=-1)
    else:
        payload = jnp.stack([start, keys], axis=-1)
    return ht.win.data, dst, payload


def _queue(eng, rng, name):
    q = q_mod.make_queue(P, host=1, capacity=16, val_words=1)
    q_mod.build_am_handlers(q, eng)
    q, _ = q_mod.push(q, jnp.arange(P * 2, dtype=jnp.int32).reshape(
        P, 2, 1) + 100, backend="rpc", engine=eng)
    dst = jnp.full((P, N), 1, jnp.int32)
    if name == "q_push":
        vals = rng.integers(1, 4, (P, N, 1))
    else:
        vals = np.zeros((P, N, 1))
    return q.win.data, dst, jnp.asarray(vals, jnp.int32)


def _txn(eng, rng, name):
    txn_mod.TxnEngine(P, am_engine=eng)._ensure_handlers()
    lock = win_mod.make_window(P, 16).data.at[:, ::2].set(3)
    dst = jnp.asarray(rng.integers(0, P, (P, N)), jnp.int32)
    off = jnp.asarray(rng.integers(0, 16, (P, N)), jnp.int32)
    cmp = jnp.asarray(rng.choice([0, 3], (P, N)), jnp.int32)
    payload = jnp.stack([off, cmp, jnp.full((P, N), 5, jnp.int32)], axis=-1)
    return lock, dst, payload


MAKERS = {"ht_insert": _ht, "ht_find": _ht, "q_push": _queue,
          "q_pop": _queue, "txn_cas": _txn}


def _case(name, seed=0):
    rng = np.random.default_rng(seed)
    eng = am_mod.AMEngine(P)
    state, dst, payload = MAKERS[name](eng, rng, name)
    valid = jnp.asarray(rng.random((P, N)) < 0.7)
    return eng, eng.handler(name), state, dst, payload, valid


def _equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("planned", [False, True], ids=["unplanned", "plan"])
@pytest.mark.parametrize("coalesce", [False, True], ids=["plain", "coalesce"])
@pytest.mark.parametrize("partial", [False, True], ids=["all", "valid"])
@pytest.mark.parametrize("name", sorted(MAKERS))
def test_compiled_equals_op_by_op(name, partial, coalesce, planned):
    eng, h, state, dst, payload, valid = _case(name)
    valid = valid if partial else None
    plan = routing.make_plan(dst, cap=N) if planned else None

    def call():
        return eng.dispatch(h, state, dst, payload, valid, plan=plan,
                            coalesce=coalesce)

    made = len(eng._programs)
    got = call()
    with jax.disable_jit():
        want = call()
    _equal(got, want)
    assert len(eng._programs) == made + 1


@pytest.fixture
def traced():
    trace.take()
    trace.enable()
    yield
    trace.disable()
    trace.take()


@pytest.mark.parametrize("name", ["ht_insert", "q_pop"])
def test_second_call_reuses_the_program(traced, name):
    """The second identical call traces nothing and counts the same
    exchanges as the first: request, occupancy mask, reply."""
    eng, h, state, dst, payload, _ = _case(name)
    trace.take()
    first = eng.dispatch(h, state, dst, payload)
    once = trace.take()["counts"]
    second = eng.dispatch(h, state, dst, payload)
    twice = trace.take()["counts"]
    assert once == {"am_dispatches": 1, "am_dispatch_traces": 1,
                    "exchanges": 3}
    assert twice == {"am_dispatches": 1, "exchanges": 3}
    _equal(first, second)


def test_fault_plane_acts_on_a_cached_call():
    """An owner that dies after the first call loses its rows on the
    second, though the second call reuses the first one's program."""
    eng, h, state, dst, payload, _ = _case("txn_cas")
    valid = jnp.ones((P, N), bool)
    plane = flt.FaultPlan(P)
    with flt.fault_scope(plane):
        _, _, dlv1 = eng.dispatch(h, state, dst, payload, valid)
        plane.dead_owners[2] = None
        state2, _, dlv2 = eng.dispatch(h, state, dst, payload, valid)
    (prog,) = eng._programs.values()
    assert prog.traces == 1
    to2 = np.asarray(dst) == 2
    assert to2.any() and bool(np.asarray(dlv1).all())
    np.testing.assert_array_equal(np.asarray(dlv2), ~to2)
    np.testing.assert_array_equal(np.asarray(state2)[2],
                                  np.asarray(state)[2])


def test_front_end_under_an_outer_jit(traced):
    """The cached program nests in an outer jit: the same answers as an
    eager call, and the outer trace counts no dispatch."""
    rng = np.random.default_rng(3)
    eng = am_mod.AMEngine(P)
    ht = ht_mod.make_hashtable(P, NSLOTS, 1)
    ht_mod.build_am_handlers(ht, eng)
    keys = _keys(rng)
    vals = keys[..., None] + 1

    def insert(data, keys, vals):
        t = ht_mod.DHashTable(win=win_mod.Window(data=data), nslots=NSLOTS,
                              val_words=1)
        t2, ok, probes = ht_mod.insert_rpc(t, eng, keys, vals)
        return t2.win.data, ok, probes

    trace.take()
    got = jax.jit(insert)(ht.win.data, keys, vals)
    assert "am_dispatches" not in trace.take()["counts"]
    _equal(got, insert(ht.win.data, keys, vals))
    assert bool(np.asarray(got[1]).all())
