"""Distributed data-structure semantics: RDMA backend == RPC backend ==
python oracle, across promise levels (paper Tables II/III structures)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import am as am_mod
from repro.core import hashtable as ht_mod
from repro.core import queue as q_mod
from repro.core import routing, trace, window
from repro.core.types import AmoKind, Promise


P = 4


# ---------------------------------------------------------------------------
# Routing engine
# ---------------------------------------------------------------------------
def test_route_delivers_every_valid_op():
    rng = np.random.default_rng(0)
    dst = jnp.asarray(rng.integers(0, P, (P, 9)), jnp.int32)
    payload = jnp.asarray(rng.integers(0, 100, (P, 9, 2)), jnp.int32)
    routed = routing.route(dst, payload, cap=9)
    assert int(routed.dropped.sum()) == 0
    assert bool(routed.op_ok.all())
    # every payload word appears exactly once at its owner
    flat, mask = routing.flatten_owner_view(routed)
    got = np.sort(np.asarray(flat[np.asarray(mask)])[:, 0])
    want = np.sort(np.asarray(payload[..., 0]).ravel())
    np.testing.assert_array_equal(got, want)


def test_route_capacity_drops_are_reported():
    dst = jnp.zeros((P, 8), jnp.int32)          # everyone targets rank 0
    payload = jnp.ones((P, 8, 1), jnp.int32)
    routed = routing.route(dst, payload, cap=3)
    # per-origin cap of 3 toward one destination -> 5 dropped per origin
    assert int(routed.dropped.sum()) == P * 5


def test_reply_routing_aligns_with_op_order():
    rng = np.random.default_rng(1)
    dst = jnp.asarray(rng.integers(0, P, (P, 6)), jnp.int32)
    off = jnp.asarray(rng.integers(0, 32, (P, 6)), jnp.int32)
    win = window.make_window(P, 32)
    # write rank*1000+off at each location, then get and check
    base = jnp.arange(P)[:, None] * 1000 + jnp.arange(32)[None]
    win = window.Window(data=base.astype(jnp.int32))
    got = window.rdma_get(win, dst, off, width=1)[..., 0]
    want = dst * 1000 + off
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Route plans (DESIGN.md §2): reuse is bit-exact vs a fresh route per phase
# ---------------------------------------------------------------------------
def test_route_plan_matches_fresh_route():
    rng = np.random.default_rng(4)
    dst = jnp.asarray(rng.integers(0, P, (P, 9)), jnp.int32)
    payload = jnp.asarray(rng.integers(0, 100, (P, 9, 2)), jnp.int32)
    fresh = routing.route(dst, payload, cap=9)
    plan = routing.make_plan(dst, cap=9)
    planned = routing.route_with_plan(plan, payload)
    np.testing.assert_array_equal(np.asarray(fresh.at_owner),
                                  np.asarray(planned.at_owner))
    np.testing.assert_array_equal(np.asarray(fresh.mask),
                                  np.asarray(planned.mask))
    np.testing.assert_array_equal(np.asarray(fresh.op_slot),
                                  np.asarray(planned.op_slot))
    np.testing.assert_array_equal(np.asarray(fresh.op_ok),
                                  np.asarray(planned.op_ok))


def test_route_plan_shrinking_active_masks_out_ops():
    """A shrinking probe-loop mask ANDs into the plan occupancy: inactive
    ops vanish from the owner view but active ops keep their (src, slot)
    serialization positions."""
    rng = np.random.default_rng(5)
    dst = jnp.asarray(rng.integers(0, P, (P, 8)), jnp.int32)
    payload = jnp.asarray(rng.integers(1, 100, (P, 8, 1)), jnp.int32)
    active = jnp.asarray(rng.random((P, 8)) > 0.5)
    plan = routing.make_plan(dst, cap=8)
    planned = routing.route_with_plan(plan, payload, active=active)
    # owner view contains exactly the active payload words
    flat, mask = routing.flatten_owner_view(planned)
    got = np.sort(np.asarray(flat[np.asarray(mask)])[:, 0])
    want = np.sort(np.asarray(payload[..., 0])[np.asarray(active)].ravel())
    np.testing.assert_array_equal(got, want)
    # active ops occupy the same slots as in the full-batch plan
    np.testing.assert_array_equal(np.asarray(planned.op_slot),
                                  np.asarray(plan.op_slot))
    np.testing.assert_array_equal(
        np.asarray(planned.op_ok), np.asarray(plan.op_ok & active))


def test_planned_fao_matches_unplanned_under_shrinking_mask():
    rng = np.random.default_rng(6)
    dst = jnp.asarray(rng.integers(0, P, (P, 6)), jnp.int32)
    off = jnp.asarray(rng.integers(0, 16, (P, 6)), jnp.int32)
    masks = [jnp.asarray(rng.random((P, 6)) > t) for t in (0.0, 0.4, 0.8)]
    win_a = window.make_window(P, 16)
    win_b = window.make_window(P, 16)
    plan = routing.make_plan(dst, cap=6)
    for m in masks:
        old_a, win_a = window.rdma_fao(win_a, dst, off, 1, AmoKind.FAA,
                                       valid=m)
        old_b, win_b = window.rdma_fao(win_b, dst, off, 1, AmoKind.FAA,
                                       valid=m, plan=plan)
        np.testing.assert_array_equal(
            np.asarray(old_a)[np.asarray(m)], np.asarray(old_b)[np.asarray(m)])
        np.testing.assert_array_equal(np.asarray(win_a.data),
                                      np.asarray(win_b.data))


# ---------------------------------------------------------------------------
# One-sided AMOs
# ---------------------------------------------------------------------------
def test_faa_tickets_are_unique_and_dense():
    win = window.make_window(P, 4)
    dst = jnp.zeros((P, 3), jnp.int32)
    off = jnp.zeros((P, 3), jnp.int32)
    old, win = window.rdma_fao(win, dst, off, 1, AmoKind.FAA)
    tickets = np.sort(np.asarray(old).ravel())
    np.testing.assert_array_equal(tickets, np.arange(P * 3))
    assert int(win.data[0, 0]) == P * 3


def test_cas_exactly_one_winner():
    win = window.make_window(P, 2)
    dst = jnp.zeros((P, 2), jnp.int32)
    off = jnp.zeros((P, 2), jnp.int32)
    old, win = window.rdma_cas(win, dst, off, 0, 7)
    winners = int((np.asarray(old) == 0).sum())
    assert winners == 1
    assert int(win.data[0, 0]) == 7


def test_fao_variants_match_numpy():
    rng = np.random.default_rng(2)
    for kind, op in [(AmoKind.FOR, np.bitwise_or),
                     (AmoKind.FAND, np.bitwise_and),
                     (AmoKind.FXOR, np.bitwise_xor)]:
        init = rng.integers(0, 2**20, (P, 8)).astype(np.int32)
        win = window.Window(data=jnp.asarray(init))
        dst = jnp.asarray(rng.integers(0, P, (P, 5)), jnp.int32)
        off = jnp.asarray(rng.integers(0, 8, (P, 5)), jnp.int32)
        operand = jnp.asarray(rng.integers(0, 2**20, (P, 5)), jnp.int32)
        _, win2 = window.rdma_fao(win, dst, off, operand, kind)
        expect = init.copy()
        for p in range(P):
            for i in range(5):
                d, o = int(dst[p, i]), int(off[p, i])
                expect[d, o] = op(expect[d, o], int(operand[p, i]))
        np.testing.assert_array_equal(np.asarray(win2.data), expect)


# ---------------------------------------------------------------------------
# Hash table
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("fused", [False, True])
@pytest.mark.parametrize("backend", ["rdma_crw", "rdma_cw", "rpc"])
def test_hashtable_insert_find_roundtrip(backend, fused):
    rng = np.random.default_rng(3)
    keys = jnp.asarray(rng.permutation(10000)[:P * 8].reshape(P, 8) + 1,
                       jnp.int32)
    vals = jnp.stack([keys * 2, keys + 5], axis=-1)
    ht = ht_mod.make_hashtable(P, 64, 2)
    if backend == "rpc":
        if fused:
            pytest.skip("no fused variant of the RPC path")
        eng = am_mod.AMEngine(P)
        ht_mod.build_am_handlers(ht, eng)
        ht, ok, probes = ht_mod.insert_rpc(ht, eng, keys, vals)
        assert bool((probes[np.asarray(ok)] >= 1).all())
        found, got = ht_mod.find_rpc(ht, eng, keys)
    else:
        promise = Promise.CRW if backend == "rdma_crw" else Promise.CW
        ht, ok, probes = ht_mod.insert_rdma(ht, keys, vals, promise=promise,
                                            fused=fused)
        ht, found, got = ht_mod.find_rdma(ht, keys, promise=Promise.CR,
                                          fused=fused)
    assert bool(ok.all()) and bool(found.all())
    np.testing.assert_array_equal(np.asarray(got[..., 0]),
                                  np.asarray(keys * 2))
    # misses stay misses
    if backend == "rpc":
        found2, _ = ht_mod.find_rpc(ht, eng, keys + 100000)
    else:
        ht, found2, _ = ht_mod.find_rdma(ht, keys + 100000,
                                         promise=Promise.CR, fused=fused)
    assert not bool(found2.any())


@pytest.mark.parametrize("fused", [False, True])
def test_hashtable_crw_find_with_lock(fused):
    keys = jnp.arange(P * 4, dtype=jnp.int32).reshape(P, 4) + 1
    vals = jnp.stack([keys, keys], axis=-1)
    ht = ht_mod.make_hashtable(P, 32, 2)
    ht, ok, _ = ht_mod.insert_rdma(ht, keys, vals, promise=Promise.CRW,
                                   fused=fused)
    ht, found, got = ht_mod.find_rdma(ht, keys, promise=Promise.CRW,
                                      fused=fused)
    assert bool(found.all())
    # read locks fully released: flag state back to READY with no readers
    recs = ht.win.data.reshape(P, ht.nslots, ht.rec_w)
    flags = np.asarray(recs[..., 0])
    assert ((flags == 0) | (flags == 2)).all()


def test_hashtable_rpc_insert_or_assign_updates():
    """RPC expressivity (paper §II-B): handler does insert-or-assign."""
    eng = am_mod.AMEngine(P)
    ht = ht_mod.make_hashtable(P, 32, 1)
    ht_mod.build_am_handlers(ht, eng)
    keys = jnp.arange(P * 2, dtype=jnp.int32).reshape(P, 2) + 1
    ht, ok1, _ = ht_mod.insert_rpc(ht, eng, keys, keys[..., None] * 10)
    ht, ok2, _ = ht_mod.insert_rpc(ht, eng, keys, keys[..., None] * 20)
    assert bool(ok1.all()) and bool(ok2.all())
    found, got = ht_mod.find_rpc(ht, eng, keys)
    np.testing.assert_array_equal(np.asarray(got[..., 0]),
                                  np.asarray(keys * 20))


# ---------------------------------------------------------------------------
# Fused component phases: bit-exact vs the unfused per-component sequences,
# and the exchange counts the cost model promises
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("promise", [Promise.CRW, Promise.CW])
def test_fused_insert_bit_exact_vs_unfused(promise):
    """Fused claim/write(/publish) == CAS + W (+ FXOR) on a contended batch
    (many keys collide into few slots, so probe chains interleave)."""
    rng = np.random.default_rng(8)
    keys = jnp.asarray(rng.permutation(4000)[:P * 8].reshape(P, 8) + 1,
                       jnp.int32)
    vals = jnp.stack([keys * 3, keys - 7], axis=-1)
    ht_a = ht_mod.make_hashtable(P, 8, 2)    # tiny table -> contention
    ht_b = ht_mod.make_hashtable(P, 8, 2)
    ht_a, ok_a, pr_a = ht_mod.insert_rdma(ht_a, keys, vals, promise=promise,
                                          max_probes=8, fused=False)
    ht_b, ok_b, pr_b = ht_mod.insert_rdma(ht_b, keys, vals, promise=promise,
                                          max_probes=8, fused=True)
    np.testing.assert_array_equal(np.asarray(ht_a.win.data),
                                  np.asarray(ht_b.win.data))
    np.testing.assert_array_equal(np.asarray(ok_a), np.asarray(ok_b))
    np.testing.assert_array_equal(np.asarray(pr_a), np.asarray(pr_b))


@pytest.mark.parametrize("promise", [Promise.CR, Promise.CRW])
def test_fused_find_bit_exact_vs_unfused(promise):
    rng = np.random.default_rng(9)
    keys = jnp.asarray(rng.permutation(4000)[:P * 6].reshape(P, 6) + 1,
                       jnp.int32)
    vals = jnp.stack([keys, keys * 2], axis=-1)
    ht = ht_mod.make_hashtable(P, 16, 2)
    ht, ok, _ = ht_mod.insert_rdma(ht, keys, vals, promise=Promise.CRW)
    probe = jnp.where(jnp.arange(P * 6).reshape(P, 6) % 2 == 0, keys,
                      keys + 100000)   # mix of hits and misses
    ht_a, f_a, v_a = ht_mod.find_rdma(ht, probe, promise=promise,
                                      fused=False)
    ht_b, f_b, v_b = ht_mod.find_rdma(ht, probe, promise=promise,
                                      fused=True)
    np.testing.assert_array_equal(np.asarray(f_a), np.asarray(f_b))
    np.testing.assert_array_equal(np.asarray(v_a), np.asarray(v_b))
    np.testing.assert_array_equal(np.asarray(ht_a.win.data),
                                  np.asarray(ht_b.win.data))


def _count_exchanges(fn):
    """Run fn under a sharding hook that counts routing.exchange calls."""
    count = [0]

    def hook(x, role):
        if role.endswith("_pre"):
            count[0] += 1
        return x

    with routing.sharding_hook(hook):
        jax.block_until_ready(fn())
    return count[0]


def test_exchange_counts_agree_with_costmodel():
    """The engine's actual all-to-all count matches costmodel.exchange_count
    — the roofline collective counter and the model see the same phase
    structure (C_RW find: 4 exchanges/probe fused, was 9 engine-level /
    6 paper-level)."""
    from repro.core import costmodel as cm
    from repro.core.types import Backend
    keys = jnp.arange(P * 4, dtype=jnp.int32).reshape(P, 4) + 1
    vals = jnp.stack([keys, keys], axis=-1)
    ht, _, _ = ht_mod.insert_rdma(ht_mod.make_hashtable(P, 32, 2), keys,
                                  vals, promise=Promise.CRW)

    for fused in (False, True):
        got = _count_exchanges(lambda: ht_mod.find_rdma(
            ht, keys, promise=Promise.CRW, max_probes=1,
            fused=fused)[1])
        want = cm.exchange_count(cm.DSOp.HT_FIND, Promise.CRW, Backend.RDMA,
                                 fused=fused, probes=1)
        if fused:
            want += cm.PLAN_EXCHANGES
        assert got == want, (fused, got, want)
    assert cm.exchange_count(cm.DSOp.HT_FIND, Promise.CRW, Backend.RDMA,
                             fused=True) <= 4

    for fused in (False, True):
        got = _count_exchanges(lambda: ht_mod.insert_rdma(
            ht_mod.make_hashtable(P, 32, 2), keys, vals,
            promise=Promise.CRW, max_probes=1, fused=fused)[0].win.data)
        want = cm.exchange_count(cm.DSOp.HT_INSERT, Promise.CRW,
                                 Backend.RDMA, fused=fused, probes=1)
        if fused:
            want += cm.PLAN_EXCHANGES
        assert got == want, (fused, got, want)


# ---------------------------------------------------------------------------
# Queue
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("promise", [Promise.CRW, Promise.CW])
def test_queue_push_pop_conservation(promise):
    q = q_mod.make_queue(P, host=1, capacity=128, val_words=1)
    vals = jnp.arange(P * 5, dtype=jnp.int32).reshape(P, 5, 1) + 1
    q, ok = q_mod.push_rdma(q, vals, promise=promise)
    assert bool(ok.all())
    q, got, out = q_mod.pop_rdma(q, 6, promise=Promise.CR)
    popped = np.asarray(out[np.asarray(got)]).ravel()
    np.testing.assert_array_equal(np.sort(popped),
                                  np.arange(P * 5) + 1)


def test_queue_checksum_crw_push_costs_no_ready_cas():
    """Checksum queue (paper Fig. 4): reader verifies payload checksum, so
    the push is FAO + W (phases reported by the cost model), yet pops are
    still safe."""
    q = q_mod.make_queue(P, host=0, capacity=64, val_words=2, checksum=True)
    vals = jnp.arange(P * 4 * 2, dtype=jnp.int32).reshape(P, 4, 2)
    q, ok = q_mod.push_rdma(q, vals, promise=Promise.CRW)
    assert bool(ok.all())
    q, got, out = q_mod.pop_rdma(q, 5, promise=Promise.CRW)
    assert int(got.sum()) == P * 4


def test_queue_overflow_reports_failure():
    q = q_mod.make_queue(P, host=0, capacity=6, val_words=1)
    vals = jnp.ones((P, 4, 1), jnp.int32)
    q, ok = q_mod.push_rdma(q, vals, promise=Promise.CW)
    assert int(ok.sum()) == 6                 # ring held exactly capacity
    assert int((~ok).sum()) == P * 4 - 6


def test_queue_rpc_matches_rdma():
    valsA = jnp.arange(P * 3, dtype=jnp.int32).reshape(P, 3, 1) + 1
    qa = q_mod.make_queue(P, host=2, capacity=64, val_words=1)
    qa, ok_a = q_mod.push_rdma(qa, valsA, promise=Promise.CW)
    qb = q_mod.make_queue(P, host=2, capacity=64, val_words=1)
    eng = am_mod.AMEngine(P)
    q_mod.build_am_handlers(qb, eng)
    qb, ok_b = q_mod.push_rpc(qb, eng, valsA)
    assert bool(ok_a.all()) and bool(ok_b.all())
    qa, got_a, out_a = q_mod.pop_rdma(qa, 3, promise=Promise.CR)
    qb, got_b, out_b = q_mod.pop_rpc(qb, eng, 3)
    a = np.sort(np.asarray(out_a[np.asarray(got_a)]).ravel())
    b = np.sort(np.asarray(out_b[np.asarray(got_b)]).ravel())
    np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("promise", [Promise.CRW, Promise.CW])
def test_queue_planned_bit_exact_vs_unplanned(promise):
    """One RoutePlan across the push/pop component phases == fresh routing
    per phase (delivered ops and final ring state identical)."""
    vals = jnp.arange(P * 5, dtype=jnp.int32).reshape(P, 5, 1) + 1
    qa = q_mod.make_queue(P, host=1, capacity=16, val_words=1)
    qb = q_mod.make_queue(P, host=1, capacity=16, val_words=1)
    qa, ok_a = q_mod.push_rdma(qa, vals, promise=promise, planned=False)
    qb, ok_b = q_mod.push_rdma(qb, vals, promise=promise, planned=True)
    np.testing.assert_array_equal(np.asarray(ok_a), np.asarray(ok_b))
    np.testing.assert_array_equal(np.asarray(qa.win.data),
                                  np.asarray(qb.win.data))
    qa, got_a, out_a = q_mod.pop_rdma(qa, 6, promise=Promise.CR,
                                      planned=False)
    qb, got_b, out_b = q_mod.pop_rdma(qb, 6, promise=Promise.CR,
                                      planned=True)
    np.testing.assert_array_equal(np.asarray(got_a), np.asarray(got_b))
    np.testing.assert_array_equal(
        np.asarray(out_a)[np.asarray(got_a)],
        np.asarray(out_b)[np.asarray(got_b)])
    np.testing.assert_array_equal(np.asarray(qa.win.data),
                                  np.asarray(qb.win.data))


def test_queue_local_promise_zero_phases():
    q = q_mod.make_queue(P, host=0, capacity=16, val_words=1)
    q, ok = q_mod.push_local(q, jnp.arange(5, dtype=jnp.int32)[:, None])
    assert bool(ok.all())
    q, got, vals = q_mod.pop_local(q, 8)
    assert int(got.sum()) == 5
    np.testing.assert_array_equal(np.asarray(vals[:5, 0]), np.arange(5))


# ---------------------------------------------------------------------------
# The phase log: an explicit bound + drain, and coalescing stats recorded
# into decision_scope entries.
# ---------------------------------------------------------------------------
def test_window_phase_log_bounded_and_drains(monkeypatch):
    win = window.make_window(P, 32)
    dst = jnp.zeros((P, 4), jnp.int32)
    off = jnp.zeros((P, 4), jnp.int32)
    window.drain_phase_log()
    monkeypatch.setattr(trace, "PHASE_LOG_MAX", 3)
    with window.decision_scope("dec"):
        for _ in range(4):  # 4 FAOs x 1 logged phase each (role="fao")
            _, win = window.rdma_fao(win, dst, off, 1, AmoKind.FAA)
    log = window.drain_phase_log()
    assert len(log) == 3          # bounded: the oldest entry dropped
    assert window.drain_phase_log() == []  # drained
    # outside a decision scope nothing is logged
    window.rdma_fao(win, dst, off, 1, AmoKind.FAA)
    assert window.drain_phase_log() == []


def test_phase_log_records_coalescing_stats():
    win = window.make_window(P, 32)
    dst = jnp.zeros((P, 6), jnp.int32)
    off = jnp.zeros((P, 6), jnp.int32)  # single hot word: 6 -> 1 per origin
    window.drain_phase_log()
    with window.decision_scope("dec"):
        window.rdma_fao(win, dst, off, 1, AmoKind.FAA, coalesce=True)
        window.rdma_fao(win, dst, off, 1, AmoKind.FAA)
    (role_a, dec_a, info_a), (role_b, dec_b, info_b) = \
        window.drain_phase_log()
    assert role_a == role_b == "fao" and dec_a == dec_b == "dec"
    assert info_b is None                       # uncoalesced phase
    assert info_a["coalesced"] is True
    assert info_a["rows_in"] == P * 6
    assert info_a["rows_out"] == P              # one rep per origin
    assert info_a["dedup_ratio"] == pytest.approx(1 / 6)
