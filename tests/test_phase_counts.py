"""Phase-count regression: pin the DESIGN.md §2 exchange table so future
refactors cannot silently add network phases.

Two mechanisms:
  * in-process: a sharding hook counts `routing.exchange` calls per role
    (the same counter tests/test_datastructures.py uses) — put=1,
    get/cas/fao=2, AM dispatch=2, reply-elided dispatch=1, and exactly ONE
    occupancy (mask) exchange per planned batch;
  * subprocess (tests/phase_count_probe.py): the engine lowered under a
    real 8-way sharded mesh, all-to-alls counted in the optimized HLO by
    the launch/hlo_stats collective counter, plus the planner's
    one-argsort claim (make_plan HLO has exactly 1 sort, route_with_plan
    has 0). XLA_FLAGS must precede jax init, hence the subprocess.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import am as am_mod
from repro.core import costmodel as cm
from repro.core import queue as q_mod
from repro.core import routing, window
from repro.core.types import AmoKind, Backend, Promise

P = 4


class ExchangeCounter:
    """Counts exchanges by role via the sharding hook (each exchange calls
    the hook twice: role_pre and role_post)."""

    def __init__(self):
        self.roles = []

    def hook(self, x, role):
        if role.endswith("_pre"):
            self.roles.append(role[:-4])
        return x

    def run(self, fn):
        self.roles = []
        with routing.sharding_hook(self.hook):
            jax.block_until_ready(fn())
        return len(self.roles)

    def mask_exchanges(self):
        return sum(1 for r in self.roles if r.endswith("_mask"))


def _fixtures():
    rng = np.random.default_rng(0)
    dst = jnp.asarray(rng.integers(0, P, (P, 6)), jnp.int32)
    off = jnp.asarray(rng.integers(0, 32, (P, 6)), jnp.int32)
    win = window.make_window(P, 64)
    vals = jnp.ones((P, 6, 2), jnp.int32)
    return dst, off, win, vals


def test_component_op_exchange_table_planned():
    """The §2 component table on the planned engine: put=1, get=2, cas=2,
    fao=2 exchanges — and none of them is a mask exchange."""
    dst, off, win, vals = _fixtures()
    plan = routing.make_plan(dst, cap=6)
    c = ExchangeCounter()
    assert c.run(lambda: window.rdma_put(win, dst, off, vals,
                                         plan=plan)) == 1
    assert c.mask_exchanges() == 0
    assert c.run(lambda: window.rdma_get(win, dst, off, 2, plan=plan)) == 2
    assert c.run(lambda: window.rdma_cas(win, dst, off, 0, 1,
                                         plan=plan)) == 2
    assert c.run(lambda: window.rdma_fao(win, dst, off, 1, AmoKind.FAA,
                                         plan=plan)) == 2
    # fused descriptors are ordinary two-exchange component ops
    assert c.run(lambda: window.rdma_cas_put(win, dst, off, 0, 1, off + 1,
                                             vals, plan=plan)) == 2
    assert c.run(lambda: window.rdma_fao_get(win, dst, off, 1, AmoKind.FAA,
                                             off, 2, plan=plan)) == 2


def test_component_op_exchange_table_unplanned():
    """Unplanned route() pays one extra occupancy-mask exchange per phase
    (engine-level 2 for put, 3 for two-phase ops)."""
    dst, off, win, vals = _fixtures()
    c = ExchangeCounter()
    assert c.run(lambda: window.rdma_put(win, dst, off, vals)) == 2
    assert c.mask_exchanges() == 1
    assert c.run(lambda: window.rdma_cas(win, dst, off, 0, 1)) == 3
    assert c.mask_exchanges() == 1


def test_am_dispatch_exchange_table():
    """AM dispatch = 2 exchanges; reply-elided (reply_width=0) = 1; the
    plan's occupancy exchange happens once at plan time, not per dispatch."""
    dst, off, win, vals = _fixtures()
    eng = am_mod.AMEngine(P)
    echo = eng.register("echo", lambda l, p, m: (l, p[:, :1]),
                        reply_width=1)
    fire = eng.register("fire", lambda l, p, m: (l + p.sum(),
                                                 p[:, :0]), reply_width=0)
    state = jnp.zeros((P, 4), jnp.int32)
    c = ExchangeCounter()
    plan = routing.make_plan(dst, cap=6)
    assert c.run(lambda: eng.dispatch(echo, state, dst, vals,
                                      plan=plan)) == 2
    assert c.run(lambda: eng.dispatch(fire, state, dst, vals,
                                      plan=plan)) == 1
    # unplanned: +1 mask exchange riding with the request
    assert c.run(lambda: eng.dispatch(echo, state, dst, vals)) == 3
    assert c.mask_exchanges() == 1


def test_cached_dispatch_is_traced_again_under_a_new_hook():
    """A dispatch program cached under one sharding hook is traced anew
    under another: the hook is read while tracing, so a reused program
    would carry the old hook's constraints (and count nothing here)."""
    dst, _, _, vals = _fixtures()
    eng = am_mod.AMEngine(P)
    echo = eng.register("echo", lambda l, p, m: (l, p[:, :1]),
                        reply_width=1)
    state = jnp.zeros((P, 4), jnp.int32)
    jax.block_until_ready(eng.dispatch(echo, state, dst, vals))
    c = ExchangeCounter()
    assert c.run(lambda: eng.dispatch(echo, state, dst, vals)) == 3
    assert c.mask_exchanges() == 1
    assert len(eng._programs) == 2


def test_planned_batch_has_one_occupancy_exchange():
    """A planned probe loop exchanges the occupancy mask exactly ONCE per
    batch (at plan time); every subsequent phase ships payload only."""
    from repro.core import hashtable as ht_mod
    keys = jnp.arange(P * 4, dtype=jnp.int32).reshape(P, 4) + 1
    vals = jnp.stack([keys, keys], axis=-1)
    ht, _, _ = ht_mod.insert_rdma(ht_mod.make_hashtable(P, 32, 2), keys,
                                  vals, promise=Promise.CRW)
    c = ExchangeCounter()
    c.run(lambda: ht_mod.find_rdma(ht, keys, promise=Promise.CRW,
                                   max_probes=1, fused=True)[1])
    assert c.mask_exchanges() == cm.PLAN_EXCHANGES == 1
    c.run(lambda: ht_mod.insert_rdma(ht_mod.make_hashtable(P, 32, 2), keys,
                                     vals, promise=Promise.CRW,
                                     max_probes=1, fused=True)[0].win.data)
    assert c.mask_exchanges() == 1
    # unfused engine: one mask exchange per phase instead
    c.run(lambda: ht_mod.find_rdma(ht, keys, promise=Promise.CRW,
                                   max_probes=1, fused=False)[1])
    assert c.mask_exchanges() == 3  # lock FAO + get + unlock FAO


def test_coalescing_adds_zero_exchanges():
    """The §6 pin: sender-side coalescing is pure local compute. A
    coalesced component phase issues exactly the planned engine's
    exchange counts (put=1, get/cas/fao=2), a coalesce_plan pays the same
    ONE occupancy exchange as make_plan, and a coalesced AM dispatch stays
    at 2 exchanges."""
    dst, off, win, vals = _fixtures()
    hot = jnp.zeros_like(off)  # everything duplicates onto one word
    c = ExchangeCounter()
    # phase-local coalescing, unplanned: same counts as the unplanned
    # engine (payload + mask [+ reply])
    assert c.run(lambda: window.rdma_put(win, dst, hot, vals,
                                         coalesce=True)) == 2
    assert c.run(lambda: window.rdma_fao(win, dst, hot, 1, AmoKind.FAA,
                                         coalesce=True)[1].data) == 3
    # coalesce_plan: ONE occupancy exchange, exactly PLAN_EXCHANGES
    assert c.run(lambda: routing.coalesce_plan(dst, hot, cap=6).plan.mask
                 ) == 1
    assert c.mask_exchanges() == cm.PLAN_EXCHANGES == 1
    cplan = routing.coalesce_plan(dst, hot, cap=6)
    assert c.run(lambda: window.rdma_get(win, dst, hot, 2,
                                         plan=cplan)) == 2
    assert c.mask_exchanges() == 0
    assert c.run(lambda: window.rdma_cas(win, dst, hot, 0, 1,
                                         plan=cplan)[1].data) == 2
    assert c.run(lambda: window.rdma_fao_get(win, dst, hot, 1, AmoKind.FAA,
                                             hot, 2, plan=cplan)[2].data
                 ) == 2
    # coalesced AM dispatch: the paper's 2-exchange round trip, unchanged
    eng = am_mod.AMEngine(P)
    echo = eng.register("echo", lambda l, p, m: (l, p[:, :1]),
                        reply_width=1)
    state = jnp.zeros((P, 4), jnp.int32)
    plan = routing.make_plan(dst, cap=6)
    assert c.run(lambda: eng.dispatch(echo, state, dst, vals, plan=plan,
                                      coalesce=True)) == 2


def test_coalesced_fused_insert_exchanges_match_uncoalesced():
    """A whole coalesced fused C_RW insert traces the same phase
    structure as the uncoalesced one — ONE plan occupancy exchange + the
    probe request/reply pair — while on duplicate-heavy batches the
    adaptive while_loop runs FEWER probe phases at runtime (every
    duplicate group resolves with its representative's first claim,
    visible in the returned probe counts)."""
    from repro.core import hashtable as ht_mod
    keys = jnp.broadcast_to(jnp.arange(1, P + 1, dtype=jnp.int32)[:, None],
                            (P, 6)).astype(jnp.int32)  # 6 dups per origin
    vals = jnp.stack([keys, keys], axis=-1)
    c = ExchangeCounter()
    got_unc = c.run(lambda: ht_mod.insert_rdma(
        ht_mod.make_hashtable(P, 64, 2), keys, vals, promise=Promise.CRW,
        max_probes=8, fused=True)[0].win.data)
    _, _, probes_co = ht_mod.insert_rdma(
        ht_mod.make_hashtable(P, 64, 2), keys, vals, promise=Promise.CRW,
        max_probes=8, fused=True, coalesce=True)
    got_co = c.run(lambda: ht_mod.insert_rdma(
        ht_mod.make_hashtable(P, 64, 2), keys, vals, promise=Promise.CRW,
        max_probes=8, fused=True, coalesce=True)[0].win.data)
    assert c.mask_exchanges() == 1  # still ONE plan occupancy exchange
    assert got_co == got_unc        # zero extra exchanges, trace-level
    _, _, probes_unc = ht_mod.insert_rdma(
        ht_mod.make_hashtable(P, 64, 2), keys, vals, promise=Promise.CRW,
        max_probes=8, fused=True)
    assert int(probes_co.max()) == 1      # every dup rides the rep's claim
    assert int(probes_unc.max()) == 6     # uncoalesced dups probe onward


def test_queue_exchange_counts_agree_with_costmodel():
    """Queue push/pop engine exchanges match costmodel.exchange_count (the
    §2 table), extending the hash-table cross-check in
    tests/test_datastructures.py to the hosted queue."""
    vals = jnp.ones((P, 5, 2), jnp.int32)
    c = ExchangeCounter()
    for promise in (Promise.CRW, Promise.CW):
        for planned in (False, True):
            q = q_mod.make_queue(P, 0, 64, 2)
            got = c.run(lambda: q_mod.push_rdma(
                q, vals, promise=promise, planned=planned,
                max_cas_rounds=1)[0].win.data)
            want = cm.exchange_count(cm.DSOp.Q_PUSH, promise, Backend.RDMA,
                                     fused=planned)
            if planned:
                want += cm.PLAN_EXCHANGES
            assert got == want, (promise, planned, got, want)
    for promise in (Promise.CRW, Promise.CR):
        for planned in (False, True):
            q = q_mod.make_queue(P, 0, 64, 2)
            q, _ = q_mod.push_rdma(q, vals, promise=Promise.CW)
            got = c.run(lambda: q_mod.pop_rdma(
                q, 5, promise=promise, planned=planned,
                max_cas_rounds=1)[0].win.data)
            want = cm.exchange_count(cm.DSOp.Q_POP, promise, Backend.RDMA,
                                     fused=planned)
            if planned:
                want += cm.PLAN_EXCHANGES
            assert got == want, (promise, planned, got, want)


def test_rpc_exchange_count_constant_in_handler_complexity():
    """The paper's central RPC property at the engine level: dispatch costs
    the same 2 exchanges whether the handler is an echo or a full
    sequential hash-table probe loop."""
    from repro.core import hashtable as ht_mod
    keys = jnp.arange(P * 4, dtype=jnp.int32).reshape(P, 4) + 1
    vals = keys[..., None]
    ht = ht_mod.make_hashtable(P, 64, 1)
    eng = am_mod.AMEngine(P)
    ht_mod.build_am_handlers(ht, eng)
    c = ExchangeCounter()
    got_insert = c.run(lambda: ht_mod.insert_rpc(ht, eng, keys,
                                                 vals)[0].win.data)
    got_find = c.run(lambda: ht_mod.find_rpc(ht, eng, keys)[0])
    # unplanned dispatch: request + mask + reply = 3 engine exchanges,
    # independent of what the handler does
    assert got_insert == got_find == cm.exchange_count(
        cm.DSOp.HT_INSERT, Promise.CRW, Backend.RPC, fused=False) == 3


# ---------------------------------------------------------------------------
# Scale-parameterized phase counts (DESIGN.md §9): the §2 exchange table
# is P-INDEPENDENT — growing the shard count widens each exchange's lanes
# but never adds a network phase. Pinned at P=16 and P=64 so the scaling
# benches measure wider exchanges, not silently more of them.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("scale_p", (16, 64))
def test_exchange_counts_p_independent(scale_p):
    """Planned put=1, get=2, cas=2, fao=2, AM dispatch=2, plan occupancy=1
    at P=16/64 — identical to the P=4 table above."""
    rng = np.random.default_rng(scale_p)
    dst = jnp.asarray(rng.integers(0, scale_p, (scale_p, 4)), jnp.int32)
    off = jnp.asarray(rng.integers(0, 32, (scale_p, 4)), jnp.int32)
    win = window.make_window(scale_p, 64)
    vals = jnp.ones((scale_p, 4, 2), jnp.int32)
    plan = routing.make_plan(dst, cap=4)
    c = ExchangeCounter()
    assert c.run(lambda: window.rdma_put(win, dst, off, vals, plan=plan)) == 1
    assert c.run(lambda: window.rdma_get(win, dst, off, 2, plan=plan)) == 2
    assert c.run(lambda: window.rdma_cas(win, dst, off, 0, 1, plan=plan)) == 2
    assert c.run(lambda: window.rdma_fao(win, dst, off, 1, AmoKind.FAA,
                                         plan=plan)) == 2
    assert c.run(lambda: routing.make_plan(dst, cap=4).mask) == 1
    assert c.mask_exchanges() == cm.PLAN_EXCHANGES == 1
    eng = am_mod.AMEngine(scale_p)
    echo = eng.register("echo", lambda l, p, m: (l, p[:, :1]),
                        reply_width=1)
    state = jnp.zeros((scale_p, 4), jnp.int32)
    assert c.run(lambda: eng.dispatch(echo, state, dst, vals,
                                      plan=plan)) == 2


@pytest.mark.parametrize("scale_p", (16, 64))
def test_planned_ht_batch_one_occupancy_exchange_at_scale(scale_p):
    """A fused hash-table batch at P=16/64 still exchanges the occupancy
    mask exactly ONCE (at plan time) — the §9 scaling claim that per-batch
    phase structure is flat in P, and the coalesce plan's occupancy is
    bit-identical to the plain plan's on distinct traffic."""
    from repro.core import hashtable as ht_mod
    keys = (jnp.arange(scale_p * 4, dtype=jnp.int32).reshape(scale_p, 4)
            + 1)
    vals = jnp.stack([keys, keys], axis=-1)
    ht, _, _ = ht_mod.insert_rdma(ht_mod.make_hashtable(scale_p, 64, 2),
                                  keys, vals, promise=Promise.CRW)
    c = ExchangeCounter()
    c.run(lambda: ht_mod.find_rdma(ht, keys, promise=Promise.CRW,
                                   max_probes=1, fused=True)[1])
    assert c.mask_exchanges() == cm.PLAN_EXCHANGES == 1
    c.run(lambda: ht_mod.insert_rdma(
        ht_mod.make_hashtable(scale_p, 64, 2), keys, vals,
        promise=Promise.CRW, max_probes=1, fused=True)[0].win.data)
    assert c.mask_exchanges() == 1
    # occupancy bit-exactness across the plan paths
    rng = np.random.default_rng(scale_p + 1)
    dst = jnp.asarray(rng.integers(0, scale_p, (scale_p, 5)), jnp.int32)
    off = jnp.asarray(rng.integers(0, 64, (scale_p, 5)), jnp.int32)
    plain = routing.make_plan(dst, cap=5)
    co = routing.coalesce_plan(dst, off, cap=5)
    np.testing.assert_array_equal(np.asarray(plain.mask),
                                  np.asarray(co.plan.mask))


# ---------------------------------------------------------------------------
# Sharded-HLO cross-check (the roofline collective counter sees the same
# phase structure the hook counts).
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def hlo_counts():
    probe = os.path.join(os.path.dirname(__file__), "phase_count_probe.py")
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.join(os.path.dirname(__file__), "..", "src"),
         os.environ.get("PYTHONPATH", "")]))
    try:
        out = subprocess.run([sys.executable, probe], capture_output=True,
                             text=True, timeout=900, env=env)
    except subprocess.TimeoutExpired:
        pytest.skip("phase_count_probe timed out")
    if out.returncode != 0:
        pytest.skip(f"sharded lowering unavailable: {out.stderr[-500:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_hlo_all_to_all_counts_pin_exchange_table(hlo_counts):
    """Lowered, SPMD-partitioned HLO emits exactly the §2 table's
    all-to-alls: put=1, get/cas/fao=2, dispatch=2, reply-elided=1, plan=1,
    and the unplanned engine's extra mask exchange shows up as +1."""
    c = hlo_counts
    assert c["put"]["a2a"] == 1
    assert c["get"]["a2a"] == 2
    assert c["cas"]["a2a"] == 2
    assert c["fao"]["a2a"] == 2
    assert c["cas_unplanned"]["a2a"] == 3
    assert c["dispatch"]["a2a"] == 2
    assert c["dispatch_elided"]["a2a"] == 1
    assert c["make_plan"]["a2a"] == 1
    assert c["route_with_plan"]["a2a"] == 1


def test_hlo_planned_probe_loop_is_one_argsort(hlo_counts):
    """The route-plan claim in HLO: make_plan lowers to exactly ONE sort
    (the stable argsort by destination) and a plan-reusing payload phase
    contains NO sort at all."""
    c = hlo_counts
    assert c["make_plan"]["sorts"] == 1
    assert c["route_with_plan"]["sorts"] == 0


def test_hlo_fused_insert_matches_costmodel(hlo_counts):
    """Whole fused C_RW insert at max_probes=1: probe exchanges + the one
    plan exchange, agreeing with costmodel.exchange_count."""
    want = cm.exchange_count(cm.DSOp.HT_INSERT, Promise.CRW, Backend.RDMA,
                             fused=True, probes=1) + cm.PLAN_EXCHANGES
    assert hlo_counts["ht_insert_fused"]["a2a"] == want == 3
