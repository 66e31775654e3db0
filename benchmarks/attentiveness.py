"""Paper Fig. 6: queue-insert latency as the target's attentiveness
degrades (interspersed compute between AM dispatch points).

Emulation: the target services AMs only at dispatch points separated by
`busy_us` of real compute (busy-wait). A request arrives uniformly inside
the busy window, so it waits busy/2 on average. Three curves:

  am            request waits for the next dispatch point
  am_pt         a progress thread services immediately, at a constant
                contention factor (cost model's pt_overhead)
  rdma          the NIC lane (window phase engine) is always live:
                latency independent of target compute — the paper's
                central RDMA advantage.

CI knobs (de-flaking): the RNG is seeded (`seed`), and the sweep is
env-overridable — REPRO_ATT_ROUNDS (int), REPRO_ATT_BUSY (comma list of
µs), REPRO_ATT_SEED. `--smoke` runs a seconds-scale two-point sweep that
only asserts the structural Fig. 6 shape (AM latency grows with busy).

Fault sweep (DESIGN.md §10): `--faults` replays a mixed insert/find
stream per arm under seeded FaultPlans of increasing loss
(`--loss-rate` / REPRO_FAULT_LOSS comma list, `--dead-owner` /
REPRO_FAULT_DEAD rank) and records the plane's deterministic retransmit
counters plus conformance vs the fault-free oracle into
artifacts/bench/BENCH_faults.json (trajectory.py files it under a
"faults" section). `--smoke-chaos` is the CI gate: a seeded soak of
drops + duplicates + one permanently dead owner at P=8 must stay
conformant on every arm, and a permanently stalled queue must raise
RemoteTimeout inside the retry deadline instead of hanging.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import adaptive as ad_mod
from repro.core import am as am_mod
from repro.core import costmodel as cm
from repro.core import faults as flt
from repro.core import hashtable as ht_mod
from repro.core import pipeline as pl_mod
from repro.core import queue as q_mod
from repro.core.types import Promise

from . import components
from .common import Csv, busy_wait as _busy_wait, stamp_label


def _env_overrides(rounds, busy_list, seed):
    rounds = int(os.environ.get("REPRO_ATT_ROUNDS", rounds))
    busy = os.environ.get("REPRO_ATT_BUSY")
    if busy:
        busy_list = tuple(float(b) for b in busy.split(","))
    seed = int(os.environ.get("REPRO_ATT_SEED", seed))
    return rounds, busy_list, seed


def bench_attentiveness(P: int = 4, n: int = 16, rounds: int = 30,
                        busy_list=(0, 1, 2, 4, 8, 16, 32), seed: int = 0):
    """Latency is per *dispatch* (one service opportunity), not per op:
    aggregation would otherwise amortize the attentiveness wait across the
    batch, which is a real property of the batched engine but hides the
    paper's per-request effect being measured here.

    Arguments are taken literally; only main() applies the REPRO_ATT_*
    env overrides (so smoke()'s fixed two-point sweep cannot be bent into
    a shape that fails its own assertion)."""
    vals = jnp.ones((P, n, 1), jnp.int32)
    ops = 1  # per-dispatch latency
    q0 = q_mod.make_queue(P, 0, 1 << 16, 1)
    eng = am_mod.AMEngine(P)
    q_mod.build_am_handlers(q0, eng)

    def am_phase(data):
        q = q_mod.DQueue(win=q_mod.Window(data=data), host=0,
                         capacity=1 << 16, val_words=1)
        q, _ = q_mod.push_rpc(q, eng, vals)
        return q.win.data

    def rdma_phase(data):
        q = q_mod.DQueue(win=q_mod.Window(data=data), host=0,
                         capacity=1 << 16, val_words=1)
        q, _ = q_mod.push_rdma(q, vals, promise=Promise.CW)
        return q.win.data

    am_j = jax.jit(am_phase)
    rdma_j = jax.jit(rdma_phase)
    jax.block_until_ready(am_j(q0.win.data))
    jax.block_until_ready(rdma_j(q0.win.data))
    rng = np.random.default_rng(seed)

    out = []
    for busy in busy_list:
        lat = {"am": [], "am_pt": [], "rdma": []}
        for _ in range(rounds):
            # request issued at a uniform offset into the busy window
            offset = rng.uniform(0, busy) if busy else 0.0
            t0 = time.perf_counter()
            _busy_wait(busy - offset)        # residual target compute
            jax.block_until_ready(am_j(q0.win.data))
            lat["am"].append((time.perf_counter() - t0) * 1e6 / ops)
            # progress thread: immediate service, constant overhead
            t0 = time.perf_counter()
            jax.block_until_ready(am_j(q0.win.data))
            dt = (time.perf_counter() - t0) * 1e6 / ops
            lat["am_pt"].append(dt * cm.CORI_PHASE1.pt_overhead)
            # rdma: NIC lane needs no target participation
            t0 = time.perf_counter()
            jax.block_until_ready(rdma_j(q0.win.data))
            lat["rdma"].append((time.perf_counter() - t0) * 1e6 / ops)
        med = {k: float(np.median(v)) for k, v in lat.items()}
        out.append((busy, med))
    return out


def main(out="artifacts/bench"):
    csv = Csv(["benchmark", "busy_us", "impl", "us_per_op"])
    rounds, busy_list, seed = _env_overrides(30, (0, 1, 2, 4, 8, 16, 32), 0)
    rows = bench_attentiveness(rounds=rounds, busy_list=busy_list,
                               seed=seed)
    for busy, med in rows:
        for impl, us in med.items():
            csv.add("attentiveness(fig6)", busy, impl, f"{us:.3f}")
    csv.dump(f"{out}/attentiveness.csv")
    # Fig. 6 structure: AM latency grows with busy; RDMA roughly flat;
    # crossover exists.
    am0 = rows[0][1]["am"]
    amN = rows[-1][1]["am"]
    r0 = rows[0][1]["rdma"]
    rN = rows[-1][1]["rdma"]
    print(f"# am {am0:.2f} -> {amN:.2f} us (grows); "
          f"rdma {r0:.2f} -> {rN:.2f} us (flat-ish)")
    crossover = next((b for b, m in rows if m["am"] > m["rdma"]), None)
    print(f"# am/rdma crossover at busy ~= {crossover} us")
    return rows


def smoke() -> bool:
    """Fast CI path: a seeded two-point sweep asserting only the robust
    structural property — AM latency strictly grows once the busy window
    dwarfs the dispatch itself (the wait is busy/2 in expectation, so the
    1000 µs point exceeds the 0 µs point by construction, not by luck)."""
    rows = bench_attentiveness(rounds=5, busy_list=(0, 1000), seed=0)
    am0, amN = rows[0][1]["am"], rows[-1][1]["am"]
    ok = amN > am0
    print(f"# smoke: am {am0:.1f} -> {amN:.1f} us at busy 0 -> 1000 "
          f"({'OK' if ok else 'FAIL'})")
    return ok


# ---------------------------------------------------------------------------
# Fault sweep + chaos gate (DESIGN.md §10)
# ---------------------------------------------------------------------------
_FAULT_ARMS = ("rdma", "rdma_fused", "am", "auto")


def _val_of(keys):
    return jnp.concatenate([((keys * 31 + 7) & 0x7FFFFF)[..., None],
                            ((keys * 17 + 3) & 0x7FFFFF)[..., None]],
                           axis=-1).astype(jnp.int32)


class _ArmStream:
    """Mixed insert/find stream on one arm — the fault-free instance is
    the oracle (cross-arm conformance is pinned by the test suite)."""

    def __init__(self, nranks: int, arm: str, nslots: int = 256):
        self.ht = ht_mod.make_hashtable(nranks, nslots, 2)
        self.auto = ad_mod.AdaptiveEngine(nranks,
                                          am_engine=am_mod.AMEngine(nranks),
                                          policy="round_robin")
        if arm != "auto":
            self.auto.policy = "cost"
            self.auto.force_arm = arm

    def step(self, keys):
        self.ht, ok, _ = self.auto.ht_insert(self.ht, keys, _val_of(keys))
        self.ht, found, vals = self.auto.ht_find(self.ht, keys)
        return np.asarray(ok), np.asarray(found), np.asarray(vals)


def _distinct_batches(nranks: int, nbatches: int, n: int, seed: int):
    rng = np.random.default_rng(seed)
    flat = rng.choice(np.arange(1, 1 << 20), size=nbatches * nranks * n,
                      replace=False)
    return [jnp.asarray(flat[i * nranks * n:(i + 1) * nranks * n]
                        .reshape(nranks, n), jnp.int32)
            for i in range(nbatches)]


def _run_schedule(nranks: int, arm: str, plan, batches):
    """(conformant, wall_us_per_batch): replay `batches` under `plan`
    next to a fault-free oracle and compare every visible output."""
    oracle, chaos = _ArmStream(nranks, arm), _ArmStream(nranks, arm)
    plan.reset()
    conformant = True
    t0 = time.perf_counter()
    for keys in batches:
        o = oracle.step(keys)
        with flt.fault_scope(plan):
            c = chaos.step(keys)
        conformant &= all(np.array_equal(a, b) for a, b in zip(o, c))
    wall = (time.perf_counter() - t0) * 1e6 / max(1, len(batches))
    return conformant, wall


def _fault_env(loss_rates, dead_owner):
    env = os.environ.get("REPRO_FAULT_LOSS")
    if env:
        loss_rates = tuple(float(x) for x in env.split(","))
    if "--loss-rate" in sys.argv:
        loss_rates = (float(sys.argv[sys.argv.index("--loss-rate") + 1]),)
    env = os.environ.get("REPRO_FAULT_DEAD")
    if env is not None:
        dead_owner = int(env)
    if "--dead-owner" in sys.argv:
        dead_owner = int(sys.argv[sys.argv.index("--dead-owner") + 1])
    return loss_rates, dead_owner


def fault_sweep(nranks: int = 8, nbatches: int = 4, n: int = 8,
                loss_rates=(0.05, 0.2, 0.4), dead_owner=None,
                seed: int = 7, out: str = "artifacts/bench"):
    """Per-loss-rate fault sweep: conformance plus the plane's
    deterministic retransmit counters (pure functions of the seed, so
    the trajectory gate sees run-to-run-stable numbers, unlike wall
    time, which is reported but not filed)."""
    report = {"schema": "bench-faults-v1", "P": nranks,
              "dead_owner": dead_owner, "seed": seed, "sweep": {}}
    batches = _distinct_batches(nranks, nbatches, n, seed)
    for lr in loss_rates:
        dead = {int(dead_owner): None} if dead_owner is not None else None
        row = {"drop_rate": lr, "dup_rate": lr / 2,
               "nonconformant_arms": 0}
        for arm in _FAULT_ARMS:
            plan = flt.FaultPlan(nranks, seed=seed, drop_rate=lr,
                                 dup_rate=lr / 2, dead_owners=dead)
            okc, wall = _run_schedule(nranks, arm, plan, batches)
            s = plan.stats()
            row[f"wall_us_{arm}"] = round(wall, 1)
            row["nonconformant_arms"] += 0 if okc else 1
            if arm == "rdma":     # plane counters: same plan per arm
                row.update(retransmits=s["dropped"],
                           dup_redeliveries=s["dup_filtered"],
                           backoff_units=round(s["backoff_total"], 2),
                           exhausted=s["exhausted"])
        report["sweep"][f"{lr:g}"] = row
        print(f"# faults loss={lr:g}: retransmits={row['retransmits']} "
              f"dups={row['dup_redeliveries']} "
              f"nonconformant={row['nonconformant_arms']}")
    os.makedirs(out, exist_ok=True)
    with open(f"{out}/BENCH_faults.json", "w") as f:
        json.dump(stamp_label(report), f, indent=2)
    return report


def smoke_chaos() -> bool:
    """CI chaos gate: a seeded soak — drops + duplicates + one
    permanently dead owner at P=8 — must stay conformant with the
    fault-free oracle on every arm, the plane must never exhaust a row
    (exactly-once holds inside the retry budget), and a permanently
    stalled deferred queue must fail fast with RemoteTimeout instead of
    hanging past the retry deadline."""
    nranks, ok = 8, True
    batches = _distinct_batches(nranks, nbatches=3, n=8, seed=11)
    for arm in _FAULT_ARMS:
        plan = flt.FaultPlan(nranks, seed=17, drop_rate=0.25,
                             dup_rate=0.30, dead_owners={2: None})
        conf, wall = _run_schedule(nranks, arm, plan, batches)
        s = plan.stats()
        arm_ok = conf and s["exhausted"] == 0
        ok &= arm_ok
        print(f"# chaos {arm:10s}: conformant={conf} "
              f"exhausted={s['exhausted']} ({wall:.0f} us/batch) "
              f"({'OK' if arm_ok else 'FAIL'})")
    # liveness: dead queue -> typed timeout inside the deadline ceiling
    plan = flt.FaultPlan(nranks, seed=17, stall_forever=True,
                         retry=flt.RetryPolicy(deadline=8))
    plan.reset()
    eng = am_mod.AMEngine(nranks)
    ht = ht_mod.make_hashtable(nranks, 256, 2)
    keys = batches[0]
    t0 = time.perf_counter()
    try:
        with flt.fault_scope(plan):
            pipe = pl_mod.Pipeline(ht, depth=2, am_engine=eng)

            def op(state, k=keys):
                st, okk, _ = ht_mod.insert_rdma(st, k, _val_of(k))
                return st, okk

            h = pipe.submit(op, deferred=True)
            h.result(timeout=8)
        timed_out = False
    except flt.RemoteTimeout:
        timed_out = True
    dt = time.perf_counter() - t0
    ok &= timed_out and dt < 60.0
    print(f"# chaos dead-queue: RemoteTimeout={timed_out} in {dt:.1f}s "
          f"({'OK' if timed_out else 'FAIL'})")
    return ok


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if "--smoke" in sys.argv:
        sys.exit(0 if smoke() else 1)
    if "--smoke-chaos" in sys.argv:
        sys.exit(0 if smoke_chaos() else 1)
    if "--faults" in sys.argv:
        rates, dead = _fault_env((0.05, 0.2, 0.4), None)
        fault_sweep(loss_rates=rates, dead_owner=dead)
        sys.exit(0)
    main()
