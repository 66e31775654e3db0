"""Scale as a first-class axis: weak + strong scaling sweeps over the
simulated shard count P (DESIGN.md §9, ISSUE 8 tentpole).

The paper measures at fixed machine scale; this repo's emulation carries P
as the leading array dimension, so "more ranks" is a reshape, not a
cluster — which makes shard count a sweepable benchmark axis on one host.
For P = 8 -> 64 -> 256 this bench measures every data-structure op
(hash-table insert/find, queue push/pop) on every arm

    rdma        seed per-component one-sided engine
    rdma_fused  planned + fused-descriptor one-sided engine
    am          aggregated active messages (vmapped handler dispatch)
    cached      hot-bucket cache attached (CR find only, DESIGN.md §8):
                host lookup + one jitted miss-subset find step

under two scalings:

  * **weak**: n ops per rank held constant — total work grows with P.
    Per-op time should stay flat for a scalable engine; growth isolates
    the per-rank occupancy-exchange and reply fan-out costs that the
    cost model's `exch_per_rank` / `fanout_per_rank` terms price
    (costmodel._p_scaled).
  * **strong**: TOTAL ops held constant — n = total / P shrinks per rank.
    Smaller per-rank batches amortize the fixed exchange overheads worse,
    the classic strong-scaling wall.

The measured weak-scaling growth of the one-sided and AM find arms is
least-squares-fitted back into the two cost-model slopes and emitted as
`fitted_params` — the per-P recalibration that keeps `predict_arm`
ordering arms correctly at P=64/256 (pinned by tests/
test_costmodel_ordering.py).

  python -m benchmarks.scaling_bench             # full run -> JSON artifact
  python -m benchmarks.scaling_bench --smoke     # reduced config

Env overrides: REPRO_SCALE_N (weak n/rank), REPRO_SCALE_TOTAL (strong
total ops), REPRO_SCALE_ITERS, REPRO_SCALE_PS (comma-separated).
Artifact: artifacts/bench/BENCH_scaling.json (folded into
BENCH_trajectory.json's "scaling" section by benchmarks/trajectory.py).
"""
from __future__ import annotations

import json
import os
import pathlib
import sys
import time
from typing import Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import am as am_mod
from repro.core import cache as cache_mod
from repro.core import hashtable as ht_mod
from repro.core import queue as q_mod
from repro.core import window

from .common import Csv, gen_batch_keys, stamp_label

PS = (8, 64, 256)
NSLOTS = 4096          # per rank — weak scaling of table memory
VAL_WORDS = 1
MAX_PROBES = 8
HT_ARMS = ("rdma", "rdma_fused", "am", "cached")
Q_ARMS = ("rdma", "rdma_fused", "am")


def _cfg(smoke: bool):
    n_weak = int(os.environ.get("REPRO_SCALE_N", 16 if smoke else 32))
    total = int(os.environ.get("REPRO_SCALE_TOTAL", 1024 if smoke else 2048))
    iters = int(os.environ.get("REPRO_SCALE_ITERS", 2 if smoke else 3))
    ps = tuple(int(x) for x in os.environ.get(
        "REPRO_SCALE_PS", ",".join(map(str, PS))).split(","))
    return n_weak, total, iters, ps


def _median(xs: List[float]) -> float:
    return float(np.median(xs))


def _timed_us_per_op(fn, outputs_of, ops: int, iters: int) -> float:
    """Median wall µs/op of `fn()` over `iters` reps (first rep warms the
    jit cache and is discarded)."""
    jax.block_until_ready(outputs_of(fn()))
    reps = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(outputs_of(fn()))
        reps.append((time.perf_counter() - t0) * 1e6 / ops)
    return _median(reps)


# ---------------------------------------------------------------------------
# hash table
# ---------------------------------------------------------------------------
def _ht_executors(P: int, eng: am_mod.AMEngine):
    def _wrap(data):
        return ht_mod.DHashTable(win=window.Window(data=data),
                                 nslots=NSLOTS, val_words=VAL_WORDS)

    def mk_insert(fused):
        @jax.jit
        def f(data, keys, vals):
            t, ok, _ = ht_mod.insert_rdma(_wrap(data), keys, vals,
                                          max_probes=MAX_PROBES, fused=fused)
            return t.win.data, ok
        return f

    def mk_find(fused):
        @jax.jit
        def f(data, keys):
            _, found, _ = ht_mod.find_rdma(_wrap(data), keys,
                                           max_probes=MAX_PROBES,
                                           fused=fused)
            return found
        return f

    @jax.jit
    def am_insert(data, keys, vals):
        t, ok, _ = ht_mod.insert_rpc(_wrap(data), eng, keys, vals)
        return t.win.data, ok

    @jax.jit
    def am_find(data, keys):
        found, _ = ht_mod.find_rpc(_wrap(data), eng, keys)
        return found

    @jax.jit
    def miss_find(data, keys, miss):
        _, found, vals, slot = ht_mod.find_rdma(_wrap(data), keys,
                                                valid=miss, fused=True,
                                                max_probes=MAX_PROBES,
                                                return_slot=True)
        return found, vals, slot

    return {
        "insert": {"rdma": mk_insert(False), "rdma_fused": mk_insert(True),
                   "am": am_insert},
        "find": {"rdma": mk_find(False), "rdma_fused": mk_find(True),
                 "am": am_find},
        "miss_find": miss_find,
        "wrap": _wrap,
    }


def bench_ht(P: int, n: int, iters: int, seed: int) -> Dict[str, Dict]:
    """{op: {arm: us_per_op}} for one (P, n) hash-table config."""
    rng = np.random.default_rng(seed)
    keys = jnp.asarray(gen_batch_keys(P, n, "uniform", rng))
    vals = jnp.asarray(
        rng.integers(1, 1 << 20, (P, n, VAL_WORDS)).astype(np.int32))
    ht0 = ht_mod.make_hashtable(P, NSLOTS, VAL_WORDS)
    eng = am_mod.AMEngine(P)
    ht_mod.build_am_handlers(ht0, eng, max_probes=MAX_PROBES)
    ex = _ht_executors(P, eng)
    data0 = ht0.win.data
    ops = P * n
    out: Dict[str, Dict] = {"insert": {}, "find": {}}

    filled = {}
    for arm in ("rdma", "rdma_fused", "am"):
        ins = ex["insert"][arm]
        out["insert"][arm] = _timed_us_per_op(
            lambda ins=ins: ins(data0, keys, vals), lambda r: r[1],
            ops, iters)
        filled[arm] = ins(data0, keys, vals)[0]
        fnd = ex["find"][arm]
        d1 = filled[arm]
        out["find"][arm] = _timed_us_per_op(
            lambda fnd=fnd, d1=d1: fnd(d1, keys), lambda r: r, ops, iters)

    # cached arm: warm the hot-bucket cache with the find keys, then
    # measure the §8 steady state — host lookup + one jitted miss-subset
    # step (all-hit: the step's probe loop exits immediately, the cost is
    # the lookup itself, which scales with P on the host)
    cache = cache_mod.BucketCache(P, NSLOTS, VAL_WORDS, capacity=4096,
                                  max_probes=MAX_PROBES)
    ht1 = ex["wrap"](filled["rdma_fused"])
    _, f_w, _ = ht_mod.find_rdma(ht1, keys, fused=True,
                                 max_probes=MAX_PROBES, cache=cache)
    jax.block_until_ready(f_w)
    cache.drain_fills(force=True)
    keys_np = np.asarray(keys)
    miss_step = ex["miss_find"]
    d1 = filled["rdma_fused"]

    def cached_find():
        look = cache.lookup(keys_np)
        miss = jnp.asarray(look.miss)
        return miss_step(d1, keys, miss)

    out["find"]["cached"] = _timed_us_per_op(
        cached_find, lambda r: r, ops, iters)
    return out


# ---------------------------------------------------------------------------
# queue
# ---------------------------------------------------------------------------
def bench_q(P: int, n: int, iters: int, seed: int) -> Dict[str, Dict]:
    """{op: {arm: us_per_op}} for one (P, n) hosted-queue config."""
    rng = np.random.default_rng(seed)
    vals = jnp.asarray(
        rng.integers(1, 1 << 20, (P, n, VAL_WORDS)).astype(np.int32))
    cap = max(1024, 2 * P * n)
    q0 = q_mod.make_queue(P, 0, cap, VAL_WORDS)
    eng = am_mod.AMEngine(P)
    q_mod.build_am_handlers(q0, eng)
    ops = P * n

    def mk_push(planned):
        @jax.jit
        def f(data, vals):
            q2, ok = q_mod.push_rdma(_wrapq(data), vals, planned=planned)
            return q2.win.data, ok
        return f

    def mk_pop(planned):
        @jax.jit
        def f(data):
            q2, got, v = q_mod.pop_rdma(_wrapq(data), n, planned=planned)
            return q2.win.data, got, v
        return f

    def _wrapq(data):
        return q_mod.DQueue(win=window.Window(data=data), host=q0.host,
                            capacity=q0.capacity, val_words=q0.val_words,
                            checksum=q0.checksum)

    @jax.jit
    def am_push(data, vals):
        q2, ok = q_mod.push_rpc(_wrapq(data), eng, vals)
        return q2.win.data, ok

    @jax.jit
    def am_pop(data):
        q2, got, v = q_mod.pop_rpc(_wrapq(data), eng, n)
        return q2.win.data, got, v

    pushes = {"rdma": mk_push(False), "rdma_fused": mk_push(True),
              "am": am_push}
    pops = {"rdma": mk_pop(False), "rdma_fused": mk_pop(True),
            "am": am_pop}
    data0 = q0.win.data
    out: Dict[str, Dict] = {"push": {}, "pop": {}}
    for arm in Q_ARMS:
        push, pop = pushes[arm], pops[arm]
        out["push"][arm] = _timed_us_per_op(
            lambda push=push: push(data0, vals), lambda r: r[1], ops, iters)
        d1 = push(data0, vals)[0]
        out["pop"][arm] = _timed_us_per_op(
            lambda pop=pop, d1=d1: pop(d1), lambda r: r[1:], ops, iters)
    return out


# ---------------------------------------------------------------------------
# slope fitting (cost-model P-dependence recalibration)
# ---------------------------------------------------------------------------
def _fit_slope(per_p: Dict[int, float], base_p: int) -> Optional[float]:
    """Least-squares slope s of t(P)/t(P0) = (1 + s(P-1)) / (1 + s(P0-1))
    over the measured per-P medians — closed form per point, averaged.
    None when the base is missing; clamped at 0 (a measured SPEEDUP at
    higher P is noise, not negative wire cost)."""
    t0 = per_p.get(base_p)
    if not t0:
        return None
    ss = []
    for p, t in per_p.items():
        if p == base_p or not t:
            continue
        r = t / t0
        denom = (p - 1) - r * (base_p - 1)
        if denom > 0:
            ss.append(max(0.0, (r - 1.0) / denom))
    return float(np.mean(ss)) if ss else None


def fit_params(weak: Dict[str, Dict]) -> Dict[str, Optional[float]]:
    """Fit the two _p_scaled slopes from the weak-scaling find medians:
    the one-sided fused find is a pure wire-term op (R per probe) ->
    exch_per_rank; the AM find's growth is reply fan-out -> fanout_per_rank.
    """
    rdma_pp = {int(p): d["ht"]["find"].get("rdma_fused")
               for p, d in weak.items()}
    am_pp = {int(p): d["ht"]["find"].get("am") for p, d in weak.items()}
    base = min(rdma_pp)
    return {"exch_per_rank": _fit_slope(rdma_pp, base),
            "fanout_per_rank": _fit_slope(am_pp, base),
            "base_p": base}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------
def run(smoke: bool) -> Dict:
    n_weak, total, iters, ps = _cfg(smoke)
    weak: Dict[str, Dict] = {}
    strong: Dict[str, Dict] = {}
    for P in ps:
        n_strong = max(1, total // P)
        weak[str(P)] = {
            "n_per_rank": n_weak,
            "ht": bench_ht(P, n_weak, iters, seed=P),
            "q": bench_q(P, n_weak, iters, seed=P + 1),
        }
        strong[str(P)] = {
            "n_per_rank": n_strong,
            "ht": bench_ht(P, n_strong, iters, seed=P + 2),
            "q": bench_q(P, n_strong, iters, seed=P + 3),
        }
        print(f"# P={P}: weak n/rank={n_weak}, strong n/rank={n_strong}")
    fitted = fit_params(weak)
    result = {
        "schema": "bench-scaling-v1",
        "ps": list(ps), "nslots_per_rank": NSLOTS,
        "weak_n_per_rank": n_weak, "strong_total_ops": total,
        "iters": iters,
        "weak": weak, "strong": strong,
        "fitted_params": fitted,
    }
    csv = Csv(["scaling", "P", "struct", "op", "arm", "us_per_op"])
    for label, section in (("weak", weak), ("strong", strong)):
        for p, d in section.items():
            for struct in ("ht", "q"):
                for op, arms in d[struct].items():
                    for arm, us in arms.items():
                        if us is not None:
                            csv.add(label, p, struct, op, arm,
                                    round(us, 4))
    print(f"# fitted exch_per_rank={fitted['exch_per_rank']} "
          f"fanout_per_rank={fitted['fanout_per_rank']}")
    emit_json(result)
    return result


def emit_json(result: Dict, out_dir: str = "artifacts/bench") -> str:
    p = pathlib.Path(out_dir) / "BENCH_scaling.json"
    p.parent.mkdir(parents=True, exist_ok=True)
    with open(p, "w") as f:
        json.dump(stamp_label(result), f, indent=2)
    print(f"# wrote {p}")
    return str(p)


def main():
    run(smoke="--smoke" in sys.argv)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
