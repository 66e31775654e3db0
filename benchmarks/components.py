"""Paper Fig. 3 / Table I: component-operation latencies.

Measures put, get, FAD (random addresses), FAD-single-variable,
single CAS, persistent CAS, and the AM round trip on the batched phase
engine, at several virtual-rank counts. Emits per-op µs and the
calibrated ComponentCosts used by the queue/hash-table benchmark
predictions (Figs. 4–5 methodology).

Reproduces the paper's two qualitative findings structurally:
  * persistent CAS >> single CAS (multiple rounds under contention);
  * FAD-single-variable > FAD-random: all AMOs funnel into one owner's
    serialized lane (on Aries the cause was NIC-side; here it is the
    owner-lane serialization — same shape, different microarchitecture,
    see DESIGN.md §2).
"""
from __future__ import annotations

import json
import pathlib
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import am as am_mod
from repro.core import costmodel as cm
from repro.core import hashtable as ht_mod
from repro.core import routing
from repro.core import window
from repro.core.types import AmoKind

from .common import (Csv, gen_batch_keys, gen_zipf_dup_keys, stamp_label,
                     time_op)

LOCAL = 4096


def _mk(P, n, seed=0):
    rng = np.random.default_rng(seed)
    dst = jnp.asarray(rng.integers(0, P, (P, n)), jnp.int32)
    off = jnp.asarray(rng.integers(0, LOCAL, (P, n)), jnp.int32)
    return dst, off


def bench_components(P: int = 8, n: int = 64, iters: int = 15):
    win = window.make_window(P, LOCAL)
    dst, off = _mk(P, n)
    ops = P * n

    def put(w):
        return window.rdma_put(w, dst, off, jnp.ones((P, n, 1), jnp.int32))

    def get(w):
        return window.rdma_get(w, dst, off, width=1)

    def fad(w):
        return window.rdma_fao(w, dst, off, 1, AmoKind.FAA)

    zero_off = jnp.zeros_like(off)

    def fad_single(w):
        return window.rdma_fao(w, dst, zero_off, 1, AmoKind.FAA)

    def cas(w):
        return window.rdma_cas(w, dst, off, 0, 1)

    def cas_persistent(w):
        # poll until success: swap cur -> cur+1, retry on conflict
        def round_(i, carry):
            w, pending, cur = carry
            old, w = window.rdma_cas(w, dst, zero_off, cur, cur + 1,
                                     valid=pending)
            done = pending & (old == cur)
            return w, pending & ~done, old
        cur = window.rdma_get(w, dst, zero_off, width=1)[..., 0]
        w, pending, _ = jax.lax.fori_loop(
            0, 8, round_, (w, jnp.ones((P, n), bool), cur))
        return w

    # Fused component descriptors (DESIGN.md §2) — the claim+write,
    # claim+write+publish, and lock+gather compound phases the hash table's
    # fused hot path is built from, plus a planned persistent CAS showing
    # route-plan reuse across rounds.
    vals2 = jnp.ones((P, n, 2), jnp.int32)

    def cas_put(w):
        _, w = window.rdma_cas_put(w, dst, off, 0, 1, off + 1, vals2)
        return w

    def cas_put_pub(w):
        _, w = window.rdma_cas_put_publish(w, dst, off, 0, 1, off + 1,
                                           vals2, 3)
        return w

    def fao_get(w):
        _, rec, w = window.rdma_fao_get(w, dst, off, 1, AmoKind.FAA, off, 3)
        return w, rec

    def cas_persistent_planned(w):
        plan = routing.make_plan(dst, cap=n)

        def round_(i, carry):
            w, pending, cur = carry
            old, w = window.rdma_cas(w, dst, zero_off, cur, cur + 1,
                                     valid=pending, plan=plan)
            done = pending & (old == cur)
            return w, pending & ~done, old
        cur = window.rdma_get(w, dst, zero_off, width=1, plan=plan)[..., 0]
        w, pending, _ = jax.lax.fori_loop(
            0, 8, round_, (w, jnp.ones((P, n), bool), cur))
        return w

    # AM round trip: the inner operation is a remote hash-table insert
    # (matches the paper's AM benchmark).
    ht = ht_mod.make_hashtable(P, LOCAL, 1)
    eng = am_mod.AMEngine(P)
    ht_mod.build_am_handlers(ht, eng)
    rng = np.random.default_rng(1)
    keys = jnp.asarray(rng.integers(1, 1 << 20, (P, n)), jnp.int32)

    def am_rt(table):
        ht2 = ht_mod.DHashTable(win=window.Window(data=table),
                                nslots=LOCAL, val_words=1)
        ht3, ok, probes = ht_mod.insert_rpc(ht2, eng, keys, keys[..., None])
        return ht3.win.data

    rows = {}
    rows["put"] = time_op(put, win, iters=iters, ops_per_call=ops)
    rows["get"] = time_op(get, win, iters=iters, ops_per_call=ops)
    rows["fad"] = time_op(fad, win, iters=iters, ops_per_call=ops)
    rows["fad_single"] = time_op(fad_single, win, iters=iters,
                                 ops_per_call=ops)
    rows["cas_single"] = time_op(cas, win, iters=iters, ops_per_call=ops)
    rows["cas_persistent"] = time_op(cas_persistent, win, iters=iters,
                                     ops_per_call=ops)
    rows["cas_persistent_planned"] = time_op(cas_persistent_planned, win,
                                             iters=iters, ops_per_call=ops)
    rows["cas_put"] = time_op(cas_put, win, iters=iters, ops_per_call=ops)
    rows["cas_put_pub"] = time_op(cas_put_pub, win, iters=iters,
                                  ops_per_call=ops)
    rows["fao_get"] = time_op(fao_get, win, iters=iters, ops_per_call=ops)
    rows["am_rt"] = time_op(am_rt, ht.win.data, iters=iters,
                            ops_per_call=ops)
    return rows


def calibrated_costs(rows) -> cm.ComponentCosts:
    return cm.calibrate({
        "W": rows["put"], "R": rows["get"], "A_cas": rows["cas_single"],
        "A_fao": rows["fad"], "am_rt": rows["am_rt"],
        "A_cas_put": rows.get("cas_put"),
        "A_cas_put_pub": rows.get("cas_put_pub"),
        "A_fao_get": rows.get("fao_get"),
        "handler": 0.0,
    })


# ---------------------------------------------------------------------------
# Coalescing acceptance workload (DESIGN.md §6): hot-owner, zipfian
# duplicate-heavy hash-table insert+find — sender-side combining vs the
# PR 3 planned/fused path on the SAME batch.
# ---------------------------------------------------------------------------
def bench_coalescing(P: int = 8, n: int = 64, alpha: float = 1.1,
                     nkeys: int = 48, iters: int = 9,
                     max_probes: int = 48, nslots: int = 4096):
    """Returns a row dict: µs/op for the fused and fused+coalesced engines
    on a hot-owner zipfian insert+find workload, plus the measured wire
    statistics (dedup ratio, request payload rows per probe phase) —
    `payload_rows_*` from the coalescing structure, `engine_rows_coalesced`
    independently from the engine's own phase log, so the smoke gate can
    cross-check that the wire actually shrank."""
    from repro.core import window as win_mod
    from repro.core.types import Promise

    rng = np.random.default_rng(7)
    keys = jnp.asarray(gen_zipf_dup_keys(P, n, rng, alpha=alpha,
                                         nkeys=nkeys, hot_owner=0),
                       jnp.int32)
    vals = ((keys * 31 + 7) & 0x7FFFFF)[..., None]
    base = ht_mod.make_hashtable(P, nslots, 1)
    ops = P * n

    def wrap(data):
        return ht_mod.DHashTable(win=window.Window(data=data), nslots=nslots,
                                 val_words=1)

    def insert_find(coalesce):
        def fn(data):
            ht, ok, _ = ht_mod.insert_rdma(
                wrap(data), keys, vals, promise=Promise.CRW,
                max_probes=max_probes, fused=True, coalesce=coalesce)
            ht, f, v = ht_mod.find_rdma(
                ht, keys, promise=Promise.CR, max_probes=max_probes,
                fused=True, coalesce=coalesce)
            return ht.win.data, f, v
        return fn

    us_fused = time_op(insert_find(False), base.win.data, iters=iters,
                       ops_per_call=ops)
    us_coalesced = time_op(insert_find(True), base.win.data, iters=iters,
                           ops_per_call=ops)

    # Wire statistics. payload_rows_* come from the coalescing structure
    # the insert's CoalescedPlan uses; engine_rows_coalesced is measured
    # INDEPENDENTLY, from the rows-out stats the engine records into its
    # phase log while actually executing the coalesced workload — the two
    # must agree or the engine is not shipping what the structure claims.
    dst, start = ht_mod._place(base, keys)
    payload = jnp.concatenate([keys[..., None], vals], axis=-1)
    co = routing.coalesce(dst, start, match=payload)
    rows_in = int(np.asarray(co.rows_in).sum())
    rows_out = int(np.asarray(co.rows_out).sum())
    win_mod.drain_phase_log()
    with win_mod.decision_scope("bench_coalescing"):
        insert_find(True)(base.win.data)
    infos = [info for _, _, info in win_mod.drain_phase_log() if info]
    engine_rows = infos[0]["rows_out"] if infos else None
    return {
        "ht_hot_insert_find_fused": us_fused,
        "ht_hot_insert_find_coalesced": us_coalesced,
        "coalesce_speedup": us_fused / us_coalesced if us_coalesced else None,
        "dedup_ratio": rows_out / max(rows_in, 1),
        "payload_rows_uncoalesced": rows_in,
        "payload_rows_coalesced": rows_out,
        "engine_rows_coalesced": engine_rows,
        "alpha": alpha, "nkeys": nkeys, "n": n, "P": P,
    }


# ---------------------------------------------------------------------------
# Cache-tier acceptance workload (DESIGN.md §8): read-heavy zipfian
# duplicate-heavy hash-table finds — hot-bucket cache vs the PR 4
# fused+coalesced path on the SAME stream. Both arms run EAGERLY: the
# cache's lookup/fill book-keeping is host-side by design (it no-ops
# under tracing), so a jitted timing loop would silently bench the
# uncached path twice.
# ---------------------------------------------------------------------------
def bench_cache(P: int = 8, n: int = 64, batches: int = 8,
                alpha: float = 1.1, nkeys: int = 48, iters: int = 7,
                max_probes: int = 8, nslots: int = 4096,
                n_mix: int = 160, read_frac: float = 0.9,
                capacity: int = 4096, seed: int = 11):
    """Returns a row dict: MEDIAN per-find-batch µs/op for the
    fused+coalesced find (no cache) and the cached engine on a read-heavy
    zipfian stream (`batches` find batches + one fresh-key insert batch
    per rep, ~{batches}:1 read:write), plus the measured hit rate and the
    exchange counts both arms issue on a steady-state all-hit batch.

    The median-of-batches statistic is the honest one for a cache tier:
    the batch right after an insert refills its invalidated entries at
    miss cost (and a single miss row pays the FULL probe-phase loop —
    exchanges are per phase, not per row), while every steady batch
    short-circuits to zero exchanges. The median prices the steady state;
    the refill spikes stay in the stream and in the hit-rate figure."""
    from repro.core import adaptive as ad_mod
    from repro.core import cache as cache_mod
    from repro.core import routing as rt_mod
    from repro.core.types import Promise

    rng = np.random.default_rng(seed)
    used: set = set()
    # One zipf draw over ONE shared key universe, sliced into the stream's
    # find batches (per-call universes would never re-hit the cache).
    big = gen_zipf_dup_keys(P, n * batches, rng, alpha=alpha, nkeys=nkeys)
    finds = [jnp.asarray(big[:, i * n:(i + 1) * n], jnp.int32)
             for i in range(batches)]
    used.update(int(k) for k in np.unique(big))

    def val_of(keys):
        return ((keys * 31 + 7) & 0x7FFFFF)[..., None]

    def seed_table():
        ht = ht_mod.make_hashtable(P, nslots, 1)
        ht, ok, _ = ht_mod.insert_rdma(
            ht, jnp.asarray(big, jnp.int32), val_of(jnp.asarray(big)),
            promise=Promise.CRW, max_probes=max_probes, fused=True,
            coalesce=True)
        jax.block_until_ready(ht.win.data)
        return ht

    # Fresh-key insert batches: the WRITE fraction of a
    # gen_batch_keys(read_frac=...) mixed batch (insert with valid=~reads
    # — exercising valid-masked invalidation), pre-generated so both arms
    # replay the IDENTICAL stream (cache invalidation included).
    writes = []
    for _ in range(iters + 2):
        wk, reads = gen_batch_keys(P, n_mix, "uniform", rng, used,
                                   read_frac=read_frac)
        writes.append((jnp.asarray(wk, jnp.int32),
                       jnp.asarray(~reads)))

    def run_stream(state, find_fn, insert_fn, reps):
        """Replay the read-heavy stream; returns per-find-batch seconds."""
        per_batch = []
        for r in range(reps):
            for keys in finds:
                t0 = time.perf_counter()
                state["ht"], f, v = find_fn(state["ht"], keys)
                jax.block_until_ready(v)
                per_batch.append(time.perf_counter() - t0)
            wkeys, wmask = writes[r % len(writes)]
            state["ht"], ok, _ = insert_fn(state["ht"], wkeys, wmask)
            jax.block_until_ready(state["ht"].win.data)
        return per_batch

    def median_us(per_batch):
        per_batch = sorted(per_batch)
        return per_batch[len(per_batch) // 2] / (P * n) * 1e6

    # Baseline arm: PR 4 fused+coalesced, eager, no cache.
    def find_base(ht, keys):
        return ht_mod.find_rdma(ht, keys, promise=Promise.CR,
                                max_probes=max_probes, fused=True,
                                coalesce=True)

    def insert_base(ht, wkeys, wmask):
        return ht_mod.insert_rdma(ht, wkeys, val_of(wkeys),
                                  promise=Promise.CRW, valid=wmask,
                                  max_probes=max_probes, fused=True,
                                  coalesce=True)

    state_b = {"ht": seed_table()}
    run_stream(state_b, find_base, insert_base, 1)  # warmup
    us_base = median_us(run_stream(state_b, find_base, insert_base, iters))

    # Cached arm: same stream through the adaptive engine with a
    # hot-bucket cache attached; one warm rep fills the cache.
    eng = ad_mod.AdaptiveEngine(P, arms=("rdma_fused",))
    eng.attach_cache(cache_mod.BucketCache(P, nslots, 1, capacity=capacity,
                                           max_probes=max_probes))

    def find_cached(ht, keys):
        return eng.ht_find(ht, keys, promise=Promise.CR,
                           max_probes=max_probes)

    def insert_cached(ht, wkeys, wmask):
        return eng.ht_insert(ht, wkeys, val_of(wkeys), promise=Promise.CRW,
                             valid=wmask, max_probes=max_probes)

    state_c = {"ht": seed_table()}
    run_stream(state_c, find_cached, insert_cached, 1)  # warm: fill cache
    us_cached = median_us(
        run_stream(state_c, find_cached, insert_cached, iters))
    c = eng.cache.counters
    looked = (c["hits"] + c["misses"]) or 1
    hit_rate = c["hits"] / looked

    # Wire cross-check: exchanges a steady-state find batch issues per
    # arm. The same batch runs twice and the SECOND run is counted, so
    # the cached arm has refilled anything the stream's last insert
    # invalidated — steady state is all-hit and must issue ZERO
    # exchanges, while the baseline pays its full probe loop every time.
    def count_exchanges(find_fn, state):
        roles = []

        def hook(x, role):
            if role.endswith("_pre"):
                roles.append(role[:-4])
            return x
        state["ht"], _, _ = find_fn(state["ht"], finds[0])  # refill pass
        with rt_mod.sharding_hook(hook):
            state["ht"], _, v = find_fn(state["ht"], finds[0])
            jax.block_until_ready(v)
        return len(roles)

    exch_base = count_exchanges(find_base, state_b)
    exch_cached = count_exchanges(find_cached, state_c)

    # Bit-exactness on a final all-universe find.
    probe = jnp.asarray(big[:, :n], jnp.int32)
    _, f_b, v_b = ht_mod.find_rdma(state_b["ht"], probe, promise=Promise.CR,
                                   max_probes=max_probes, fused=True)
    _, f_c, v_c = eng.ht_find(state_c["ht"], probe, promise=Promise.CR,
                              max_probes=max_probes)
    exact = (bool(np.array_equal(np.asarray(f_b), np.asarray(f_c)))
             and bool(np.array_equal(np.asarray(v_b), np.asarray(v_c))))
    return {
        "ht_read_heavy_find_coalesced": us_base,
        "ht_read_heavy_find_cached": us_cached,
        "cache_speedup": us_base / us_cached if us_cached else None,
        "hit_rate": hit_rate,
        "exchanges_coalesced": exch_base,
        "exchanges_cached": exch_cached,
        "bit_exact": exact,
        "alpha": alpha, "nkeys": nkeys, "n": n, "batches": batches,
        "n_mix": n_mix, "read_frac": read_frac, "P": P,
    }


# ---------------------------------------------------------------------------
# Transaction acceptance workload (DESIGN.md §11): zipfian hot-key
# multi-op txn mix — contention priced as the measured abort rate, both
# one-sided and AM commit arms on the IDENTICAL stream.
# ---------------------------------------------------------------------------
def hot_key_txn_mix(P: int = 8, nops: int = 4, batches: int = 6,
                    alpha: float = 1.1, nkeys: int = 24, L: int = 256,
                    seed: int = 23):
    """The hot-key transaction mix: (initial (P, L) window, one Txn per
    batch). Every rank runs one `nops`-op txn per batch; keys are drawn
    zipf(alpha) from `nkeys` hot words owned by rank 0's key set, so
    write sets collide and the OCC lock phase aborts and retries."""
    from repro.core import txn as txn_mod

    rng = np.random.default_rng(seed)
    hot = gen_zipf_dup_keys(P, nops * batches, rng, alpha=alpha,
                            nkeys=nkeys, hot_owner=0)
    init = rng.integers(-50, 50, size=(P, L)).astype(np.int32)

    def stage(b):
        """Batch b's txn: same zipfian (dst, off) stream for every arm."""
        t = txn_mod.Txn(P)
        brng = np.random.default_rng(seed * 1000 + b)
        for j in range(nops):
            k = np.asarray(hot[:, b * nops + j])
            dst, off = k % P, k % 64           # hot keys -> hot words
            kind = int(brng.integers(0, 4))
            if kind == 0:
                t.put(dst, off, brng.integers(-9, 9, P))
            elif kind == 1:
                t.get(dst, off)
            elif kind == 2:
                t.cas(dst, off, brng.integers(-50, 50, P),
                      brng.integers(-9, 9, P))
            else:
                t.fao(dst, off, brng.integers(-3, 4, P))
        return t

    return init, [stage(b) for b in range(batches)]


def bench_txn(P: int = 8, nops: int = 4, batches: int = 6,
              alpha: float = 1.1, nkeys: int = 24, L: int = 256,
              seed: int = 23):
    """Returns a row dict: µs per transaction for the rdma_fused and am
    commit arms on the hot-key txn mix (`hot_key_txn_mix`), plus the
    measured abort rate, median round count, stamp-served read count,
    and a per-batch serial-oracle conformance verdict."""
    from repro.core import txn as txn_mod

    init, txns = hot_key_txn_mix(P, nops, batches, alpha, nkeys, L, seed)

    row = {"P": P, "nops": nops, "batches": batches, "alpha": alpha,
           "nkeys": nkeys, "conformant": True}
    for arm in ("rdma_fused", "am"):
        eng = txn_mod.TxnEngine(P, am_engine=am_mod.AMEngine(P))
        win = window.Window(data=jnp.asarray(init))
        aborts = attempts = rounds_sum = saved = 0
        rounds_all = []
        t0 = time.perf_counter()
        for t in txns:
            st0 = {"ht": np.asarray(win.data).copy()}
            res = eng.run(win, t, arm=arm)
            win = res.wins["ht"]
            aborts += res.aborts
            attempts += int(res.attempts.sum())
            rounds_sum += res.rounds
            saved += res.saved_reads
            rounds_all.append(res.rounds)
            rep, st = txn_mod.serial_apply(st0, t,
                                           [p for _, p in res.order])
            if (not np.array_equal(st["ht"], np.asarray(win.data))
                    or not np.array_equal(rep, res.replies)):
                row["conformant"] = False
        wall = time.perf_counter() - t0
        rounds_all.sort()
        row[f"us_per_txn_{arm}"] = wall / (batches * P) * 1e6
        row[f"abort_rate_{arm}"] = aborts / max(1, attempts)
        row[f"rounds_median_{arm}"] = rounds_all[len(rounds_all) // 2]
        row[f"saved_reads_{arm}"] = saved
    return row


# Fused-vs-unfused pairing: fused op -> (unfused component sequence) for the
# machine-readable artifact.
FUSED_PAIRS = {
    "cas_put": ["cas_single", "put"],
    "cas_put_pub": ["cas_single", "put", "fad"],
    "fao_get": ["fad", "get"],
    "cas_persistent_planned": ["cas_persistent"],
}


def emit_json(all_rows, out="artifacts/bench",
              fname="BENCH_components.json", coalescing=None, cache=None,
              txn=None):
    """Machine-readable per-op µs + exchange counts + fused-vs-unfused
    ratios (+ the coalescing / cache acceptance rows when measured), for
    cross-PR perf trajectories (consumed by benchmarks/trajectory.py and
    CI)."""
    from repro.core.types import Backend, Promise
    report = {"benchmark": "components", "unit": "us_per_op",
              "rows": {str(P): rows for P, rows in all_rows.items()},
              "fused_vs_unfused": {}, "exchange_counts": {}}
    if coalescing is not None:
        report["coalescing"] = {str(r["P"]): r for r in coalescing}
    if cache is not None:
        report["cache"] = {str(r["P"]): r for r in cache}
    if txn is not None:
        report["txn"] = {str(r["P"]): r for r in txn}
    for P, rows in all_rows.items():
        pairs = {}
        for fused_op, seq in FUSED_PAIRS.items():
            if fused_op not in rows:
                continue
            unfused_us = sum(rows[c] for c in seq)
            pairs[fused_op] = {
                "fused_us": rows[fused_op],
                "unfused_us": unfused_us,
                "unfused_sequence": seq,
                "speedup": unfused_us / rows[fused_op]
                if rows[fused_op] else None,
            }
        report["fused_vs_unfused"][str(P)] = pairs
    for fused in (False, True):
        key = "fused" if fused else "unfused"
        report["exchange_counts"][key] = {
            "ht_find_crw_per_probe": cm.exchange_count(
                cm.DSOp.HT_FIND, Promise.CRW, Backend.RDMA, fused=fused),
            "ht_insert_crw_per_probe": cm.exchange_count(
                cm.DSOp.HT_INSERT, Promise.CRW, Backend.RDMA, fused=fused,
                probes=1),
            "network_phases_ht_insert_crw": cm.network_phases(
                cm.DSOp.HT_INSERT, Promise.CRW, Backend.RDMA, fused=fused),
            "network_phases_ht_find_crw": cm.network_phases(
                cm.DSOp.HT_FIND, Promise.CRW, Backend.RDMA, fused=fused),
        }
    stamp_label(report)
    p = pathlib.Path(out) / fname
    p.parent.mkdir(parents=True, exist_ok=True)
    with open(p, "w") as f:
        json.dump(report, f, indent=2)
    print(f"# wrote {p}")
    return str(p)


def main(out="artifacts/bench", ranks=(2, 4, 8, 16)):
    csv = Csv(["benchmark", "nranks", "op", "us_per_op"])
    all_rows = {}
    for P in ranks:
        rows = bench_components(P=P)
        all_rows[P] = rows
        for op, us in rows.items():
            csv.add("components(fig3)", P, op, f"{us:.3f}")
    csv.dump(f"{out}/components.csv")
    co_row = bench_coalescing(P=8)
    csv.add("coalescing", 8, "ht_hot_insert_find_fused",
            f"{co_row['ht_hot_insert_find_fused']:.3f}")
    csv.add("coalescing", 8, "ht_hot_insert_find_coalesced",
            f"{co_row['ht_hot_insert_find_coalesced']:.3f}")
    ca_row = bench_cache(P=8)
    csv.add("cache", 8, "ht_read_heavy_find_coalesced",
            f"{ca_row['ht_read_heavy_find_coalesced']:.3f}")
    csv.add("cache", 8, "ht_read_heavy_find_cached",
            f"{ca_row['ht_read_heavy_find_cached']:.3f}")
    tx_row = bench_txn(P=8)
    csv.add("txn", 8, "us_per_txn_rdma_fused",
            f"{tx_row['us_per_txn_rdma_fused']:.3f}")
    csv.add("txn", 8, "us_per_txn_am", f"{tx_row['us_per_txn_am']:.3f}")
    emit_json(all_rows, out=out, coalescing=[co_row], cache=[ca_row],
              txn=[tx_row])
    # structural findings (paper Fig. 3)
    r = all_rows[8] if 8 in all_rows else all_rows[max(all_rows)]
    print(f"# persistent_cas/single_cas = "
          f"{r['cas_persistent']/r['cas_single']:.2f} (expect > 1)")
    print(f"# fad_single/fad = {r['fad_single']/r['fad']:.2f} "
          f"(expect >= 1; Aries pathology analogue)")
    print(f"# fused cas_put vs cas+put: "
          f"{(r['cas_single']+r['put'])/r['cas_put']:.2f}x")
    print(f"# fused fao_get vs fad+get: "
          f"{(r['fad']+r['get'])/r['fao_get']:.2f}x")
    print(f"# coalescing hot-owner insert+find: "
          f"{co_row['coalesce_speedup']:.2f}x at dedup ratio "
          f"{co_row['dedup_ratio']:.2f}")
    print(f"# cache read-heavy zipfian find: "
          f"{ca_row['cache_speedup']:.2f}x at hit rate "
          f"{ca_row['hit_rate']:.3f}")
    print(f"# txn zipf hot-key mix: abort rate "
          f"{tx_row['abort_rate_rdma_fused']:.3f} (fused) / "
          f"{tx_row['abort_rate_am']:.3f} (am), conformant "
          f"{tx_row['conformant']}")
    return all_rows


def smoke_coalesce(P: int = 8, n: int = 64, iters: int = 9,
                   threshold: float = 1.3,
                   update_artifact: bool = True) -> bool:
    """Coalescing smoke gate (scripts/smoke.sh): hot-owner zipfian
    insert+find must speed up >= `threshold` over the PR 3 planned/fused
    path, the wire rows must actually shrink (dedup < 1), and the rows
    the ENGINE logged while executing must equal the rows the coalescing
    structure predicted. Folds its row into the existing
    BENCH_components.json (written by the earlier smoke step) so the
    workload runs once per smoke invocation."""
    row = bench_coalescing(P=P, n=n, iters=iters)
    print(f"fused      {row['ht_hot_insert_find_fused']:8.3f} us/op")
    print(f"coalesced  {row['ht_hot_insert_find_coalesced']:8.3f} us/op")
    print(f"speedup    {row['coalesce_speedup']:.2f}x "
          f"(target >= {threshold}x)")
    print(f"dedup ratio {row['dedup_ratio']:.3f}  payload rows "
          f"{row['payload_rows_uncoalesced']} -> "
          f"{row['payload_rows_coalesced']} "
          f"(engine logged {row['engine_rows_coalesced']})")
    rows_ok = (row["payload_rows_coalesced"]
               < row["payload_rows_uncoalesced"]
               and row["engine_rows_coalesced"]
               == row["payload_rows_coalesced"])
    if not rows_ok:
        print("FAIL: engine-logged wire rows do not shrink as the "
              "coalescing structure predicts")
    if update_artifact:
        p = pathlib.Path("artifacts/bench") / "BENCH_components.json"
        if p.exists():
            with open(p) as f:
                report = json.load(f)
            report.setdefault("coalescing", {})[str(P)] = row
            with open(p, "w") as f:
                json.dump(report, f, indent=2)
            print(f"# updated coalescing row in {p}")
    return bool(row["coalesce_speedup"] >= threshold) and rows_ok


def smoke_cache(P: int = 8, iters: int = 7, threshold: float = 5.0,
                update_artifact: bool = True) -> bool:
    """Cache-tier smoke gate (scripts/smoke.sh): the read-heavy zipfian
    find stream must speed up >= `threshold` over the PR 4
    fused+coalesced path, the observed hit rate must be high enough for
    the §8 discount to be the explanation (>= 0.9), the cached arm must
    issue strictly fewer exchanges (wire shrink, not wall-clock luck),
    and the two arms' final find results must be bit-identical. Folds its
    row into the existing BENCH_components.json (written by the earlier
    smoke step) so the workload runs once per smoke invocation."""
    row = bench_cache(P=P, iters=iters)
    print(f"coalesced  {row['ht_read_heavy_find_coalesced']:8.3f} us/op")
    print(f"cached     {row['ht_read_heavy_find_cached']:8.3f} us/op")
    print(f"speedup    {row['cache_speedup']:.2f}x "
          f"(target >= {threshold}x)")
    print(f"hit rate   {row['hit_rate']:.3f}  exchanges "
          f"{row['exchanges_coalesced']} -> {row['exchanges_cached']}  "
          f"bit_exact {row['bit_exact']}")
    wire_ok = row["exchanges_cached"] < row["exchanges_coalesced"]
    if not wire_ok:
        print("FAIL: cached arm did not issue fewer exchanges than the "
              "coalesced baseline")
    if not row["bit_exact"]:
        print("FAIL: cached and uncached finds disagree")
    if row["hit_rate"] < 0.9:
        print("FAIL: hit rate below 0.9 on the read-heavy stream")
    if update_artifact:
        p = pathlib.Path("artifacts/bench") / "BENCH_components.json"
        if p.exists():
            with open(p) as f:
                report = json.load(f)
            report.setdefault("cache", {})[str(P)] = row
            with open(p, "w") as f:
                json.dump(report, f, indent=2)
            print(f"# updated cache row in {p}")
    return (bool(row["cache_speedup"] >= threshold) and wire_ok
            and row["bit_exact"] and row["hit_rate"] >= 0.9)


def smoke_txn(P: int = 8, abort_ceiling: float = 0.9,
              update_artifact: bool = True) -> bool:
    """Transaction smoke gate (scripts/smoke.sh): the zipfian hot-key
    txn mix must replay bit-exactly through the serial oracle on BOTH
    commit arms (serializability), the contention must actually bite
    (abort rate > 0 — otherwise the workload stopped testing OCC), and
    retry must win through below `abort_ceiling` (livelock guard). Folds
    its row into the existing BENCH_components.json so the trajectory
    picks up the txn section."""
    row = bench_txn(P=P)
    for arm in ("rdma_fused", "am"):
        print(f"{arm:12s} {row[f'us_per_txn_{arm}']:8.1f} us/txn  "
              f"abort_rate {row[f'abort_rate_{arm}']:.3f}  "
              f"rounds(med) {row[f'rounds_median_{arm}']}  "
              f"saved_reads {row[f'saved_reads_{arm}']}")
    print(f"conformant  {row['conformant']}")
    ok = row["conformant"]
    if not ok:
        print("FAIL: txn stream diverged from the serial oracle")
    for arm in ("rdma_fused", "am"):
        ar = row[f"abort_rate_{arm}"]
        if ar <= 0.0:
            print(f"FAIL: {arm} abort rate is zero — the hot-key mix "
                  f"no longer exercises OCC contention")
            ok = False
        if ar > abort_ceiling:
            print(f"FAIL: {arm} abort rate {ar:.3f} above the "
                  f"{abort_ceiling} livelock ceiling")
            ok = False
    if update_artifact:
        p = pathlib.Path("artifacts/bench") / "BENCH_components.json"
        if p.exists():
            with open(p) as f:
                report = json.load(f)
            report.setdefault("txn", {})[str(P)] = row
            with open(p, "w") as f:
                json.dump(report, f, indent=2)
            print(f"# updated txn row in {p}")
    return bool(ok)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if "--smoke-coalesce" in sys.argv:
        sys.exit(0 if smoke_coalesce() else 1)
    if "--smoke-cache" in sys.argv:
        sys.exit(0 if smoke_cache() else 1)
    if "--smoke-txn" in sys.argv:
        sys.exit(0 if smoke_txn() else 1)
    main()
