"""Paper Fig. 5: hash-table operation latencies — RDMA find C_R / C_RW,
AM insert/find, RDMA insert C_RW / C_W — measured vs model prediction,
for BOTH engines: the seed per-component path (fused=False) and the
planned+fused path (fused=True, DESIGN.md §2). The `*_fused` columns
re-validate the model's ordering claim against the faster engine.

Run `python -m benchmarks.hashtable_bench --smoke` for the single-config
(P=8, n=64) fused-vs-seed speedup check used by scripts/smoke.sh.
"""
from __future__ import annotations

import sys

import numpy as np
import jax.numpy as jnp

from repro.core import am as am_mod
from repro.core import costmodel as cm
from repro.core import hashtable as ht_mod
from repro.core import window
from repro.core.types import Backend, Promise

from . import components
from .common import Csv, time_op

NSLOTS = 8192


def bench_hashtable(P: int = 8, n: int = 32, iters: int = 15):
    ops = P * n
    rng = np.random.default_rng(0)
    keys = jnp.asarray(
        rng.permutation(1 << 20)[:ops].reshape(P, n) + 1, jnp.int32)
    vals = jnp.stack([keys, keys], axis=-1)
    base = ht_mod.make_hashtable(P, NSLOTS, 2)
    eng = am_mod.AMEngine(P)
    ht_mod.build_am_handlers(base, eng)
    filled, ok, _ = ht_mod.insert_rdma(base, keys, vals, promise=Promise.CW)
    assert bool(ok.all())

    def wrap(data):
        return ht_mod.DHashTable(win=window.Window(data=data),
                                 nslots=NSLOTS, val_words=2)

    def insert(promise, fused):
        def fn(data):
            ht, _, _ = ht_mod.insert_rdma(wrap(data), keys, vals,
                                          promise=promise, max_probes=4,
                                          fused=fused)
            return ht.win.data
        return fn

    def insert_am(data):
        ht, _, _ = ht_mod.insert_rpc(wrap(data), eng, keys, vals)
        return ht.win.data

    def find(promise, fused):
        def fn(data):
            ht, f, v = ht_mod.find_rdma(wrap(data), keys, promise=promise,
                                        max_probes=4, fused=fused)
            return ht.win.data, f, v
        return fn

    def find_am(data):
        return ht_mod.find_rpc(wrap(data), eng, keys)

    empty = base.win.data
    full = filled.win.data
    return {
        "rdma_find_cr": time_op(find(Promise.CR, False), full, iters=iters,
                                ops_per_call=ops),
        "rdma_find_cr_fused": time_op(find(Promise.CR, True), full,
                                      iters=iters, ops_per_call=ops),
        "am_find_crw": time_op(find_am, full, iters=iters,
                               ops_per_call=ops),
        "am_insert_crw": time_op(insert_am, empty, iters=iters,
                                 ops_per_call=ops),
        "rdma_find_crw": time_op(find(Promise.CRW, False), full,
                                 iters=iters, ops_per_call=ops),
        "rdma_find_crw_fused": time_op(find(Promise.CRW, True), full,
                                       iters=iters, ops_per_call=ops),
        "rdma_insert_crw": time_op(insert(Promise.CRW, False), empty,
                                   iters=iters, ops_per_call=ops),
        "rdma_insert_crw_fused": time_op(insert(Promise.CRW, True), empty,
                                         iters=iters, ops_per_call=ops),
        "rdma_insert_cw": time_op(insert(Promise.CW, False), empty,
                                  iters=iters, ops_per_call=ops),
        "rdma_insert_cw_fused": time_op(insert(Promise.CW, True), empty,
                                        iters=iters, ops_per_call=ops),
    }


# impl -> (op, promise, backend, fused)
PRED = {
    "rdma_find_cr": (cm.DSOp.HT_FIND, Promise.CR, Backend.RDMA, False),
    "rdma_find_cr_fused": (cm.DSOp.HT_FIND, Promise.CR, Backend.RDMA, True),
    "rdma_find_crw": (cm.DSOp.HT_FIND, Promise.CRW, Backend.RDMA, False),
    "rdma_find_crw_fused": (cm.DSOp.HT_FIND, Promise.CRW, Backend.RDMA,
                            True),
    "am_find_crw": (cm.DSOp.HT_FIND, Promise.CRW, Backend.RPC, False),
    "am_insert_crw": (cm.DSOp.HT_INSERT, Promise.CRW, Backend.RPC, False),
    "rdma_insert_crw": (cm.DSOp.HT_INSERT, Promise.CRW, Backend.RDMA,
                        False),
    "rdma_insert_crw_fused": (cm.DSOp.HT_INSERT, Promise.CRW, Backend.RDMA,
                              True),
    "rdma_insert_cw": (cm.DSOp.HT_INSERT, Promise.CW, Backend.RDMA, False),
    "rdma_insert_cw_fused": (cm.DSOp.HT_INSERT, Promise.CW, Backend.RDMA,
                             True),
}

# fused impl -> its seed-engine counterpart (speedup accounting)
SPEEDUP_PAIRS = {
    "rdma_insert_crw_fused": "rdma_insert_crw",
    "rdma_insert_cw_fused": "rdma_insert_cw",
    "rdma_find_crw_fused": "rdma_find_crw",
    "rdma_find_cr_fused": "rdma_find_cr",
}


def _predict(impl, params):
    op, promise, backend, fused = PRED[impl]
    return cm.predict(op, promise, backend, params=params, fused=fused)


def fused_speedups(rows):
    return {f: rows[u] / rows[f] for f, u in SPEEDUP_PAIRS.items()
            if f in rows and u in rows and rows[f]}


def main(out="artifacts/bench"):
    csv = Csv(["benchmark", "nranks", "impl", "measured_us",
               "predicted_us"])
    comp = components.bench_components(P=8)
    params = components.calibrated_costs(comp)
    for P in (2, 4, 8):
        rows = bench_hashtable(P=P)
        preds = {impl: _predict(impl, params) for impl in rows}
        for impl, us in rows.items():
            csv.add("hashtable(fig5)", P, impl, f"{us:.3f}",
                    f"{preds[impl]:.3f}")
        m_order = sorted(rows, key=rows.get)
        p_order = sorted(preds, key=preds.get)
        agree = sum(a == b for a, b in zip(m_order, p_order))
        print(f"# P={P} order agreement {agree}/{len(m_order)}: "
              f"measured {m_order}")
        for f, s in fused_speedups(rows).items():
            print(f"# P={P} {f} speedup over seed path: {s:.2f}x")
    csv.dump(f"{out}/hashtable.csv")
    return csv


def smoke(P: int = 8, n: int = 64, iters: int = 9) -> bool:
    """Acceptance config: fused+planned RDMA path vs the seed path at
    P=8, n=64 — median speedup must be >= 1.3x on the hot ops."""
    rows = bench_hashtable(P=P, n=n, iters=iters)
    speedups = fused_speedups(rows)
    for f, s in sorted(speedups.items()):
        print(f"{f:28s} {rows[f]:8.3f} us  (seed {rows[SPEEDUP_PAIRS[f]]:8.3f}"
              f" us)  speedup {s:.2f}x")
    med = float(np.median(list(speedups.values())))
    print(f"median fused/planned speedup at P={P}, n={n}: {med:.2f}x "
          f"(target >= 1.3x)")
    return med >= 1.3


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    if "--smoke" in sys.argv:
        sys.exit(0 if smoke() else 1)
    main()
