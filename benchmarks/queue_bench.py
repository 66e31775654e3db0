"""Paper Fig. 4: queue push latencies — AM push, RDMA C_W, RDMA C_RW,
checksum C_RW — measured on the phase engine vs the analytical model's
prediction from calibrated component costs. The validation target is the
model's ORDERING of implementations (paper §IV)."""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from repro.core import am as am_mod
from repro.core import costmodel as cm
from repro.core import queue as q_mod
from repro.core.types import Backend, Promise

from . import components
from .common import Csv, time_op


def bench_queue(P: int = 8, n: int = 32, iters: int = 15):
    ops = P * n
    vals = jnp.ones((P, n, 2), jnp.int32)

    def push_cw(data, planned=False):
        q = q_mod.DQueue(win=q_mod.Window(data=data), host=0,
                         capacity=1 << 16, val_words=2)
        q, _ = q_mod.push_rdma(q, vals, promise=Promise.CW, planned=planned)
        return q.win.data

    def push_crw(data, planned=False):
        q = q_mod.DQueue(win=q_mod.Window(data=data), host=0,
                         capacity=1 << 16, val_words=2)
        q, _ = q_mod.push_rdma(q, vals, promise=Promise.CRW,
                               planned=planned)
        return q.win.data

    def push_cw_planned(data):
        return push_cw(data, planned=True)

    def push_crw_planned(data):
        return push_crw(data, planned=True)

    def push_csum(data):
        q = q_mod.DQueue(win=q_mod.Window(data=data), host=0,
                         capacity=1 << 16, val_words=2, checksum=True)
        q, _ = q_mod.push_rdma(q, vals, promise=Promise.CRW)
        return q.win.data

    qa = q_mod.make_queue(P, 0, 1 << 16, 2)
    qc = q_mod.make_queue(P, 0, 1 << 16, 2, checksum=True)
    eng = am_mod.AMEngine(P)
    q_mod.build_am_handlers(q_mod.make_queue(P, 0, 1 << 16, 2), eng)

    def push_am(data):
        q = q_mod.DQueue(win=q_mod.Window(data=data), host=0,
                         capacity=1 << 16, val_words=2)
        q, _ = q_mod.push_rpc(q, eng, vals)
        return q.win.data

    return {
        "am_push": time_op(push_am, qa.win.data, iters=iters,
                           ops_per_call=ops),
        "rdma_push_cw": time_op(push_cw, qa.win.data, iters=iters,
                                ops_per_call=ops),
        "rdma_push_cw_planned": time_op(push_cw_planned, qa.win.data,
                                        iters=iters, ops_per_call=ops),
        "rdma_push_crw": time_op(push_crw, qa.win.data, iters=iters,
                                 ops_per_call=ops),
        "rdma_push_crw_planned": time_op(push_crw_planned, qa.win.data,
                                         iters=iters, ops_per_call=ops),
        "rdma_checksum_push_crw": time_op(push_csum, qc.win.data,
                                          iters=iters, ops_per_call=ops),
    }


PRED = {
    "am_push": (cm.DSOp.Q_PUSH, Promise.CW, Backend.RPC),
    "rdma_push_cw": (cm.DSOp.Q_PUSH, Promise.CW, Backend.RDMA),
    "rdma_push_cw_planned": (cm.DSOp.Q_PUSH, Promise.CW, Backend.RDMA),
    "rdma_push_crw": (cm.DSOp.Q_PUSH, Promise.CRW, Backend.RDMA),
    "rdma_push_crw_planned": (cm.DSOp.Q_PUSH, Promise.CRW, Backend.RDMA),
}


def main(out="artifacts/bench"):
    csv = Csv(["benchmark", "nranks", "impl", "measured_us",
               "predicted_us"])
    comp = components.bench_components(P=8)
    params = components.calibrated_costs(comp)
    ordering_ok = []
    for P in (2, 4, 8):
        rows = bench_queue(P=P)
        preds = {}
        for impl, us in rows.items():
            if impl in PRED:
                op, promise, backend = PRED[impl]
                pred = cm.predict(op, promise, backend, params=params)
            else:
                pred = cm.predict_checksum_push(params=params)
            preds[impl] = pred
            csv.add("queue_push(fig4)", P, impl, f"{us:.3f}", f"{pred:.3f}")
        # ordering validation (the model's real claim) — over the paper's
        # impl set; planned rows share predictions so they would tie
        base_impls = [i for i in rows if not i.endswith("_planned")]
        m_order = sorted(base_impls, key=rows.get)
        p_order = sorted(base_impls, key=preds.get)
        ordering_ok.append(m_order == p_order)
        print(f"# P={P} measured order {m_order}")
        print(f"# P={P} predicted order {p_order}")
        for promise in ("cw", "crw"):
            seed = rows[f"rdma_push_{promise}"]
            planned = rows[f"rdma_push_{promise}_planned"]
            print(f"# P={P} push_{promise} planned speedup: "
                  f"{seed / planned:.2f}x")
    csv.dump(f"{out}/queue.csv")
    print(f"# ordering agreement: {sum(ordering_ok)}/{len(ordering_ok)}")
    return csv


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
