"""Readings for the limits of a cell's checks: sound windows and the
broken ones of `faults.py`, at the cell's own size, several seeds in one
process.

    python3 benchmarks/chip/control.py --workload <cell> \
        --seeds 11,12,13 --seconds 5 [--faults control,stale_state,...]

For every seed it sets the cell up once, then runs a sound window and
one window under each fault that the cell can have, and prints one JSON
line per window with each check's count. Not part of a benchmark run.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def readings(name, seeds, seconds, fault_names, *, cell=None, devices=None,
             emit=print):
    from benchmarks.chip import faults, harness
    cell = cell or harness.resolve(name)
    pattern = cell.mix["pattern"]
    out = []
    for seed in seeds:
        bench = None
        for fname in [None] + list(fault_names):
            if fname is not None and not faults.applies(fname, pattern):
                continue
            if bench is None or not bench.plan.epoch:
                if bench is not None:
                    bench.drv.free()
                t = time.perf_counter()
                bench = harness.Bench(cell, seed, devices)
                bench.prepare()
                setup_s = time.perf_counter() - t
            else:
                bench.reset()
            if fname is None:
                run = bench.window(seconds, False)
            else:
                with faults.FAULTS[fname](bench.drv):
                    run = bench.window(seconds, False)
            checks = bench.check()
            row = {"workload": name, "seed": seed, "fault": fname,
                   "batches": len(run.latencies), "setup_s": setup_s,
                   "correct": all(v == 0 for v in checks.values()),
                   "checks": checks}
            emit(json.dumps(row))
            out.append(row)
        bench.drv.free()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--faults",
                    default="control,stale_state,half_batch,altered_answer")
    args = ap.parse_args(argv)
    from benchmarks.chip import harness
    cell = harness.resolve(args.workload)
    import jax
    why = harness.need_chips(cell, jax.devices())
    if why:
        print(f"control.py: {args.workload} {why}", file=sys.stderr)
        return 2
    harness.set_compile_cache(harness.ROOT)
    seeds = [int(s) for s in args.seeds.split(",")]
    faults = [f for f in args.faults.split(",") if f]
    readings(args.workload, seeds, args.seconds, faults,
             cell=cell, emit=lambda s: print(s, flush=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
