"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell is a `workloads` entry of `BENCHMARK.json`. With `--trace 0` the
last line of stdout reports the cell's end-to-end metrics; with
`--trace 1` a traced window reports its per-layer metrics, the device's
busy seconds and the top device operations and idle gaps. Progress, the
set-up split and each checked count beside its limit go to stderr.

Exits with code 2, printing no result, unless JAX finds a TPU and as many
chips as the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks.chip import harness
    cell = harness.resolve(args.workload)
    import jax
    devices = jax.devices()
    why = harness.need_chips(cell, devices)
    if why:
        print(f"run.py: {args.workload} {why}", file=sys.stderr)
        return 2
    harness.set_compile_cache(harness.ROOT)
    out = harness.run_cell(args.workload, args.seed, args.seconds,
                           bool(args.trace), cell=cell, devices=devices,
                           t_start=T_START)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
