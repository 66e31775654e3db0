"""Shared by the benchmark's CPU tests: the repository on sys.path, and
each cell cut to a size the CPU runs in seconds (same code paths, same
traffic shape, smaller tables and batches)."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmarks.chip import harness  # noqa: E402

CELLS = [w["name"] for w in harness.load_benchmark()["workloads"]]


def toy(name: str) -> harness.Cell:
    cell = harness.resolve(name)
    cfg, mix = dict(cell.config), dict(cell.mix)
    if cfg["structure"] == "hashtable":
        cfg["nslots"] = 1 << 12
        mix.update(batch_per_rank=64, pool_batches=4)
    else:
        cfg["capacity"] = 1 << 12
        mix.update(batch_per_rank=64, prefill=1 << 11, pool_batches=4)
    cell.config, cell.mix = cfg, mix
    return cell
