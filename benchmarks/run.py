"""Benchmark driver: one section per paper table/figure + the roofline
report. Prints CSV; artifacts land in artifacts/bench/, including the
machine-readable artifacts/bench/BENCH_components.json (per-op µs,
exchange counts, fused-vs-unfused speedups — the cross-PR perf
trajectory; see also scripts/smoke.sh for the quick config)."""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from . import (adaptive_bench, attentiveness, components,
                   hashtable_bench, pipeline_bench, queue_bench, roofline,
                   trajectory)
    sections = [
        ("components (paper Fig. 3 / Table I)", components.main),
        ("queue push (paper Fig. 4)", queue_bench.main),
        ("hash table (paper Fig. 5)", hashtable_bench.main),
        ("attentiveness (paper Fig. 6)", attentiveness.main),
        ("adaptive backend selection (DESIGN.md §4)", adaptive_bench.main),
        ("pipelined batch engine (DESIGN.md §7)", pipeline_bench.main),
        ("roofline (assignment §Roofline)", roofline.main),
        ("perf trajectory (BENCH_trajectory.json)", trajectory.main),
    ]
    failures = 0
    for title, fn in sections:
        print(f"\n=== {title} ===", flush=True)
        try:
            fn()
        except Exception:
            traceback.print_exc()
            failures += 1
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
