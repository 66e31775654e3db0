"""One run of one cell: set-up from the seed, a closed-loop window, the
check against the plain reference, and the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name `BENCHMARK.json` gives:

    configs/<config>.json        deployment, sizes, source, guarantees
    traffic/<mix>.json           parameters that `traffic.py` reads
    structures/<structure>.py    drives the program's front-ends
    reference/<structure>.py     plain numpy reference and its checks
    metrics/<metric>.py          `read(ctx)` of one per-layer metric

The window is a closed loop: one caller issues a batch through the
program's front-end, waits on `block_until_ready` of everything it
returns, copies the answers to the host and issues the next batch, until
`seconds` have passed. The batch in flight at the deadline completes and
counts; the window ends at its completion.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import pathlib
import shutil
import statistics
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
WARMUP_CYCLES = 1       # passes of the traffic pattern before the window


def say(*parts) -> None:
    print("bench:", *parts, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# finding a cell's files by name
# ---------------------------------------------------------------------------
def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


@dataclasses.dataclass
class Cell:
    workload: dict
    config_entry: dict
    config: dict
    mix: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def resolve(name: str, bench: Optional[dict] = None) -> Cell:
    """The workload `name` with its configuration, traffic mix and the
    metrics it reports."""
    bench = bench or load_benchmark()
    wl = {w["name"]: w for w in bench["workloads"]}.get(name)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    entry = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    config = json.loads((ROOT / entry["file"]).read_text())
    mix = json.loads((HERE / "traffic" / f"{wl['traffic']}.json").read_text())

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return Cell(wl, entry, config, mix, mine(bench["end_to_end"]),
                mine(bench["per_layer"]))


def structure(kind: str):
    return importlib.import_module(f"benchmarks.chip.structures.{kind}")


def reference(kind: str):
    return importlib.import_module(f"benchmarks.chip.reference.{kind}")


def metric_reader(name: str) -> Callable[[dict], Optional[float]]:
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "benchmarks.chip.metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
class CompileClock:
    """Counts and seconds of JAX's compile events while `active`, from
    `jax.monitoring`'s duration listener."""

    def __init__(self):
        import jax
        self.active = False
        self.n: Dict[str, int] = {}
        self.s: Dict[str, float] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.active and event.startswith("/jax/core/compile/"):
            self.n[event] = self.n.get(event, 0) + 1
            self.s[event] = self.s.get(event, 0.0) + duration

    def close(self):
        from jax._src import monitoring
        unreg = getattr(monitoring,
                        "_unregister_event_duration_listener_by_callback",
                        None)
        if unreg is not None:
            unreg(self._on)


def set_compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent compilation cache at the fixed `<checkout>/.jax_cache`,
    every program kept, whatever the environment says."""
    import jax
    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(devices) -> dict:
    d = devices[0]
    peak = max((x.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for x in devices)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Run:
    """What one run measured and recorded."""
    setup: Dict[str, float]
    latencies: List[float]
    window_s: float
    attempted: int
    failed: int
    counters: Dict[str, float]
    compile_n: Dict[str, int]
    compile_s: Dict[str, float]
    arms: Dict[str, Dict[str, int]]
    trace: object = None


class Bench:
    """One cell at one seed: set-up, then any number of windows, each
    checked against the reference."""

    def __init__(self, cell: Cell, seed: int, devices=None,
                 t_start: Optional[float] = None):
        import jax
        from benchmarks.chip import traffic
        self.t_start = time.perf_counter() if t_start is None else t_start
        self.cell = cell
        self.devices = devices or jax.devices()
        self.plan = traffic.Plan(cell.config, cell.mix, seed)
        self.drv = structure(cell.config["structure"]).Driver(cell.config,
                                                              self.plan)
        self.setup: Dict[str, float] = {}
        self.warm: List[dict] = []
        self.k = 0

    def _one(self, k: int, ep: int, batches: List[dict]):
        import jax
        op, slot = self.plan.op(k), self.plan.slot(k)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.front_end_call"):
            n, out = self.drv.issue(op, slot)
            jax.block_until_ready(out)
        with jax.profiler.TraceAnnotation("bench.result_copy"):
            outs = tuple(np.asarray(x) for x in self.drv.accept(out))
        dt = time.perf_counter() - t0
        batches.append({"op": op, "slot": slot, "epoch": ep, "out": outs})
        return n, outs, dt

    def prepare(self) -> Dict[str, float]:
        """Build and pre-load the state, make the inputs, and warm up the
        cell's own batch shapes."""
        import jax
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.preload"):
            self.built = self.drv.build()
            jax.block_until_ready(self.built)
        self.setup["preload_s"] = time.perf_counter() - t
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.key_generation"):
            self.drv.make_pools()
        self.setup["key_generation_s"] = time.perf_counter() - t
        t = time.perf_counter()
        for k in range(WARMUP_CYCLES * len(self.plan.pattern)):
            self._one(k, -1, self.warm)
        # with epochs the window starts again from the pre-loaded state
        self.k = 0 if self.plan.epoch else len(self.warm)
        if self.plan.epoch:
            self.drv.restore()
        self.setup["warmup_s"] = time.perf_counter() - t
        return self.setup

    def reset(self):
        """Back to the state right after set-up, for a further window;
        only a cell with epochs can go back."""
        if not self.plan.epoch:
            raise ValueError("this cell keeps its state; set it up again")
        self.drv.restore()
        self.k = 0

    def window(self, seconds: float, trace: bool) -> Run:
        """One closed-loop window of `seconds`."""
        import jax
        plan, epoch, k0 = self.plan, self.plan.epoch, self.k
        n_log0 = len(self.drv.arms())
        clock = CompileClock()
        tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
        if trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(tdir, profiler_options=opts)
        self.setup["setup_s"] = time.perf_counter() - self.t_start
        batches: List[dict] = []
        latencies, attempted, failed = [], 0, 0
        counters: Dict[str, float] = {}
        clock.active = True
        w0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            k = k0
            while True:
                ep = (k - k0) // epoch if epoch else 0
                n, outs, dt = self._one(k, ep, batches)
                latencies.append(dt)
                attempted += n
                failed += self.drv.tally(plan.op(k), outs, counters)
                k += 1
                if time.perf_counter() - w0 >= seconds:
                    break
                if epoch and (k - k0) % epoch == 0:
                    with jax.profiler.TraceAnnotation("bench.epoch_restore"):
                        self.drv.restore()
        window_s = time.perf_counter() - w0
        clock.active = False
        self.k = k
        summary = None
        if trace:
            jax.profiler.stop_trace()
            from benchmarks.chip import tracereduce
            summary = tracereduce.read_dir(tdir)
            shutil.rmtree(tdir, ignore_errors=True)
        clock.close()
        arms: Dict[str, Dict[str, int]] = {}
        for i, arm in enumerate(self.drv.arms()[n_log0:]):
            per_op = arms.setdefault(plan.op(k0 + i), {})
            per_op[arm] = per_op.get(arm, 0) + 1
        self.batches = batches
        return Run(setup=dict(self.setup), latencies=latencies,
                   window_s=window_s, attempted=attempted, failed=failed,
                   counters=counters, compile_n=clock.n,
                   compile_s=clock.s, arms=arms, trace=summary)

    def check(self) -> Dict[str, int]:
        """The reference's counts for the last window, the warm-up
        batches and the set-up; every count must be 0."""
        kind = self.cell.config["structure"]
        record = dict(self.drv.record(self.built),
                      batches=self.warm + self.batches)
        return reference(kind).check(self.plan, self.cell.config, record)


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             cell: Optional[Cell] = None, devices=None,
             t_start: Optional[float] = None) -> dict:
    """One run of a cell; returns the result object (`result_line`)."""
    bench = Bench(cell or resolve(name), seed, devices, t_start)
    bench.prepare()
    run = bench.window(seconds, trace)
    dev = device_info(bench.devices)
    t = time.perf_counter()
    checks = bench.check()
    bench.drv.free()
    return result_line(bench.cell, run, trace, dev, checks,
                       time.perf_counter() - t)


def _quantile(xs, q):
    """The q-th percentile by linear interpolation between order stats."""
    return float(np.percentile(np.asarray(xs), q))


def result_line(cell: Cell, run: Run, trace: bool, dev: dict,
                checks: Dict[str, int], check_s: float) -> dict:
    lat_ms = [x * 1e3 for x in run.latencies]
    say(f"setup split: " + " ".join(f"{k}={v}" for k, v in run.setup.items()))
    say(f"window: {len(lat_ms)} batches in {run.window_s} s, "
        f"batch_p50_ms={statistics.median(lat_ms)} "
        f"batch_p90_ms={_quantile(lat_ms, 90)} "
        f"batch_max_ms={max(lat_ms)}")
    say(f"arms picked per op: {json.dumps(run.arms, sort_keys=True)}")
    say(f"compile events in the window: {json.dumps(run.compile_n)}")
    say(f"device {dev}")
    say(f"reference check took {check_s} s")
    if run.trace is not None:
        say(f"traced window {run.trace.window_s} s, busy {run.trace.busy_s} s,"
            f" {run.trace.batches} batches; lost to a trace buffer drop: "
            f"{run.trace.dropped_s} s")
    ctx = {"window_s": run.window_s, "batches": len(lat_ms),
           "compile_n": run.compile_n, "compile_s": run.compile_s,
           "counters": run.counters, "trace": run.trace}
    metrics: Dict[str, dict] = {}
    if trace:
        for m in cell.per_layer:
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        values = {"ops_per_s": run.attempted / run.window_s,
                  "batch_p90_ms": _quantile(lat_ms, 90),
                  "setup_s": run.setup["setup_s"]}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(values[m["name"]]),
                                  "unit": m["unit"]}
    device = dict(dev)
    out = {"correct": all(v == 0 for v in checks.values()),
           "attempted": run.attempted, "failed": run.failed,
           "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in run.trace.device_ops],
            "idle_gaps": [[n, s] for n, s in run.trace.idle_gaps]}
    out["checks"] = {k: {"value": v, "limit": 0} for k, v in checks.items()}
    for k, v in checks.items():
        say(f"check {k}: {v} (limit 0)")
    return out


def need_chips(cell: Cell, devices) -> Optional[str]:
    """Why this machine cannot run the cell, or None."""
    if not devices or devices[0].platform != "tpu":
        return (f"needs a TPU, found "
                f"{devices[0].platform if devices else 'no device'}")
    if len(devices) < int(cell.workload["chips"]):
        return f"needs {cell.workload['chips']} chips, found {len(devices)}"
    return None
