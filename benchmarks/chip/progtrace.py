"""The program's own tracing over a window of one cell: the spans and
counters of `repro.core.trace`, read beside the profiler's device planes.

    python3 benchmarks/chip/progtrace.py --workload <cell> --seed <n> \
        --seconds <s> [--windows off,on,profiled]

Each window runs on a fresh set-up of the cell (`harness.Bench`), with
the program's tracing off (`off`), on (`on`) or on under the profiler
(`profiled`), is checked against the reference, and prints one JSON line:
its ops per second, the program's totals (`trace.take()`), the per-batch
readings below and, for a profiled window, the idle gaps labelled with
the innermost program span and the device time per named scope.

The readings, each over the window's batches:
    chooser.decide_ms_per_batch   self time of the span `ds.chooser.decide`
    host.reads_per_batch          counter `host_reads`
    exchange.exchanges_per_batch  counter `exchanges`
    device.exchange_share         device time of ops in the scope
                                  `ds.exchange` over the busy time

Exits with code 2 unless JAX finds a TPU and as many chips as the cell
asks for.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import glob
import json
import os
import sys
import time
from typing import Dict, List, Optional, Sequence, Tuple

if __name__ == "__main__":
    ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from benchmarks.chip import tracereduce as tr  # noqa: E402

DS_PREFIX = "ds."
EXCHANGE_SCOPE = "ds.exchange"


def ds_spans(pd) -> List[Tuple[float, float, str]]:
    """The program's spans on the host planes, (start_s, end_s, name)."""
    return [(e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9, e.name)
            for plane in pd.planes
            if not plane.name.startswith(tr.DEVICE_PLANE)
            for line in plane.lines for e in line.events
            if e.name.startswith(DS_PREFIX)]


def _xspace():
    """Message class of the part of the profiler's XSpace proto that
    holds the device ops and their metadata (field numbers as in
    `tsl/profiler/protobuf/xplane.proto`; other fields are skipped)."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(name="progtrace_xspace.proto",
                                           package="progtrace")

    def msg(name, *fields):
        m = f.message_type.add(name=name)
        for fname, num, typ, sub in fields:
            fd = m.field.add(name=fname, number=num, type=typ,
                             label=F.LABEL_REPEATED if sub and sub[0] == "*"
                             else F.LABEL_OPTIONAL)
            if typ == F.TYPE_MESSAGE:
                fd.type_name = ".progtrace." + sub.lstrip("*")

    I64, U64, STR, MSG = F.TYPE_INT64, F.TYPE_UINT64, F.TYPE_STRING, \
        F.TYPE_MESSAGE
    msg("XStat", ("metadata_id", 1, I64, None), ("str_value", 5, STR, None),
        ("ref_value", 7, U64, None))
    msg("XStatMetadata", ("id", 1, I64, None), ("name", 2, STR, None))
    msg("XEventMetadata", ("id", 1, I64, None), ("name", 2, STR, None),
        ("display_name", 4, STR, None), ("stats", 5, MSG, "*XStat"))
    msg("XEvent", ("metadata_id", 1, I64, None), ("offset_ps", 2, I64, None),
        ("duration_ps", 3, I64, None))
    msg("XLine", ("name", 2, STR, None), ("timestamp_ns", 3, I64, None),
        ("events", 4, MSG, "*XEvent"))
    msg("EventMetadataEntry", ("key", 1, I64, None),
        ("value", 2, MSG, "XEventMetadata"))
    msg("StatMetadataEntry", ("key", 1, I64, None),
        ("value", 2, MSG, "XStatMetadata"))
    msg("XPlane", ("name", 2, STR, None), ("lines", 3, MSG, "*XLine"),
        ("event_metadata", 4, MSG, "*EventMetadataEntry"),
        ("stat_metadata", 5, MSG, "*StatMetadataEntry"))
    msg("XSpace", ("planes", 1, MSG, "*XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("progtrace.XSpace"))


def innermost_scope(text: str) -> str:
    """The innermost `ds.` component of a scope path
    (`jit_while/while/body/ds.reply/ds.exchange/transpose`), or ""."""
    parts = [p for p in text.split("/") if p.startswith(DS_PREFIX)]
    return parts[-1].split(" ")[0] if parts else ""


def device_ops(raw: bytes) -> Dict[str, List]:
    """Per device plane, the "XLA Ops" events (start_s, end_s, scope) of
    a serialized XSpace. The scope is the innermost `ds.` component of
    the op's name path, which the profiler keeps in the op's event
    metadata (its name or a string stat), not on the event."""
    space = _xspace().FromString(raw)
    out: Dict[str, List] = {}
    for plane in space.planes:
        if not plane.name.startswith(tr.DEVICE_PLANE):
            continue
        strings = {e.key: e.value.name for e in plane.stat_metadata}
        scope = {}
        for entry in plane.event_metadata:
            md = entry.value
            texts = [md.name, md.display_name] + [
                st.str_value or strings.get(st.ref_value, "")
                for st in md.stats]
            scope[entry.key] = next(
                (x for x in map(innermost_scope, texts) if x), "")
        for line in plane.lines:
            if line.name != tr.OPS_LINE:
                continue
            t0 = line.timestamp_ns * 1e3
            out.setdefault(plane.name, []).extend(
                ((t0 + e.offset_ps) * 1e-12,
                 (t0 + e.offset_ps + e.duration_ps) * 1e-12,
                 scope.get(e.metadata_id, "")) for e in line.events)
    return out


def window_bounds(dev: Dict[str, List], spans: Sequence
                  ) -> Optional[Tuple[float, float]]:
    """[lo, hi] of the traced window as `tracereduce.reduce` takes it:
    the window span, cut where a trace buffer drop begins."""
    win = [(s, e) for s, e, n in spans if n == tr.WINDOW_SPAN]
    if not win:
        return None
    lo, end = win[0]
    drops = [s for evs in dev.values() for s, _, n in evs
             if n == tr.DROPPED and s < end]
    return lo, (max(lo, min(drops)) if drops else end)


def label(gap, spans) -> str:
    """Name of the innermost span (harness or program) that holds the
    gap's midpoint; harness spans lose their `bench.` prefix, program
    spans keep their `ds.` name."""
    mid = (gap[0] + gap[1]) / 2
    best = None
    for s, e, name in spans:
        if s <= mid <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    if best is None:
        return "outside_spans"
    name = best[2]
    return name[len(tr.SPAN_PREFIX):] if name.startswith(tr.SPAN_PREFIX) \
        else name


def top_gaps(dev: Dict[str, List], spans: Sequence, lo: float, hi: float
             ) -> List[Tuple[str, float]]:
    """The `tracereduce.TOP` longest idle gaps of the window, labelled."""
    all_gaps = []
    for evs in dev.values():
        busy = tr.union([(max(s, lo), min(e, hi)) for s, e, n in evs
                         if e > lo and s < hi and n != tr.DROPPED])
        all_gaps += tr.gaps(busy, lo, hi)
    inner = [x for x in spans if x[2] != tr.WINDOW_SPAN]
    top = sorted(all_gaps, key=lambda g: g[1] - g[0], reverse=True)[:tr.TOP]
    return [(label(g, inner), g[1] - g[0]) for g in top]


def scoped_s(ops: Dict[str, List], lo: float, hi: float) -> Dict[str, float]:
    """Device op self time per innermost `ds.` scope inside [lo, hi],
    averaged over the device planes; ops of no scope are left out."""
    out: Dict[str, float] = collections.defaultdict(float)
    for evs in ops.values():
        inside = [(max(s, lo), min(e, hi), n) for s, e, n in evs
                  if e > lo and s < hi]
        for n, t in tr.self_times(inside).items():
            if n:
                out[n] += t / len(ops)
    return dict(out)


def reduce(path: str) -> Optional[dict]:
    """The program view of the profiled window in the `.xplane.pb` at
    `path`: its bounds, the labelled idle gaps and the device time per
    scope; None without a window or a device plane (as
    `tracereduce.reduce`)."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    dev, bench_spans = tr.events_of(pd)
    bounds = window_bounds(dev, bench_spans)
    if bounds is None or not dev:
        return None
    lo, hi = bounds
    with open(path, "rb") as f:
        ops = device_ops(f.read())
    return {"window_s": hi - lo,
            "idle_gaps": top_gaps(dev, bench_spans + ds_spans(pd), lo, hi),
            "scoped_s": scoped_s(ops, lo, hi)}


def readings(program: dict, batches: int, summary=None,
             view: Optional[dict] = None) -> Dict[str, float]:
    """The per-batch readings of one window (module docstring)."""
    if not batches:
        return {}
    counts = program["counts"]
    out = {"chooser.decide_ms_per_batch":
           program["self_s"].get("chooser.decide", 0.0) * 1e3 / batches,
           "host.reads_per_batch": counts.get("host_reads", 0) / batches,
           "exchange.exchanges_per_batch":
           counts.get("exchanges", 0) / batches}
    if summary is not None and view and view["scoped_s"] and \
            summary.busy_s > 0:
        out["device.exchange_share"] = \
            view["scoped_s"].get(EXCHANGE_SCOPE, 0.0) / summary.busy_s
    return out


@contextlib.contextmanager
def _also_reduce(views: list):
    """`harness.Bench.window` reduces its profile with
    `tracereduce.read_dir` and deletes it; reduce it here as well first."""
    read_dir = tr.read_dir

    def both(log_dir):
        paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if paths:
            views.append(reduce(paths[0]))
        return read_dir(log_dir)

    tr.read_dir = both
    try:
        yield
    finally:
        tr.read_dir = read_dir


def run_window(cell, seed: int, seconds: float, mode: str,
               devices=None) -> dict:
    """One window of `cell` on a fresh set-up, with the program's
    tracing `off`, `on` or `profiled`; returns its result line."""
    from benchmarks.chip import harness
    from repro.core import trace
    bench = harness.Bench(cell, seed, devices)
    bench.prepare()
    views: list = []
    trace.take()
    if mode != "off":
        trace.enable()
    try:
        with _also_reduce(views):
            run = bench.window(seconds, mode == "profiled")
    finally:
        trace.disable()
    program = trace.take()
    checks = bench.check()
    bench.drv.free()
    batches = len(run.latencies)
    view = views[0] if views else None
    out = {"mode": mode, "seed": seed,
           "correct": all(v == 0 for v in checks.values()),
           "ops_per_s": run.attempted / run.window_s,
           "window_s": run.window_s, "batches": batches,
           "arms": run.arms, "program": program,
           "readings": readings(program, batches, run.trace, view)}
    if run.trace is not None:
        out["busy_s"] = run.trace.busy_s
        out["traced_window_s"] = run.trace.window_s
        out["harness_gaps"] = run.trace.idle_gaps
    if view is not None:
        out["idle_gaps"] = view["idle_gaps"]
        out["scoped_s"] = view["scoped_s"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--windows", default="off,on,profiled",
                    help="comma-separated modes: off, on, profiled")
    args = ap.parse_args(argv)
    from benchmarks.chip import harness
    cell = harness.resolve(args.workload)
    import jax
    devices = jax.devices()
    why = harness.need_chips(cell, devices)
    if why:
        print(f"progtrace.py: {args.workload} {why}", file=sys.stderr)
        return 2
    harness.set_compile_cache(harness.ROOT)
    for i, mode in enumerate(args.windows.split(",")):
        t = time.perf_counter()
        out = run_window(cell, args.seed + i, args.seconds, mode, devices)
        out["workload"] = args.workload
        out["run_s"] = time.perf_counter() - t
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
