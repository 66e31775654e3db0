"""The program-tracing reduction (`progtrace.py`): idle gaps labelled with
the innermost program span, device time per named scope and the
per-batch readings, against hand-computed values; and a toy window of
each cell on the CPU with the program's tracing off and on."""
import glob
from types import SimpleNamespace

import jax
import pytest

from chipbench_toy import CELLS, on_its_chips, toy

from benchmarks.chip import progtrace as pt
from benchmarks.chip import tracereduce as tr
from repro.core import trace

BENCH = [(0.0, 10.0, "bench.window"), (0.0, 6.0, "bench.front_end_call"),
         (6.0, 10.0, "bench.result_copy")]
DS = [(0.5, 5.5, "ds.find"), (1.0, 4.5, "ds.probe_loop"),
      (0.6, 0.9, "ds.chooser.decide")]
DEV = {"/device:TPU:0": [(0.2, 0.5, "a"), (4.5, 5.0, "b"), (7.0, 7.5, "c"),
                         (9.8, 12.0, tr.DROPPED)]}


def test_gaps_take_the_innermost_program_span():
    lo, hi = pt.window_bounds(DEV, BENCH)
    assert (lo, hi) == (0.0, 9.8)          # cut where the drop begins
    gaps = pt.top_gaps(DEV, BENCH + DS, lo, hi)
    # [0.5, 4.5] mid 2.5 in ds.probe_loop; [7.5, 9.8] in result_copy;
    # [5.0, 7.0] mid 6.0 in both harness spans: result_copy is shorter;
    # [0, 0.2] mid 0.1 in front_end_call only
    assert gaps == [("ds.probe_loop", pytest.approx(4.0)),
                    ("result_copy", pytest.approx(2.3)),
                    ("result_copy", pytest.approx(2.0)),
                    ("front_end_call", pytest.approx(0.2))]
    assert pt.label((0.61, 0.62), BENCH[1:] + DS) == "ds.chooser.decide"
    assert pt.label((11.0, 12.0), BENCH[1:] + DS) == "outside_spans"
    # the harness's own labels, from the same gaps, are unchanged
    s = tr.reduce(DEV, BENCH)
    assert [n for n, _ in s.idle_gaps] == ["front_end_call", "result_copy",
                                           "result_copy", "front_end_call"]


def test_scoped_time_against_hand_sums():
    ops = {"/device:TPU:0": [(1.0, 2.0, "ds.exchange"),
                             (1.5, 1.7, "ds.owner_apply"),
                             (2.0, 2.5, ""), (3.0, 4.0, "ds.owner_apply"),
                             (9.5, 11.0, "ds.exchange")],
           "/device:TPU:1": [(1.0, 1.4, "ds.exchange")]}
    got = pt.scoped_s(ops, 0.0, 10.0)
    # plane 0: exchange 1.0 - 0.2 nested + 0.5 clipped; apply 0.2 + 1.0
    assert got == {"ds.exchange": pytest.approx((1.3 + 0.4) / 2),
                   "ds.owner_apply": pytest.approx(1.2 / 2)}
    assert pt.innermost_scope(
        "jit_while/while/body/ds.reply/ds.exchange/transpose") == \
        "ds.exchange"
    assert pt.innermost_scope("jit_x/add") == ""


def test_device_ops_take_their_scope_from_the_op_metadata():
    """A serialized XSpace as the profiler writes it: the scope path sits
    in an op's event metadata, as a string stat or an interned one."""
    space = pt._xspace()()
    plane = space.planes.add(name="/device:TPU:0")
    plane.stat_metadata.add(key=9).value.name = "jit_f/ds.owner_apply/add"
    md = plane.event_metadata.add(key=1).value
    md.name = "%transpose.3 = s32[8,8] transpose(%p)"
    md.stats.add(metadata_id=7,
                 str_value="jit_while/while/body/ds.reply/ds.exchange/t")
    plane.event_metadata.add(key=2).value.stats.add(metadata_id=7,
                                                    ref_value=9)
    plane.event_metadata.add(key=3).value.name = "%fusion.1 = s32[8] add"
    line = plane.lines.add(name=tr.OPS_LINE, timestamp_ns=1000)
    for mid, off in ((1, 0), (2, 5_000_000), (3, 9_000_000)):
        line.events.add(metadata_id=mid, offset_ps=off, duration_ps=2_000)
    plane.lines.add(name=tr.MODULES_LINE).events.add(metadata_id=3)
    space.planes.add(name="/host:CPU").lines.add(name="python")
    ops = pt.device_ops(space.SerializeToString())
    assert list(ops) == ["/device:TPU:0"]
    assert [(pytest.approx(s), pytest.approx(e), n)
            for s, e, n in ops["/device:TPU:0"]] == [
        (1e-6, 1e-6 + 2e-9, "ds.exchange"),
        (1e-6 + 5e-6, 1e-6 + 5e-6 + 2e-9, "ds.owner_apply"),
        (1e-6 + 9e-6, 1e-6 + 9e-6 + 2e-9, "")]


def test_readings_per_batch():
    program = {"counts": {"host_reads": 8, "exchanges": 52},
               "spans": {}, "self_s": {"chooser.decide": 0.06}}
    summary = SimpleNamespace(busy_s=2.0)
    view = {"scoped_s": {"ds.exchange": 0.5}}
    got = pt.readings(program, 4, summary, view)
    assert got == {"chooser.decide_ms_per_batch": pytest.approx(15.0),
                   "host.reads_per_batch": 2.0,
                   "exchange.exchanges_per_batch": 13.0,
                   "device.exchange_share": 0.25}
    assert "device.exchange_share" not in pt.readings(program, 4)
    assert pt.readings(program, 0) == {}


def test_program_spans_recorded_on_the_cpu(tmp_path):
    d = str(tmp_path)
    jax.profiler.start_trace(d)
    trace.take()
    trace.enable()
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with trace.root("find"), trace.span("probe_loop"):
                jax.numpy.ones(8).block_until_ready()
    finally:
        trace.disable()
        jax.profiler.stop_trace()
    pb = glob.glob(d + "/**/*.xplane.pb", recursive=True)
    pd = jax.profiler.ProfileData.from_file(pb[0])
    names = sorted(n for _, _, n in pt.ds_spans(pd) if n != "ds.gc")
    assert names == ["ds.find", "ds.probe_loop"]
    with open(pb[0], "rb") as f:
        assert pt.device_ops(f.read()) == {}   # the CPU has no device plane
    assert pt.reduce(pb[0]) is None
    got = trace.take()["spans"]
    assert (got["find"], got["probe_loop"]) == (1, 1)


@pytest.mark.parametrize("name", CELLS)
def test_toy_windows_off_and_on(name, request):
    if on_its_chips(request, name):
        return
    off = pt.run_window(toy(name), (1 << 31) + 9, 0.3, "off")
    on = pt.run_window(toy(name), (1 << 31) + 9, 0.3, "on")
    assert off["correct"] and on["correct"]
    assert off["program"] == {"counts": {}, "spans": {}, "self_s": {}}
    r = on["readings"]
    assert r["exchange.exchanges_per_batch"] >= 3
    assert r["host.reads_per_batch"] == (0 if "queue" in name else 2)
    assert r["chooser.decide_ms_per_batch"] > 0
    assert on["program"]["spans"]["chooser.decide"] == on["batches"]
