"""The trace reduction: union, gaps, labels and self time against
hand-computed intervals, on synthetic device events and on host spans
recorded by the profiler on the CPU."""
import glob
import tempfile

import jax
import jax.numpy as jnp
import pytest

from chipbench_toy import ROOT  # noqa: F401  (puts the repo on sys.path)

from benchmarks.chip import tracereduce as tr


def test_union_clip_gaps():
    merged = tr.union([(5, 7), (0, 2), (1, 3), (6, 9), (12, 13)])
    assert merged == [(0, 3), (5, 9), (12, 13)]
    assert tr.total(merged) == 3 + 4 + 1
    assert tr.clip(merged, 2, 12.5) == [(2, 3), (5, 9), (12, 12.5)]
    assert tr.gaps(tr.clip(merged, 2, 14), 2, 14) == [(3, 5), (9, 12),
                                                       (13, 14)]
    assert tr.gaps([], 0, 4) == [(0, 4)]


def test_self_times_subtract_nested_events():
    ev = [(0, 10, "while"), (1, 3, "sort"), (4, 8, "body"), (5, 6, "sort"),
          (12, 14, "sort")]
    st = tr.self_times(ev)
    assert st == {"while": 10 - 2 - 4, "sort": 2 + 1 + 2, "body": 4 - 1}


def test_label_is_innermost_span():
    spans = [(0, 10, "bench.front_end_call"), (2, 4, "bench.result_copy")]
    assert tr.label((2.5, 3.5), spans) == "result_copy"
    assert tr.label((5, 6), spans) == "front_end_call"
    assert tr.label((11, 12), spans) == "outside_spans"


def test_reduce_against_hand_computed():
    spans = [(0.0, 10.0, "bench.window"), (0.0, 6.0, "bench.front_end_call"),
             (6.0, 10.0, "bench.result_copy")]
    dev = {"/device:TPU:0": [(-1.0, 1.0, "a"), (2.0, 3.0, "b"),
                             (2.5, 4.0, "a"), (7.0, 7.5, "c"),
                             (11.0, 12.0, "d")]}
    s = tr.reduce(dev, spans)
    # busy: [0,1] + [2,4] + [7,7.5] = 3.5 of a 10 s window
    assert s.window_s == 10.0 and s.busy_s == pytest.approx(3.5)
    assert s.idle_share == pytest.approx(0.65)
    # gaps [1,2], [4,7], [7.5,10], labelled at their midpoints
    assert s.idle_gaps == [("front_end_call", pytest.approx(3.0)),
                           ("result_copy", pytest.approx(2.5)),
                           ("front_end_call", pytest.approx(1.0))]
    assert ("a", pytest.approx(2.5)) in s.device_ops
    assert s.batches == 1 and s.dropped_s == 0
    assert tr.reduce({}, spans) is None
    assert tr.reduce(dev, spans[1:]) is None


def test_reduce_stops_at_a_buffer_drop():
    spans = [(0.0, 10.0, "bench.window"), (0.0, 2.0, "bench.front_end_call"),
             (2.0, 4.2, "bench.front_end_call"),
             (4.2, 9.0, "bench.front_end_call")]
    dev = {"/device:TPU:0": [(0.5, 1.5, "m"), (2.5, 4.0, "m"),
                             (4.5, 10.0, tr.DROPPED)]}
    s = tr.reduce(dev, spans)
    # traced window [0, 4.5]: busy 1 + 1.5, two calls completed in it
    assert s.window_s == pytest.approx(4.5) and s.dropped_s == 5.5
    assert s.busy_s == pytest.approx(2.5) and s.batches == 2


def test_spans_recorded_on_the_cpu():
    d = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    x = jnp.ones(64)
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.front_end_call"):
                x = (x * 2).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.result_copy"):
                x.tolist()
    jax.profiler.stop_trace()
    pb = glob.glob(d + "/**/*.xplane.pb", recursive=True)
    pd = jax.profiler.ProfileData.from_file(pb[0])
    dev, spans = tr.events_of(pd)
    assert dev == {}        # the CPU has no device plane
    names = [n for _, _, n in spans]
    assert names.count("bench.window") == 1
    assert names.count("bench.front_end_call") == 3
    assert names.count("bench.result_copy") == 3
    (w0, w1, _), = [s for s in spans if s[2] == "bench.window"]
    inner = [s for s in spans if s[2] != "bench.window"]
    assert all(w0 <= s <= e <= w1 for s, e, _ in inner)
    # the window's gaps with the recorded calls standing in for device ops
    calls = tr.union([(s, e) for s, e, n in inner
                      if n == "bench.front_end_call"])
    summary = tr.reduce({"/device:TPU:0": [(s, e, "call")
                                           for s, e in calls]}, spans)
    assert summary.busy_s == pytest.approx(tr.total(calls))
    assert summary.window_s == pytest.approx(w1 - w0)
    want = tr.gaps(calls, w0, w1)
    assert sorted(g for _, g in summary.idle_gaps) == pytest.approx(
        sorted(e - s for s, e in want)[-tr.TOP:])
