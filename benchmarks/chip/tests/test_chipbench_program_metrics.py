"""The per-layer metrics that read the program's own counters: a traced
window turns the program's tracing on and keeps its totals, and each
metric's reader gives what `progtrace.readings` gives for them."""
import pytest

from chipbench_toy import CELLS, harness, on_its_chips, toy

from benchmarks.chip import progtrace
from repro.core import trace

METRICS = ("host.reads_per_batch", "exchange.exchanges_per_batch")


@pytest.mark.parametrize("name", CELLS)
def test_program_metrics_equal_progtrace_readings(name, request):
    if on_its_chips(request, name):
        return
    bench = harness.Bench(toy(name), (1 << 31) + 21)
    bench.prepare()
    run = bench.window(0.3, True)
    bench.drv.free()
    assert not trace._on                 # off again after the window
    batches = len(run.latencies)
    want = progtrace.readings(run.program, batches)
    ctx = {"program": run.program, "batches": batches}
    for m in METRICS:
        got = harness.metric_reader(m)(ctx)
        assert got == want[m], m
    assert want["exchange.exchanges_per_batch"] >= 3
    # the program's counts cover the window only, not the warm-up
    assert run.program["spans"]["chooser.decide"] == batches


def test_untraced_window_reads_no_program_metric():
    ctx = {"program": None, "batches": 4}
    for m in METRICS:
        assert harness.metric_reader(m)(ctx) is None
