"""The check fails a run whose timed path is broken underneath: the
control (a guarantee broken) and each fault the cell can have. The
harness's look for a chip is skipped; the rest of the run is the
benchmark's own, at a toy size on the CPU, on as many devices as the
cell has chips."""
import pytest

from chipbench_toy import CELLS, on_its_chips, toy

from benchmarks.chip import control, faults


@pytest.mark.parametrize("name", CELLS)
def test_every_fault_fails_the_check(name, request):
    if on_its_chips(request, name):
        return
    rows = control.readings(name, [3], 0.3, list(faults.FAULTS),
                            cell=toy(name), emit=lambda s: None)
    sound = [r for r in rows if r["fault"] is None]
    broken = {r["fault"]: r for r in rows if r["fault"] is not None}
    assert len(sound) == 1 and sound[0]["correct"], sound
    cell = toy(name)
    want = {f for f in faults.FAULTS
            if faults.applies(f, cell.mix["pattern"], cell.workload["chips"])}
    assert set(broken) == want
    for f, r in broken.items():
        assert not r["correct"], (f, r["checks"])
