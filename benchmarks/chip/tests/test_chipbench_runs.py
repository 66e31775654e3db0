"""Each traffic mix runs a few batches at a toy size through the
program's real front-ends, on the CPU, and the run's result line has the
contract's shape."""
import json

import pytest

from chipbench_toy import CELLS, harness, toy


@pytest.mark.parametrize("name", CELLS)
def test_toy_run_is_correct(name):
    trace = name == CELLS[0]
    out = harness.run_cell(name, (1 << 31) + 5, 0.5, trace, cell=toy(name))
    json.dumps(out)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0
    assert all(c["limit"] == 0 for c in out["checks"].values())
    cell = harness.resolve(name)
    if trace:
        # the CPU has no device plane: only host metrics are read
        assert set(out["metrics"]) == {"host.compile_share",
                                       "host.traces_per_batch",
                                       "owner.probes_per_insert"}
        assert 1 <= out["metrics"]["owner.probes_per_insert"]["value"] <= 8
    else:
        assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
        for m in out["metrics"].values():
            assert m["value"] > 0
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] >= 1
