"""The four-chip hash-table cell rehearsed on four virtual CPU devices
(`mesh_probe.py`, in a process of its own because the device count is
fixed before JAX starts): a correct run through `harness.run_cell`, the
same answers and table as on one device with the pre-load in one chunk
and in several, the table split by rank rows, an all-to-all in the
compiled insert program, and a lost shard caught by the check."""
import json
import os
import subprocess
import sys

import pytest

from chipbench_toy import ROOT


@pytest.fixture(scope="module")
def probe():
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [here, ROOT, os.path.join(ROOT, "src"),
         os.environ.get("PYTHONPATH", "")]))
    p = subprocess.run([sys.executable, os.path.join(here, "mesh_probe.py")],
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sharded_run_is_correct(probe):
    assert probe["correct"] is True, probe["checks"]
    assert all(v == 0 for v in probe["sound"].values()), probe["sound"]


def test_sharded_run_matches_one_device(probe):
    assert probe["same_answers"] is True
    assert probe["sharded"] is True


def test_chunked_preload_matches_one_device(probe):
    assert probe["chunks"] >= 2
    assert probe["chunked"] is True
    assert probe["claim_breaks"] == 0


def test_insert_program_exchanges_between_devices(probe):
    assert probe["all_to_all"] >= 1


def test_lost_shard_fails_the_check(probe):
    got = probe["lost_shard"]
    assert got is not None and got["acked_lost"] > 0, got
