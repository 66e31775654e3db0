"""BENCHMARK.json resolves to its files by name and keeps the contract's
shape; the generator is deterministic from the seed; the peaks table
refuses an unknown device; run.py refuses a machine without a TPU."""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from chipbench_toy import CELLS, ROOT, harness

from benchmarks.chip import peaks, traffic

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
BENCH = harness.load_benchmark()


def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    script = BENCH["command"][-1]
    assert script.startswith("benchmarks/chip/") and \
        os.path.isfile(os.path.join(ROOT, script))
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_names_units_and_lengths():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    for x in BENCH["configs"] + BENCH["workloads"]:
        for key in ("why", "source"):
            if key in x:
                assert 1 <= len(x[key]) <= 200 and "\n" not in x[key]


@pytest.mark.parametrize("name", CELLS)
def test_every_cell_resolves(name):
    cell = harness.resolve(name)
    kind = cell.config["structure"]
    assert cell.mix["structure"] == kind
    assert cell.workload["chips"] in (1, 4)
    harness.structure(kind).Driver
    harness.reference(kind).check
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for key in cell.config_entry["reduced"]:
        assert key in cell.config and NAME.match(key)


def test_every_metric_has_a_reader():
    for m in BENCH["per_layer"]:
        read = harness.metric_reader(m["name"])
        assert callable(read)
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        assert set(m["workloads"]) <= set(CELLS)


@pytest.mark.parametrize("name", CELLS)
def test_generator_is_deterministic_from_the_seed(name):
    cell = harness.resolve(name)
    big = (1 << 31) + 12345
    a = traffic.Plan(cell.config, cell.mix, big)
    b = traffic.Plan(cell.config, cell.mix, big)
    c = traffic.Plan(cell.config, cell.mix, big + 1)
    if cell.config["structure"] == "hashtable":
        assert a.key_base == b.key_base != c.key_base
        assert a.n_pre == round(cell.mix['preload_load'] * a.nranks
                                * cell.config['nslots'])
        if "find" in a.pattern:
            a.pool = b.pool = c.pool = 2
            ia, ib = a.find_pool_indices(), b.find_pool_indices()
            assert np.array_equal(ia, ib)
            assert not np.array_equal(ia, c.find_pool_indices())
            assert ia.min() >= 0 and ia.max() < a.n_pre
    else:
        assert a.queue_base == b.queue_base != c.queue_base
    assert [a.slot(k) for k in range(9)] == [b.slot(k) for k in range(9)]


def test_keys_match_on_host_and_device():
    import jax.numpy as jnp
    idx = np.arange(0, 1 << 16, 7)
    base = (1 << 32) - (1 << 17)
    dev = np.asarray(traffic.key_jnp(jnp.asarray(idx, jnp.int32), base))
    host = traffic.key_np(idx, base)
    assert np.array_equal(dev, host)
    assert len(np.unique(host)) == len(host) and np.all(host != 0)
    assert np.array_equal(np.asarray(traffic.value_jnp(jnp.asarray(host))),
                          traffic.value_np(host))
    seq = np.arange(1000)
    assert np.array_equal(
        np.asarray(traffic.qval_jnp(jnp.asarray(seq, jnp.int32), 99)),
        traffic.qval_np(seq, 99))


def test_scrambled_zipfian_has_hot_keys():
    rng = np.random.default_rng(0)
    idx = traffic.scrambled_zipfian(rng, 1 << 25, 1 << 17)
    _, counts = np.unique(idx, return_counts=True)
    assert idx.min() >= 0 and idx.max() < 1 << 25
    # YCSB's 0.99 zipfian: the hottest key takes a few percent
    assert counts.max() > 0.01 * idx.size
    assert len(counts) < 0.8 * idx.size


def test_peaks_table():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, *BENCH["command"][1:]),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
