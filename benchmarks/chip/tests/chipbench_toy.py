"""Shared by the benchmark's CPU tests: the repository on sys.path, each
cell cut to a size the CPU runs in seconds (same code paths, same
traffic shape, smaller tables and batches), and a test of a cell on
several chips run again on as many virtual CPU devices."""
import os
import re
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmarks.chip import harness  # noqa: E402

CELLS = [w["name"] for w in harness.load_benchmark()["workloads"]]


def toy(name: str) -> harness.Cell:
    cell = harness.resolve(name)
    cfg, mix = dict(cell.config), dict(cell.mix)
    if cfg["structure"] == "hashtable":
        cfg["nslots"] = 1 << 12
        mix.update(batch_per_rank=64, pool_batches=4)
    else:
        cfg["capacity"] = 1 << 12
        mix.update(batch_per_rank=64, prefill=1 << 11, pool_batches=4)
    cell.config, cell.mix = cfg, mix
    return cell


def one_chip(cell: harness.Cell) -> harness.Cell:
    """The same cell asked to run on one chip."""
    import dataclasses
    return dataclasses.replace(cell, workload=dict(cell.workload, chips=1))


def claim_rule_breaks(data, config: dict, plan, ok, probes) -> int:
    """Pre-load keys that break the claim rule in the table `data`: a
    placed key not READY with its key and value at the slot its probes
    name, or with an empty slot before it in its window; a refused key
    that did not probe its whole window, or whose window is not full."""
    import numpy as np
    from benchmarks.chip import traffic
    from benchmarks.chip.reference import hashtable as ref
    ns, mp = int(config["nslots"]), int(config["max_probes"])
    table = ref.Table(np.asarray(data), ns, int(config["val_words"]))
    keys = traffic.key_np(np.arange(plan.n_pre), plan.key_base)
    owner, start = ref.place(keys, plan.nranks, ns)
    ok, probes = np.asarray(ok), np.asarray(probes)
    at = start + np.clip(probes, 1, mp) - 1
    bad = ok & ~table.holds(owner, at, keys,
                            traffic.value_np(keys)[:, None])
    for j in range(mp - 1):
        flag = table.record(owner, start + j)[:, 0] & ref.STATE_MASK
        bad |= ok & (j < probes - 1) & (flag == 0)
    bad |= ~ok & ((probes != mp)
                  | ~table.window_full_of_others(owner, start, keys, mp))
    return int(bad.sum())


def on_its_chips(request, name: str) -> bool:
    """Where the cell asks for more devices than this process has, runs
    the calling test again in a process of its own on that many virtual
    CPU devices (their count is fixed before JAX starts) and requires it
    to pass; returns True then, and the caller returns. Returns False
    where this process has the devices, and the caller goes on."""
    import jax
    chips = int(harness.resolve(name).workload["chips"])
    if len(jax.devices()) >= chips:
        return False
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        f"--xla_force_host_platform_device_count={chips} "
        + os.environ.get("XLA_FLAGS", "")))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT, env.get("PYTHONPATH", "")])
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", "-p", "no:xdist", request.node.nodeid],
        cwd=str(request.config.rootpath), env=env, capture_output=True,
        text=True, timeout=600)
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-2000:]
    assert re.search(r"\b1 passed\b", p.stdout), p.stdout[-2000:]
    return True
