"""Subprocess helper for test_chipbench_mesh.py: the four-chip hash-table
cell at its toy size on four virtual CPU devices (XLA_FLAGS must be set
before JAX starts, hence a process of its own). Prints one JSON object on
its last line:

    correct        the run through `harness.run_cell` is correct
    same_answers   the first batches' answers, the pre-load's answers and
                   the table after them are bit-equal to one device's
    chunked        the same with the pre-load walked in eight chunks, and
                   the claim rule's breaks in that table (0)
    sharded        the table stayed split by rank rows over the devices
    all_to_all     all-to-alls in the compiled AM insert program
    lost_shard     the check's counts under the `lost_shard` fault
"""
import json
import os
import sys

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                           + os.environ.get("XLA_FLAGS", ""))
os.environ["JAX_PLATFORMS"] = "cpu"

import jax                                            # noqa: E402
import numpy as np                                    # noqa: E402

from chipbench_toy import (claim_rule_breaks, harness, one_chip,  # noqa
                           toy)

from benchmarks.chip import control                   # noqa: E402
from benchmarks.chip.structures import hashtable as drv_ht  # noqa: E402
from repro.core import am, routing                    # noqa: E402
from repro.launch import hlo_stats                    # noqa: E402

NAME = "dht_p32.insert_find_uniform"
SEED = (1 << 31) + 4242
BATCHES = 4                      # insert, find, insert, find

calls = {}
_program = am.AMEngine._program


def recording_program(self, handler, *args):
    """`AMEngine._program`, keeping the last arguments of each program."""
    prog = _program(self, handler, *args)
    if not hasattr(prog.fn, "jitted"):
        jitted = prog.fn

        def fn(*a):
            calls[handler.name] = (jitted, a)
            return jitted(*a)
        fn.jitted = jitted
        prog.fn = fn
    return prog


def drive(cell, devices):
    """A fixed number of batches through the harness's own call."""
    bench = harness.Bench(cell, SEED, devices)
    bench.prepare()
    outs = [bench._one(k, 0, [])[1] for k in range(BATCHES)]
    rec = bench.drv.record(bench.built)
    return bench, outs, rec


def same_run(outs_a, rec_a, outs_b, rec_b) -> bool:
    """Answers, pre-load answers and final table bit-equal."""
    same = all(np.array_equal(x, y) for a, b in zip(outs_a, outs_b)
               for x, y in zip(a, b))
    return same and all(np.array_equal(rec_a[k], rec_b[k])
                        for k in ("preload_ok", "preload_probes", "final"))


def main():
    devices = jax.devices()
    assert len(devices) == 4, devices
    cell = toy(NAME)
    out = harness.run_cell(NAME, SEED, 0.5, False, cell=cell,
                           devices=devices)
    am.AMEngine._program = recording_program
    b4, outs4, rec4 = drive(cell, devices)
    am.AMEngine._program = _program
    data = b4.drv.state.win.data
    sharded = (len(data.addressable_shards) == 4 and all(
        s.data.shape[0] == cell.config["nranks"] // 4
        for s in data.addressable_shards))
    jitted, args = calls["ht_insert"]
    with routing.sharding_hook(b4.drv.hook):
        text = jitted.lower(*args).compile().as_text()
    st = hlo_stats.HloStats(text, world=4).summary()
    a2a = st["collectives"].get("all-to-all", {"count": 0})["count"]
    same = same_run(outs4, rec4, *drive(one_chip(cell), devices[:1])[1:])
    drv_ht.PRELOAD_CHUNK = 1 << 12
    c4, outs4c, rec4c = drive(cell, devices)
    chunks = -(-c4.plan.n_pre // drv_ht.PRELOAD_CHUNK)
    chunked = chunks >= 2 and same_run(
        outs4c, rec4c, *drive(one_chip(cell), devices[:1])[1:])
    breaks = claim_rule_breaks(rec4c["final"], cell.config, c4.plan,
                               rec4c["preload_ok"], rec4c["preload_probes"])
    rows = control.readings(NAME, [SEED + 1], 0.3, ["lost_shard"],
                            cell=cell, devices=devices, emit=lambda s: None)
    print(json.dumps({
        "correct": out["correct"], "checks": out["checks"],
        "same_answers": bool(same), "sharded": bool(sharded),
        "chunked": bool(chunked), "chunks": chunks, "claim_breaks": breaks,
        "all_to_all": a2a,
        "sound": rows[0]["checks"],
        "lost_shard": rows[1]["checks"] if len(rows) > 1 else None}))


if __name__ == "__main__":
    main()
