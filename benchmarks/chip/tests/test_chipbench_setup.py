"""Set-up is one set of compiled programs for every seed: the seed's
bases are arguments of the jitted set-up calls, never constants in them,
so a new seed is served from the persistent compilation cache."""
import jax
import numpy as np
import pytest

from chipbench_toy import (CELLS, claim_rule_breaks, harness, on_its_chips,
                           toy)

from benchmarks.chip import traffic


def _setup_programs(cell, seed, monkeypatch):
    """The text of every program a driver's set-up jits, in call order."""
    texts = []
    real_jit = jax.jit

    def recording_jit(fn, **kw):
        jitted = real_jit(fn, **kw)

        def call(*args, **kwargs):
            if not any(isinstance(x, jax.core.Tracer)
                       for x in jax.tree.leaves((args, kwargs))):
                texts.append(jitted.lower(*args, **kwargs).as_text())
            return jitted(*args, **kwargs)

        return call

    plan = traffic.Plan(cell.config, cell.mix, seed)
    devices = jax.devices()[:cell.workload["chips"]]
    drv = harness.structure(cell.config["structure"]).Driver(
        cell.config, plan, devices)
    with monkeypatch.context() as m:
        m.setattr(jax, "jit", recording_jit)
        drv.build()
        drv.make_pools()
    drv.free()
    return texts


@pytest.mark.parametrize("name", CELLS)
def test_setup_programs_do_not_depend_on_the_seed(name, monkeypatch,
                                                  request):
    if on_its_chips(request, name):
        return
    cell = toy(name)
    a = _setup_programs(cell, (1 << 31) + 77, monkeypatch)
    b = _setup_programs(cell, 3, monkeypatch)
    assert len(a) >= 2
    assert a == b


def test_preloaded_table_holds_every_acknowledged_key():
    """The benchmark's own pre-load, read back and looked up by the
    reference's probe: every acknowledged key with its value, every
    refused key absent."""
    from benchmarks.chip.reference import hashtable as ref_ht
    cell = toy(CELLS[0])
    cfg = cell.config
    plan = traffic.Plan(cfg, cell.mix, 11)
    drv = harness.structure("hashtable").Driver(cfg, plan)
    ok, _ = drv.build()
    ok = np.asarray(ok)
    table = ref_ht.Table(np.asarray(drv.state.win.data), cfg["nslots"],
                         cfg["val_words"])
    keys = traffic.key_np(np.arange(plan.n_pre), plan.key_base)
    owner, start = ref_ht.place(keys, plan.nranks, cfg["nslots"])
    found, val = table.find(owner, start, keys, cfg["max_probes"])
    assert ok.mean() > 0.99
    assert np.array_equal(found, ok)
    assert np.array_equal(val[ok], traffic.value_np(keys)[ok])
    assert table.ready() == int(ok.sum())
    drv.free()


@pytest.mark.parametrize("chunk", [None, 1 << 9])
def test_preload_keeps_the_claim_rule(chunk, monkeypatch):
    """The pre-load in one chunk and in eight: every placed key READY at
    the slot its probes name with no empty slot before it, every refused
    key's window full; eight chunks at a load that refuses keys."""
    from benchmarks.chip.structures import hashtable as drv_ht
    cell = toy(CELLS[0])
    cfg = dict(cell.config, nslots=1 << 9)
    mix = dict(cell.mix, preload_load=0.9)
    plan = traffic.Plan(cfg, mix, (1 << 31) + 13)
    if chunk is not None:
        monkeypatch.setattr(drv_ht, "PRELOAD_CHUNK", chunk)
        assert plan.n_pre > 4 * chunk
    drv = drv_ht.Driver(cfg, plan)
    ok, probes = drv.build()
    ok = np.asarray(ok)
    assert 0.5 < ok.mean() < 1
    assert claim_rule_breaks(drv.state.win.data, cfg, plan, ok, probes) == 0
    drv.free()
