"""Compile rehearsal for one TPU v5e: the owner-lane kernels with
interpret=False, and one whole hash-table insert batch on the XLA lane at
the table size `chip_smoke.py` loads. Nothing runs; the TPU compiler
refuses what the chip would refuse (tile alignment, VMEM, HBM).

The topology is described inside a fixture, never at import: only one
process may hold the TPU library, and every test worker imports this
file."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import chip_smoke
from repro.core import hashtable as ht_mod
from repro.core import window
from repro.core.types import Promise
from repro.kernels import amo_apply, hash_probe

V5E_HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    # a TPU compile is written to the persistent cache but cannot be read
    # back without a chip; keep these compiles out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        compilation_cache.reset_cache()


def _spec(shape, sharding, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _fits(compiled):
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, mem
    return mem


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


def test_owner_lane_kernels_compile_for_v5e(one_chip):
    P, L, m = (chip_smoke.KERNEL_P, chip_smoke.KERNEL_WORDS,
               chip_smoke.KERNEL_OPS)
    local = _spec((P, L), one_chip)
    mask = _spec((P, m), one_chip, jnp.bool_)
    _assert_kernel(jax.jit(lambda l, o, k: amo_apply.amo_apply(
        l, o, k, interpret=False)).lower(
            local, _spec((P, m, 4), one_chip), mask).compile())
    V, G = 2, 3
    _assert_kernel(jax.jit(lambda l, o, k: amo_apply.fused_apply(
        l, o, k, reply_width=1 + G, interpret=False)).lower(
            local, _spec((P, m, 6 + V), one_chip), mask).compile())

    nslots, rec_w = chip_smoke.KERNEL_SLOTS, 3
    table = _spec((P, nslots * rec_w), one_chip)
    req = _spec((P, m), one_chip)
    _assert_kernel(jax.jit(lambda t, s, k, v, ok: hash_probe.hash_insert(
        t, s, k, v, ok, nslots=nslots, rec_w=rec_w,
        interpret=False)).lower(
            table, req, req, _spec((P, m, 1), one_chip), mask).compile())
    _assert_kernel(jax.jit(lambda t, s, k, ok: hash_probe.hash_find(
        t, s, k, ok, nslots=nslots, rec_w=rec_w,
        interpret=False)).lower(table, req, req, mask).compile())


def test_insert_batch_compiles_at_smoke_table_size(one_chip):
    """One fused C_RW insert batch on the XLA owner lane against the
    smoke's 1.5 GiB window: the whole probe loop as one program. 128 keys
    per rank keep the compile to seconds; the smoke's 65,536 take about
    half a minute, most of it in the two sorts."""
    P, nslots, vw = chip_smoke.P, chip_smoke.NSLOTS, 1
    n = 128

    def batch(data, keys, vals):
        ht = ht_mod.DHashTable(win=window.Window(data=data), nslots=nslots,
                               val_words=vw)
        ht2, ok, probes = ht_mod.insert_rdma(ht, keys, vals,
                                             promise=Promise.CRW)
        return ht2.win.data, ok, probes

    compiled = jax.jit(batch, donate_argnums=0).lower(
        _spec((P, nslots * (2 + vw)), one_chip), _spec((P, n), one_chip),
        _spec((P, n, vw), one_chip)).compile()
    mem = _fits(compiled)
    # the table is updated in place: one window in, one out, aliased
    assert mem.alias_size_in_bytes >= P * nslots * (2 + vw) * 4
