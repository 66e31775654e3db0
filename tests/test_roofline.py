"""Roofline toolchain: the trip-count-aware HLO analyzer must (a) beat
XLA's body-once cost_analysis on scanned workloads and (b) account every
collective with the ring-model byte formulas."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import hlo_stats


def _cost(compiled) -> dict:
    return compiled.cost_analysis() or {}


def test_xla_cost_analysis_counts_loop_body_once():
    """The documented deficiency that motivates hlo_stats."""
    def f_scan(x):
        def body(c, _):
            return c @ c, None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    def f_once(x):
        return x @ x

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    c_scan = _cost(jax.jit(f_scan).lower(x).compile())
    c_once = _cost(jax.jit(f_once).lower(x).compile())
    assert c_scan.get("flops") == pytest.approx(c_once.get("flops"))


def test_hlo_stats_trip_count_flops():
    def f_scan(x):
        def body(c, _):
            return c @ c, None
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
    compiled = jax.jit(f_scan).lower(x).compile()
    st = hlo_stats.analyze(compiled.as_text(), world=1)
    assert st["flops"] == pytest.approx(2 * 128**3 * 10, rel=0.01)


def test_hlo_stats_nested_scan():
    def f(x):
        def inner(c, _):
            return c @ c, None

        def outer(c, _):
            c, _ = jax.lax.scan(inner, c, None, length=3)
            return c, None

        y, _ = jax.lax.scan(outer, x, None, length=5)
        return y

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    compiled = jax.jit(f).lower(x).compile()
    st = hlo_stats.analyze(compiled.as_text(), world=1)
    assert st["flops"] == pytest.approx(2 * 64**3 * 15, rel=0.01)


def test_hlo_stats_collective_accounting():
    crafted = """
HloModule test

ENTRY %main (p: f32[64,128]) -> f32[64,128] {
  %p = f32[64,128]{1,0} parameter(0)
  %ag = f32[64,128]{1,0} all-gather(%p), replica_groups=[16,16]<=[256], dimensions={0}
  %ar = f32[64,128]{1,0} all-reduce(%ag), replica_groups={{0,1,2,3}}, to_apply=%add
  ROOT %cp = f32[64,128]{1,0} collective-permute(%ar), source_target_pairs={{0,1}}
}
"""
    st = hlo_stats.analyze(crafted, world=256)
    b = 64 * 128 * 4
    coll = st["collectives"]
    assert coll["all-gather"]["bytes"] == pytest.approx(b * 15 / 16)
    assert coll["all-reduce"]["bytes"] == pytest.approx(2 * b * 3 / 4)
    assert coll["collective-permute"]["bytes"] == pytest.approx(b)


def test_hlo_stats_sharded_collectives_end_to_end():
    """all_to_all via shard_map on 1 device degenerates; instead check a
    psum-lowered all-reduce is found and byte-counted."""
    mesh = jax.make_mesh((1,), ("d",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    shard_map = jax.shard_map

    def f(x):
        return shard_map(lambda a: jax.lax.psum(a, "d"),
                         mesh=mesh, in_specs=jax.sharding.PartitionSpec(),
                         out_specs=jax.sharding.PartitionSpec())(x)

    x = jax.ShapeDtypeStruct((32, 32), jnp.float32)
    compiled = jax.jit(f).lower(x).compile()
    st = hlo_stats.analyze(compiled.as_text(), world=1)
    # single-device group -> zero wire bytes, but the op is still visible
    assert st["collective_bytes"] == 0.0


def test_shape_bytes_parser():
    assert hlo_stats.shape_bytes("f32[64,128]{1,0}") == 64 * 128 * 4
    assert hlo_stats.shape_bytes("bf16[8]{0}") == 16
    assert hlo_stats.shape_bytes("(f32[2,2]{1,0}, s32[4]{0})") == 32
    assert hlo_stats.shape_bytes("pred[10]{0}") == 10
    assert hlo_stats.shape_dims("f32[3,5,7]{2,1,0}") == [3, 5, 7]
