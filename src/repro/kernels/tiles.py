"""Word-addressed access to a VMEM-resident shard for the owner-lane
kernels (`amo_apply.py`, `hash_probe.py`).

The TPU's vector memory is addressed in (8, 128) int32 tiles: Mosaic
accepts a dynamic index only on a leading (untiled) axis, never a dynamic
lane offset. So a shard of L words is laid out as (T, 8, 128) tiles,
word w living at tile w // 1024, sublane (w // 128) % 8, lane w % 128.
A word is read by loading its tile and reducing it under a one-hot
mask, and written by storing the tile back with the word selected in.
Every address stays a scalar, so control flow that depends on a read
value carries the value as a scalar too.

Op lists, masks and per-op outputs are scalar streams: they live in
SMEM as (P, 1, n) arrays, whose last two block dims equal the array's
and so satisfy the tiling rule for any n.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE_WORDS = 8 * 128


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Interpret a kernel only where there is no TPU to compile it for."""
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret


def to_tiles(x: jax.Array) -> jax.Array:
    """(P, L) words -> (P, T, 8, 128) tiles, zero-padded to whole tiles."""
    P, L = x.shape
    T = pl.cdiv(L, TILE_WORDS)
    if T * TILE_WORDS != L:
        x = jnp.pad(x, ((0, 0), (0, T * TILE_WORDS - L)))
    return x.reshape(P, T, 8, 128)


def from_tiles(t: jax.Array, L: int) -> jax.Array:
    return t.reshape(t.shape[0], -1)[:, :L]


def tile_spec(T: int) -> pl.BlockSpec:
    """One owner's whole shard, resident across the grid's inner axes."""
    return pl.BlockSpec((None, T, 8, 128),
                        lambda i, *_: (i, 0, 0, 0))


def smem_spec(n: int, blocked: bool = False) -> pl.BlockSpec:
    """One owner's scalar stream of n words (or its j-th block of n)."""
    if blocked:
        return pl.BlockSpec((None, 1, n), lambda i, j: (i, 0, j),
                            memory_space=pltpu.SMEM)
    return pl.BlockSpec((None, 1, n), lambda i, *_: (i, 0, 0),
                        memory_space=pltpu.SMEM)


def _locate(ref, w):
    t = w // TILE_WORDS
    tile = ref[t]
    sub = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)
    hit = (sub == (w // 128) % 8) & (lane == w % 128)
    return t, tile, hit


def read_word(ref, w) -> jax.Array:
    """Scalar value of word `w` of a (T, 8, 128) shard ref."""
    _, tile, hit = _locate(ref, w)
    return jnp.sum(jnp.where(hit, tile, 0))


def write_word(ref, w, v) -> None:
    """Store scalar `v` at word `w` of a (T, 8, 128) shard ref."""
    t, tile, hit = _locate(ref, w)
    ref[t] = jnp.where(hit, v, tile)
