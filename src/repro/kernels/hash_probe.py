"""hash_probe — the RPC hash-table handler's hot path as a Pallas kernel.

Two kernels:

- `hash_find`: the probe loop over a block of `block_m` requests; the
  local table stays resident in VMEM across the owner's whole request
  batch (one HBM read) — exactly the "expressive control flow at the
  target, zero extra network phases" property the paper attributes to RPC
  handlers.
- `hash_insert`: sequential over requests within an owner (insert-or-assign
  must observe earlier inserts in the same batch — same serialization
  argument as amo_apply).

Each probe reads the record's flag and key words through their (8, 128)
tiles (`tiles.py`); requests, replies and values are SMEM scalar streams.

Layout: table (P, L) int32, nslots records of rec_w = 2 + vw words
[flag | key | val...] per rank; flag low byte 0=EMPTY, 2=READY.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tiles import (from_tiles, read_word, resolve_interpret, smem_spec,
                    tile_spec, to_tiles, write_word)


def _probe(table_ref, start, key, *, nslots, rec_w, max_probes,
           want_empty):
    """Walk one key's probe window. Returns (slot, kind, probes): kind 0
    searching, 1 key found at slot, 2 empty slot (when `want_empty`;
    otherwise an empty slot ends the search with kind 3)."""
    def body(p, carry):
        slot, kind, probes = carry
        s = (start + p) % nslots
        state = read_word(table_ref, s * rec_w) & 255
        rkey = read_word(table_ref, s * rec_w + 1)
        searching = kind == 0
        hit = searching & (state == 2) & (rkey == key)
        empty = searching & (state == 0)
        slot = jnp.where(hit | (empty & want_empty), s, slot)
        kind = jnp.where(hit, 1, jnp.where(empty, 2 if want_empty else 3,
                                           kind))
        return slot, kind, probes + searching.astype(jnp.int32)

    return jax.lax.fori_loop(0, max_probes, body,
                             (jnp.int32(-1), jnp.int32(0), jnp.int32(0)))


def _find_kernel(table_ref, starts_ref, keys_ref, mask_ref,
                 found_ref, vals_ref, *, nslots, rec_w, max_probes):
    # table (T, 8, 128) VMEM; starts/keys/mask/found (1, bm) SMEM;
    # vals (1, bm * vw) SMEM
    bm = starts_ref.shape[1]
    vw = rec_w - 2

    def body(j, _):
        slot, kind, _ = _probe(table_ref, starts_ref[0, j], keys_ref[0, j],
                               nslots=nslots, rec_w=rec_w,
                               max_probes=max_probes, want_empty=False)
        hit = (kind == 1) & (mask_ref[0, j] != 0)
        found_ref[0, j] = hit.astype(jnp.int32)
        base = jnp.where(hit, slot * rec_w, 0)
        for k in range(vw):
            v = read_word(table_ref, base + 2 + k)
            vals_ref[0, j * vw + k] = jnp.where(hit, v, 0)
        return 0

    jax.lax.fori_loop(0, bm, body, 0)


def hash_find(table: jax.Array, starts: jax.Array, keys: jax.Array,
              mask: jax.Array, *, nslots: int, rec_w: int,
              max_probes: int = 8, block_m: int = 128,
              interpret: Optional[bool] = None):
    """Batched find. table (P, L); starts/keys/mask (P, m).
    Returns (found (P, m) bool, vals (P, m, rec_w-2)). On a TPU `block_m`
    must be a multiple of 128 unless it covers the whole batch."""
    return _hash_find(table, starts, keys, mask, nslots=nslots, rec_w=rec_w,
                      max_probes=max_probes, block_m=block_m,
                      interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("nslots", "rec_w", "max_probes",
                                             "block_m", "interpret"))
def _hash_find(table, starts, keys, mask, *, nslots, rec_w, max_probes,
               block_m, interpret):
    P, m = starts.shape
    bm = min(block_m, m)
    grid_m = pl.cdiv(m, bm)
    mp = grid_m * bm
    vw = rec_w - 2
    tiles = to_tiles(table)
    kern = functools.partial(_find_kernel, nslots=nslots, rec_w=rec_w,
                             max_probes=max_probes)
    found, vals = pl.pallas_call(
        kern,
        grid=(P, grid_m),
        in_specs=[tile_spec(tiles.shape[1])]
        + [smem_spec(bm, blocked=True)] * 3,
        out_specs=[smem_spec(bm, blocked=True),
                   smem_spec(bm * vw, blocked=True)],
        out_shape=[
            jax.ShapeDtypeStruct((P, 1, mp), jnp.int32),
            jax.ShapeDtypeStruct((P, 1, mp * vw), jnp.int32),
        ],
        interpret=interpret,
    )(tiles, *(_stream(x, mp) for x in (starts, keys, mask)))
    return (found.reshape(P, mp)[:, :m] != 0,
            vals.reshape(P, mp, vw)[:, :m])


def _stream(x: jax.Array, to: int) -> jax.Array:
    """(P, m) -> (P, 1, to) int32 SMEM stream, zero-padded."""
    x = x.astype(jnp.int32)
    if x.shape[1] != to:
        x = jnp.pad(x, ((0, 0), (0, to - x.shape[1])))
    return x[:, None, :]


def _insert_kernel(table_ref, starts_ref, keys_ref, vals_ref, mask_ref,
                   ok_ref, probes_ref, out_ref, *, nslots, rec_w,
                   max_probes):
    # sequential insert-or-assign over the owner's request list
    out_ref[...] = table_ref[...]
    m = starts_ref.shape[1]
    vw = rec_w - 2

    def body(j, _):
        key = keys_ref[0, j]
        ok = mask_ref[0, j] != 0
        slot, kind, probes = _probe(out_ref, starts_ref[0, j], key,
                                    nslots=nslots, rec_w=rec_w,
                                    max_probes=max_probes, want_empty=True)
        can = ok & (kind > 0)

        @pl.when(can)
        def _():
            base = slot * rec_w
            write_word(out_ref, base, jnp.int32(2))
            write_word(out_ref, base + 1, key)
            for k in range(vw):
                write_word(out_ref, base + 2 + k, vals_ref[0, j * vw + k])

        ok_ref[0, j] = can.astype(jnp.int32)
        probes_ref[0, j] = jnp.where(ok, probes, 0)
        return 0

    jax.lax.fori_loop(0, m, body, 0)


def hash_insert(table: jax.Array, starts: jax.Array, keys: jax.Array,
                vals: jax.Array, mask: jax.Array, *, nslots: int,
                rec_w: int, max_probes: int = 8,
                interpret: Optional[bool] = None):
    """Serialized batched insert-or-assign. vals (P, m, rec_w-2).
    Returns (ok (P, m) bool, probes (P, m) int32, table')."""
    return _hash_insert(table, starts, keys, vals, mask, nslots=nslots,
                        rec_w=rec_w, max_probes=max_probes,
                        interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("nslots", "rec_w", "max_probes",
                                             "interpret"))
def _hash_insert(table, starts, keys, vals, mask, *, nslots, rec_w,
                 max_probes, interpret):
    P, L = table.shape
    m = starts.shape[1]
    vw = rec_w - 2
    tiles = to_tiles(table)
    T = tiles.shape[1]
    kern = functools.partial(_insert_kernel, nslots=nslots, rec_w=rec_w,
                             max_probes=max_probes)
    ok, probes, new_tiles = pl.pallas_call(
        kern,
        grid=(P,),
        in_specs=[tile_spec(T), smem_spec(m), smem_spec(m),
                  smem_spec(m * vw), smem_spec(m)],
        out_specs=[smem_spec(m), smem_spec(m), tile_spec(T)],
        out_shape=[
            jax.ShapeDtypeStruct((P, 1, m), jnp.int32),
            jax.ShapeDtypeStruct((P, 1, m), jnp.int32),
            jax.ShapeDtypeStruct(tiles.shape, jnp.int32),
        ],
        interpret=interpret,
    )(tiles, _stream(starts, m), _stream(keys, m),
      vals.astype(jnp.int32).reshape(P, 1, m * vw), _stream(mask, m))
    return (ok.reshape(P, m) != 0, probes.reshape(P, m),
            from_tiles(new_tiles, L))
