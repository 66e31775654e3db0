"""amo_apply — the owner shard's "NIC lane": a serialized batch of atomic
memory operations applied against a local window shard.

Paper mapping (DESIGN.md §2): on Cray Aries the target NIC serializes
incoming AMOs against node memory while the CPU computes. TPUs have no NIC
atomics, so the owner executes the batch itself in deterministic
(src_rank, slot) order. This kernel IS that serialization point; its cost
is the `amo_apply` term in the cost model.

Two kernels share the lane:

- `amo_apply`: primitive single-word AMOs [off|opcode|a|b];
- `fused_apply`: fused component descriptors (DESIGN.md §2)
  [off|opcode|a|b|aux0|aux1|vals...] — CAS_PUT / CAS_PUT_PUB / FAO_GET
  compound ops applied in sub-phase order (atomics, compound puts, publish
  flips, phase-end gathers; each serialized), so a claim + record write +
  publish flip arrives in ONE request phase instead of three.

Grid: one program per owner row (the P axis); within a program a sequential
fori_loop walks the op list — atomics are *inherently* serial at the memory
controller, so the loop order is the semantics, not a perf bug. The local
window lives in VMEM for the whole batch (one HBM read + one write total),
which is the TPU-native win over per-op HBM round trips. The shard is
tiled and each word is reached through its (8, 128) tile (`tiles.py`);
op lists, masks and replies are scalar streams in SMEM.

`interpret=None` interprets only on the CPU backend, so a TPU run always
compiles the kernel.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .scan import inclusive_scan
from .tiles import (from_tiles, read_word, resolve_interpret, smem_spec,
                    tile_spec, to_tiles, write_word)

OP_PUT, OP_GET, OP_CAS, OP_FAA, OP_FOR, OP_FAND, OP_FXOR = range(7)
OP_CAS_PUT, OP_CAS_PUT_PUB, OP_FAO_GET = 7, 8, 9


# ---------------------------------------------------------------------------
# Duplicate-run combining (DESIGN.md §6), owner-lane side: merge maximal
# CONSECUTIVE runs of combinable ops in the serialized list before the
# sequential lane walks it, and reconstruct per-op old values after. Runs
# are consecutive by construction so no reordering happens — the combined
# list applies exactly the state transitions of the original one.
#
#   FAA             operands sum;     old_i = old_rep + prefix_sum
#   FOR/FAND/FXOR   operands fold;    old_i = binop(old_rep, prefix_fold)
#   GET             one probe;        old_i = old_rep
#   PUT             last writer wins; old_i = prev member's stored value
#   CAS             identical (a, b) rows only; losers see the chained
#                   outcome (rep won -> b, else old_rep)
# ---------------------------------------------------------------------------
def _fao_identity(code):
    return jnp.where(code == OP_FAND, jnp.int32(-1), jnp.int32(0))


def _fao_merge(code, x, y):
    return jnp.select(
        [code == OP_FAA, code == OP_FOR, code == OP_FAND, code == OP_FXOR],
        [x + y, x | y, x & y, x ^ y], y)


@jax.jit
def combine_runs(ops, mask):
    """Merge duplicate runs of one shard's serialized op list.

    ops (m, 4) int32 [off|code|a|b]; mask (m,) bool. Returns
    (ops', mask', run_start (m,), prefix (m,)): mask' keeps only run
    representatives, ops' carries the folded operand (FAO) / last value
    (PUT) at each representative row, run_start[i] is the list index of
    op i's representative, prefix[i] the exclusive operand fold of its
    earlier run members."""
    m = ops.shape[0]
    off, code, a, b = ops[:, 0], ops[:, 1], ops[:, 2], ops[:, 3]
    same = (mask[1:] & mask[:-1] & (off[1:] == off[:-1])
            & (code[1:] == code[:-1]))
    is_cas = code == OP_CAS
    same = same & (~is_cas[1:] | ((a[1:] == a[:-1]) & (b[1:] == b[:-1])))
    run_first = jnp.concatenate([jnp.array([True]), ~same])
    idx = jnp.arange(m, dtype=jnp.int32)
    run_start = jax.lax.cummax(jnp.where(run_first, idx, -1))

    def comb(x, y):
        xf, xa, _ = x
        yf, ya, yc = y
        return xf | yf, jnp.where(yf, ya, _fao_merge(yc, xa, ya)), yc

    _, incl, _ = inclusive_scan(comb, (run_first, a, code))
    excl = jnp.where(run_first, _fao_identity(code), jnp.roll(incl, 1))
    run_last = jnp.concatenate([run_first[1:], jnp.array([True])])
    end = jax.lax.cummin(jnp.where(run_last, idx, m - 1), reverse=True)
    is_fao = ((code == OP_FAA) | (code == OP_FOR) | (code == OP_FAND)
              | (code == OP_FXOR))
    a2 = jnp.where(run_first & is_fao, incl[end], a)
    b2 = jnp.where(run_first & (code == OP_PUT), b[end], b)
    ops2 = jnp.stack([off, code, a2, b2], axis=-1)
    mask2 = mask & run_first
    return ops2, mask2, run_start, excl


@jax.jit
def reconstruct_runs(ops, mask, run_start, prefix, old_rep):
    """Per-op old values from the representatives' fetched values.

    old_rep (m,) is the combined apply's reply (meaningful at
    representative rows). Returns old (m,) as the uncombined serialized
    apply would have fetched it."""
    m = ops.shape[0]
    code, a, b = ops[:, 1], ops[:, 2], ops[:, 3]
    idx = jnp.arange(m, dtype=jnp.int32)
    pos = idx - run_start
    old_l = old_rep[run_start]
    prev_b = jnp.roll(b, 1)
    fao = _fao_merge(code, old_l, prefix)
    old = jnp.select(
        [code == OP_GET, code == OP_CAS, code == OP_PUT],
        [old_l,
         jnp.where(pos == 0, old_l, jnp.where(old_l == a, b, old_l)),
         jnp.where(pos == 0, old_l, prev_b)],
        fao)
    return jnp.where(mask, old, 0)


def _new_value(code, cur, a, b):
    """Word value after a primitive op (codes 0-6) or a fused op's atomic
    component (CAS_PUT[_PUB]: CAS; FAO_GET: fetch-and-op of sub-kind b)."""
    def fao(kind):
        return jnp.where(kind == OP_FAA, cur + a,
                         jnp.where(kind == OP_FOR, cur | a,
                                   jnp.where(kind == OP_FAND, cur & a,
                                             jnp.where(kind == OP_FXOR,
                                                       cur ^ a, cur))))
    cas = jnp.where(cur == a, b, cur)
    is_cas = ((code == OP_CAS) | (code == OP_CAS_PUT)
              | (code == OP_CAS_PUT_PUB))
    return jnp.where(code == OP_PUT, b,
                     jnp.where(is_cas, cas,
                               jnp.where(code == OP_FAO_GET, fao(b),
                                         fao(code))))


def _amo_kernel(local_ref, ops_ref, mask_ref, old_ref, out_ref):
    # local_ref/out_ref: (T, 8, 128) VMEM; ops_ref: (1, 4m) SMEM rows
    # [off|opcode|a|b]; mask_ref/old_ref: (1, m) SMEM
    out_ref[...] = local_ref[...]
    m = mask_ref.shape[1]

    def body(j, _):
        off, code = ops_ref[0, 4 * j], ops_ref[0, 4 * j + 1]
        a, b = ops_ref[0, 4 * j + 2], ops_ref[0, 4 * j + 3]
        ok = mask_ref[0, j] != 0
        cur = read_word(out_ref, jnp.where(ok, off, 0))

        @pl.when(ok)
        def _():
            write_word(out_ref, off, _new_value(code, cur, a, b))

        old_ref[0, j] = jnp.where(ok, cur, 0)
        return 0

    jax.lax.fori_loop(0, m, body, 0)


def amo_apply(local: jax.Array, ops: jax.Array, mask: jax.Array,
              *, interpret: Optional[bool] = None):
    """Apply serialized AMO batches to each owner's shard.

    local (P, L) int32; ops (P, m, 4) rows [off|opcode|a|b]; mask (P, m).
    Returns (old (P, m), local' (P, L)).
    """
    return _amo_apply(local, ops, mask,
                      interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def _amo_apply(local, ops, mask, *, interpret):
    P, L = local.shape
    m = ops.shape[1]
    tiles = to_tiles(local)
    T = tiles.shape[1]
    old, new_tiles = pl.pallas_call(
        _amo_kernel,
        grid=(P,),
        in_specs=[tile_spec(T), smem_spec(4 * m), smem_spec(m)],
        out_specs=[smem_spec(m), tile_spec(T)],
        out_shape=[
            jax.ShapeDtypeStruct((P, 1, m), jnp.int32),
            jax.ShapeDtypeStruct(tiles.shape, jnp.int32),
        ],
        interpret=interpret,
    )(tiles, ops.astype(jnp.int32).reshape(P, 1, 4 * m),
      mask.astype(jnp.int32).reshape(P, 1, m))
    return old.reshape(P, m), from_tiles(new_tiles, L)


def _fused_kernel(local_ref, ops_ref, mask_ref, reply_ref, out_ref,
                  *, L, val_words, gather_words):
    # local_ref/out_ref: (T, 8, 128) VMEM; ops_ref: (1, m*(6+V)) SMEM rows
    # [off|opcode|a|b|aux0|aux1|vals...]; mask_ref: (1, m) SMEM;
    # reply_ref: (1, m*(1+G)) SMEM rows [old|gathered...]
    #
    # Sub-phase decomposed semantics (same spec as ref.fused_apply): one
    # serialized pass per sub-phase — atomics, compound puts, publish
    # flips, phase-end gathers.
    out_ref[...] = local_ref[...]
    m = mask_ref.shape[1]
    V, G = val_words, gather_words
    W, RW = 6 + V, 1 + G

    def field(j, k):
        return ops_ref[0, W * j + k]

    def is_csp(code):
        return (code == OP_CAS_PUT) | (code == OP_CAS_PUT_PUB)

    def atomic_body(j, _):
        off, code, a, b = field(j, 0), field(j, 1), field(j, 2), field(j, 3)
        ok = mask_ref[0, j] != 0
        cur = read_word(out_ref, jnp.where(ok, off, 0))

        @pl.when(ok)
        def _():
            write_word(out_ref, off, _new_value(code, cur, a, b))

        reply_ref[0, RW * j] = jnp.where(ok, cur, 0)
        return 0

    jax.lax.fori_loop(0, m, atomic_body, 0)

    def won(j):
        # CAS success, recomputed from the recorded old value
        return (mask_ref[0, j] != 0) & (reply_ref[0, RW * j] == field(j, 2))

    if V > 0:
        def put_body(j, _):
            aux0 = field(j, 4)
            # compound payloads are dropped whole when out of range
            do = (won(j) & is_csp(field(j, 1))
                  & (aux0 >= 0) & (aux0 <= L - V))

            @pl.when(do)
            def _():
                for k in range(V):
                    write_word(out_ref, aux0 + k, field(j, 6 + k))

            return 0

        jax.lax.fori_loop(0, m, put_body, 0)

    def flip_body(j, _):
        off = field(j, 0)

        @pl.when(won(j) & (field(j, 1) == OP_CAS_PUT_PUB))
        def _():
            write_word(out_ref, off, read_word(out_ref, off) ^ field(j, 5))

        return 0

    jax.lax.fori_loop(0, m, flip_body, 0)

    if G > 0:
        def gather_body(j, _):
            aux0 = field(j, 4)
            is_get = ((mask_ref[0, j] != 0) & (field(j, 1) == OP_FAO_GET)
                      & (aux0 >= 0) & (aux0 <= L - G))
            src = jnp.where(is_get, aux0, 0)
            for k in range(G):
                g = read_word(out_ref, src + k)
                reply_ref[0, RW * j + 1 + k] = jnp.where(is_get, g, 0)
            return 0

        jax.lax.fori_loop(0, m, gather_body, 0)


def fused_apply(local: jax.Array, ops: jax.Array, mask: jax.Array,
                *, reply_width: int, interpret: Optional[bool] = None):
    """Apply serialized fused-descriptor batches to each owner's shard.

    local (P, L) int32; ops (P, m, 6 + V) rows
    [off|opcode|a|b|aux0|aux1|vals...]; mask (P, m).
    Returns (reply (P, m, reply_width), local' (P, L)): reply word 0 is the
    old value at `off`, words 1.. are the FAO_GET gather (zeros otherwise).
    Same contract as kernels/ref.py:fused_apply, validated against it.
    """
    return _fused_apply(local, ops, mask, reply_width=reply_width,
                        interpret=resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("reply_width", "interpret"))
def _fused_apply(local, ops, mask, *, reply_width, interpret):
    P, L = local.shape
    m, w = ops.shape[1], ops.shape[2]
    kern = functools.partial(_fused_kernel, L=L, val_words=w - 6,
                             gather_words=reply_width - 1)
    tiles = to_tiles(local)
    T = tiles.shape[1]
    reply, new_tiles = pl.pallas_call(
        kern,
        grid=(P,),
        in_specs=[tile_spec(T), smem_spec(m * w), smem_spec(m)],
        out_specs=[smem_spec(m * reply_width), tile_spec(T)],
        out_shape=[
            jax.ShapeDtypeStruct((P, 1, m * reply_width), jnp.int32),
            jax.ShapeDtypeStruct(tiles.shape, jnp.int32),
        ],
        interpret=interpret,
    )(tiles, ops.astype(jnp.int32).reshape(P, 1, m * w),
      mask.astype(jnp.int32).reshape(P, 1, m))
    return reply.reshape(P, m, reply_width), from_tiles(new_tiles, L)
