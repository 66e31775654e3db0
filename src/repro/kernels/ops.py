"""ops — the jit'd public entry points for the kernel layer.

Every op dispatches between the Pallas kernel (compiled on a TPU,
interpreted on the CPU backend) and the pure-jnp reference (`ref.py`),
controlled by `use_pallas`. The model/launch layers call these; the dry-run compiles
the jnp path (Pallas does not lower on the CPU backend), which is
numerically identical — the kernels are the *performance* realization.
"""
from __future__ import annotations

import os
from typing import Tuple

import jax
import jax.numpy as jnp

from . import amo_apply as _amo
from . import flash_attention as _fa
from . import flash_decode as _fd
from . import hash_probe as _hp
from . import moe_dispatch as _md
from . import ref
from . import rg_lru as _rg

Array = jax.Array

# The Pallas lane only when explicitly requested (ROADMAP S6/D3 decide it).
_USE_PALLAS = os.environ.get("REPRO_USE_PALLAS", "0") == "1"


def use_pallas_default() -> bool:
    return _USE_PALLAS


def _pick(flag):
    return _USE_PALLAS if flag is None else flag


def amo_apply(local: Array, ops: Array, mask: Array,
              use_pallas: bool | None = None,
              combine_runs: bool = False) -> Tuple[Array, Array]:
    """combine_runs=True merges consecutive duplicate runs in each owner's
    serialized op list before the lane walks it (operand folds / last
    writer / identical-row CAS — kernels/amo_apply.combine_runs) and
    reconstructs per-op old values after — bit-identical output, shorter
    effective serial chain at the owner (DESIGN.md §6)."""
    if combine_runs:
        ops2, mask2, run_start, prefix = jax.vmap(_amo.combine_runs)(ops,
                                                                     mask)
        old_rep, local2 = amo_apply(local, ops2, mask2,
                                    use_pallas=use_pallas)
        old = jax.vmap(_amo.reconstruct_runs)(ops, mask, run_start,
                                              prefix, old_rep)
        return old, local2
    if _pick(use_pallas):
        return _amo.amo_apply(local, ops, mask)
    return jax.vmap(ref.amo_apply)(local, ops, mask)


def fused_apply(local: Array, ops: Array, mask: Array, *, reply_width: int,
                use_pallas: bool | None = None) -> Tuple[Array, Array]:
    """Owner-lane apply for fused component descriptors (DESIGN.md §2).
    local (P, L); ops (P, m, 6+V); mask (P, m). Returns
    (reply (P, m, reply_width), local'). The XLA lane is the sequential
    oracle vmapped over owners; the Pallas lane is the VMEM-resident hot
    path — bit-identical by contract (tests/test_kernels.py)."""
    if _pick(use_pallas):
        return _amo.fused_apply(local, ops, mask, reply_width=reply_width)
    return jax.vmap(
        lambda l, o, m: ref.fused_apply(l, o, m, reply_width=reply_width)
    )(local, ops, mask)


def txn_group_apply(local: Array, ops: Array, mask: Array, *, ngroups: int,
                    use_pallas: bool | None = None) -> Tuple[Array, Array]:
    """Transactional owner lane with the group abort mask (DESIGN.md §11).
    local (P, L); ops (P, m, 6) rows [off|code|a|b|gid|chain]; mask (P, m).
    Returns (reply (P, m, 2) [old, applied], local'). jnp lane only for
    now — the commit batch is a short serialized chain per owner, so the
    Pallas realization (a fused_apply-style VMEM walk with an extra
    group-abort lane) is deferred until the txn path shows up hot."""
    del use_pallas  # accepted for signature parity with the other ops
    return jax.vmap(
        lambda l, o, m: ref.txn_group_apply(l, o, m, ngroups=ngroups)
    )(local, ops, mask)


def hash_find(table, starts, keys, mask, *, nslots, rec_w, max_probes=8,
              use_pallas: bool | None = None):
    if _pick(use_pallas):
        return _hp.hash_find(table, starts, keys, mask, nslots=nslots,
                             rec_w=rec_w, max_probes=max_probes)
    return jax.vmap(lambda t, s, k, m: ref.hash_find(
        t, s, k, m, nslots, rec_w, max_probes))(table, starts, keys, mask)


def hash_insert(table, starts, keys, vals, mask, *, nslots, rec_w,
                max_probes=8, use_pallas: bool | None = None):
    """Returns (ok (P, m), probes (P, m), table') — probes is the number of
    slots the handler examined, comparable with the RDMA CAS-probe count."""
    if _pick(use_pallas):
        return _hp.hash_insert(table, starts, keys, vals, mask,
                               nslots=nslots, rec_w=rec_w,
                               max_probes=max_probes)
    return jax.vmap(lambda t, s, k, v, m: ref.hash_insert(
        t, s, k, v, m, nslots, rec_w, max_probes))(table, starts, keys,
                                                   vals, mask)


def flash_attention(q, k, v, *, causal=True, window=0,
                    use_pallas: bool | None = None,
                    block_q: int = 128, block_k: int = 128):
    if _pick(use_pallas):
        return _fa.flash_attention(q, k, v, causal=causal, window=window,
                                   block_q=block_q, block_k=block_k)
    return ref.mha(q, k, v, causal=causal, window=window)


def flash_decode(q, k, v, length, *, use_pallas: bool | None = None,
                 block_k: int = 256):
    if _pick(use_pallas):
        return _fd.flash_decode(q, k, v, length, block_k=block_k)
    return ref.decode_attention(q, k, v, length)


combine_decode_stats = ref.combine_decode_stats


def moe_dispatch(expert_ids, *, n_experts, use_pallas: bool | None = None,
                 block_t: int = 256):
    if _pick(use_pallas):
        return _md.moe_dispatch(expert_ids, n_experts=n_experts,
                                block_t=block_t)
    return ref.moe_dispatch(expert_ids, n_experts)


def rg_lru_scan(a, b, h0=None, *, use_pallas: bool | None = None,
                block_s: int = 256, block_d: int = 128):
    if _pick(use_pallas):
        return _rg.rg_lru_scan(a, b, h0, block_s=block_s, block_d=block_d)
    return ref.rg_lru_scan(a, b, h0)
