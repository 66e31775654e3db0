"""Per-kernel validation: Pallas (interpreted on the CPU) vs the pure-jnp
oracles in kernels/ref.py, swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.amo_apply import amo_apply
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_decode import flash_decode
from repro.kernels.hash_probe import hash_find, hash_insert
from repro.kernels.moe_dispatch import moe_dispatch
from repro.kernels.rg_lru import rg_lru_scan

RNG = np.random.default_rng(42)


@pytest.mark.parametrize("P,L,m", [(1, 32, 8), (3, 64, 20), (2, 128, 50)])
def test_amo_apply_sweep(P, L, m):
    local = jnp.asarray(RNG.integers(0, 100, (P, L)), jnp.int32)
    ops = np.zeros((P, m, 4), np.int32)
    ops[..., 0] = RNG.integers(0, L, (P, m))
    ops[..., 1] = RNG.integers(0, 7, (P, m))
    ops[..., 2] = RNG.integers(-5, 5, (P, m))
    ops[..., 3] = RNG.integers(-5, 5, (P, m))
    mask = jnp.asarray(RNG.random((P, m)) > 0.25)
    old_k, new_k = amo_apply(local, jnp.asarray(ops), mask)
    old_r, new_r = jax.vmap(ref.amo_apply)(local, jnp.asarray(ops), mask)
    np.testing.assert_array_equal(np.asarray(old_k), np.asarray(old_r))
    np.testing.assert_array_equal(np.asarray(new_k), np.asarray(new_r))


@pytest.mark.parametrize("P,L,m,V,G", [(1, 32, 8, 2, 3), (3, 64, 20, 1, 0),
                                       (2, 128, 50, 3, 4)])
def test_fused_apply_sweep(P, L, m, V, G):
    """Heterogeneous descriptor batches (primitive 0-6 + fused 7-9 opcodes,
    including out-of-range compound offsets) — Pallas vs the sequential
    oracle."""
    from repro.kernels.amo_apply import fused_apply
    local = jnp.asarray(RNG.integers(0, 100, (P, L)), jnp.int32)
    ops = np.zeros((P, m, 6 + V), np.int32)
    ops[..., 0] = RNG.integers(0, L, (P, m))
    ops[..., 1] = RNG.integers(0, 10, (P, m))
    ops[..., 2] = RNG.integers(-5, 5, (P, m))
    ops[..., 3] = RNG.integers(0, 10, (P, m))
    ops[..., 4] = RNG.integers(-2, L + 2, (P, m))
    ops[..., 5] = RNG.integers(-5, 5, (P, m))
    ops[..., 6:] = RNG.integers(0, 100, (P, m, V))
    mask = jnp.asarray(RNG.random((P, m)) > 0.25)
    rep_k, new_k = fused_apply(local, jnp.asarray(ops), mask,
                               reply_width=1 + G)
    rep_r, new_r = jax.vmap(lambda l, o, mm: ref.fused_apply(
        l, o, mm, reply_width=1 + G))(local, jnp.asarray(ops), mask)
    np.testing.assert_array_equal(np.asarray(rep_k), np.asarray(rep_r))
    np.testing.assert_array_equal(np.asarray(new_k), np.asarray(new_r))


@pytest.mark.parametrize("P,nslots,vw,m,bm",
                         [(2, 16, 1, 10, 4), (1, 64, 3, 33, 16),
                          (3, 32, 2, 17, 128)])
def test_hash_probe_sweep(P, nslots, vw, m, bm):
    rec_w = 2 + vw
    table = jnp.zeros((P, nslots * rec_w), jnp.int32)
    starts = jnp.asarray(RNG.integers(0, nslots, (P, m)), jnp.int32)
    keys = jnp.asarray(RNG.integers(1, 60, (P, m)), jnp.int32)
    vals = jnp.asarray(RNG.integers(0, 100, (P, m, vw)), jnp.int32)
    mask = jnp.asarray(RNG.random((P, m)) > 0.1)
    ok_k, pr_k, tab_k = hash_insert(table, starts, keys, vals, mask,
                                    nslots=nslots, rec_w=rec_w, max_probes=8)
    ok_r, pr_r, tab_r = jax.vmap(lambda t, s, k, v, mm: ref.hash_insert(
        t, s, k, v, mm, nslots, rec_w, 8))(table, starts, keys, vals, mask)
    np.testing.assert_array_equal(np.asarray(ok_k), np.asarray(ok_r))
    np.testing.assert_array_equal(np.asarray(pr_k), np.asarray(pr_r))
    np.testing.assert_array_equal(np.asarray(tab_k), np.asarray(tab_r))
    f_k, v_k = hash_find(tab_k, starts, keys, mask, nslots=nslots,
                         rec_w=rec_w, max_probes=8, block_m=bm)
    f_r, v_r = jax.vmap(lambda t, s, k, mm: ref.hash_find(
        t, s, k, mm, nslots, rec_w, 8))(tab_r, starts, keys, mask)
    np.testing.assert_array_equal(np.asarray(f_k), np.asarray(f_r))
    np.testing.assert_array_equal(np.asarray(v_k), np.asarray(v_r))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,Hkv,S,d,causal,window",
                         [(2, 4, 2, 64, 32, True, 0),
                          (1, 8, 8, 48, 16, True, 24),
                          (2, 2, 1, 32, 64, False, 0)])
def test_flash_attention_sweep(B, H, Hkv, S, d, causal, window, dtype):
    q = jnp.asarray(RNG.normal(size=(B, H, S, d)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, Hkv, S, d)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, Hkv, S, d)), dtype)
    o_k = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=16, block_k=16)
    o_r = ref.mha(q, k, v, causal=causal, window=window)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o_k, np.float32),
                               np.asarray(o_r, np.float32), atol=tol)


@pytest.mark.parametrize("B,H,Hkv,S,d,bk",
                         [(2, 8, 2, 128, 32, 32), (1, 4, 4, 96, 64, 256),
                          (3, 2, 1, 64, 16, 16)])
def test_flash_decode_sweep(B, H, Hkv, S, d, bk):
    q = jnp.asarray(RNG.normal(size=(B, H, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, Hkv, S, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, Hkv, S, d)), jnp.float32)
    length = jnp.asarray(RNG.integers(1, S + 1, (B,)), jnp.int32)
    o_k, m_k, l_k = flash_decode(q, k, v, length, block_k=bk)
    o_r, m_r, l_r = ref.decode_attention(q, k, v, length)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r), atol=2e-5)
    np.testing.assert_allclose(np.asarray(m_k), np.asarray(m_r), atol=1e-6)
    np.testing.assert_allclose(np.asarray(l_k), np.asarray(l_r), rtol=1e-5)


def test_flash_decode_shard_combine():
    """Sharded partials combine to the exact full result (the RPC-style
    distributed decode invariant)."""
    B, H, Hkv, S, d = 2, 4, 2, 128, 32
    q = jnp.asarray(RNG.normal(size=(B, H, d)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, Hkv, S, d)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, Hkv, S, d)), jnp.float32)
    length = jnp.asarray([100, 64], jnp.int32)
    o, m, l = ref.decode_attention(q, k, v, length)
    full = o / jnp.maximum(l, 1e-30)[..., None]
    shards = 4
    parts = []
    for i in range(shards):
        lo, hi = i * S // shards, (i + 1) * S // shards
        ln = jnp.clip(length - lo, 0, hi - lo)
        parts.append(ref.decode_attention(q, k[:, :, lo:hi], v[:, :, lo:hi],
                                          ln))
    comb = ref.combine_decode_stats(
        jnp.stack([p[0] for p in parts]), jnp.stack([p[1] for p in parts]),
        jnp.stack([p[2] for p in parts]))
    np.testing.assert_allclose(np.asarray(comb), np.asarray(full),
                               atol=2e-6)


@pytest.mark.parametrize("T,E,bt", [(100, 4, 32), (1000, 7, 128),
                                    (256, 64, 256), (64, 2, 64)])
def test_moe_dispatch_sweep(T, E, bt):
    ids = jnp.asarray(RNG.integers(0, E, (T,)), jnp.int32)
    c_k, p_k = moe_dispatch(ids, n_experts=E, block_t=bt)
    c_r, p_r = ref.moe_dispatch(ids, E)
    np.testing.assert_array_equal(np.asarray(c_k), np.asarray(c_r))
    np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_r))


@pytest.mark.parametrize("B,S,D,bs,bd",
                         [(2, 100, 200, 32, 64), (1, 64, 128, 256, 128),
                          (3, 33, 50, 8, 16)])
def test_rg_lru_sweep(B, S, D, bs, bd):
    a = jnp.asarray(RNG.uniform(0.7, 1.0, (B, S, D)), jnp.float32)
    b = jnp.asarray(RNG.normal(size=(B, S, D)), jnp.float32)
    h0 = jnp.asarray(RNG.normal(size=(B, D)), jnp.float32)
    h_k = rg_lru_scan(a, b, h0, block_s=bs, block_d=bd)
    h_r = ref.rg_lru_scan(a, b, h0)
    np.testing.assert_allclose(np.asarray(h_k), np.asarray(h_r), atol=1e-5)


def test_kernel_lane_integration():
    """REPRO_USE_PALLAS routes the window AMO lane through the kernel and
    produces identical results to the XLA appliers."""
    import repro.core.window as window
    from repro.core.types import AmoKind
    from repro.kernels import ops as kops
    P = 3
    win_a = window.make_window(P, 16)
    win_b = window.make_window(P, 16)
    dst = jnp.asarray(RNG.integers(0, P, (P, 6)), jnp.int32)
    off = jnp.asarray(RNG.integers(0, 16, (P, 6)), jnp.int32)
    operand = jnp.asarray(RNG.integers(1, 5, (P, 6)), jnp.int32)
    old_a, win_a = window.rdma_fao(win_a, dst, off, operand, AmoKind.FAA)
    prev = kops._USE_PALLAS
    kops._USE_PALLAS = True
    try:
        old_b, win_b = window.rdma_fao(win_b, dst, off, operand,
                                       AmoKind.FAA)
    finally:
        kops._USE_PALLAS = prev
    np.testing.assert_array_equal(np.asarray(old_a), np.asarray(old_b))
    np.testing.assert_array_equal(np.asarray(win_a.data),
                                  np.asarray(win_b.data))


def test_fused_lane_integration():
    """The fused insert/find path produces identical tables and results on
    the XLA and Pallas owner lanes (REPRO_USE_PALLAS toggle)."""
    from repro.core import hashtable as ht_mod
    from repro.core.types import Promise
    from repro.kernels import ops as kops
    P = 3
    keys = jnp.asarray(RNG.permutation(2000)[:P * 6].reshape(P, 6) + 1,
                       jnp.int32)
    vals = jnp.stack([keys * 2, keys + 3], axis=-1)

    def run():
        ht = ht_mod.make_hashtable(P, 16, 2)
        ht, ok, pr = ht_mod.insert_rdma(ht, keys, vals, promise=Promise.CRW,
                                        fused=True)
        ht, f, v = ht_mod.find_rdma(ht, keys, promise=Promise.CRW,
                                    fused=True)
        return ht.win.data, ok, pr, f, v

    a = run()
    prev = kops._USE_PALLAS
    kops._USE_PALLAS = True
    try:
        b = run()
    finally:
        kops._USE_PALLAS = prev
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# Duplicate-run combining (DESIGN.md §6) at the owner lane
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("P,L,m,span", [(2, 32, 16, 2), (3, 64, 40, 4),
                                        (1, 16, 8, 1)])
def test_amo_combine_runs_bit_exact_all_lanes(P, L, m, span):
    """ops.amo_apply(combine_runs=True) == the plain serialized apply on
    duplicate-heavy op lists (offsets drawn from a tiny span so runs are
    long), across the ref, XLA, and Pallas lanes."""
    from repro.kernels import ops as kops
    local = jnp.asarray(RNG.integers(0, 100, (P, L)), jnp.int32)
    ops = np.zeros((P, m, 4), np.int32)
    ops[..., 0] = RNG.integers(0, span, (P, m))
    ops[..., 1] = RNG.integers(0, 7, (P, m))
    ops[..., 2] = RNG.integers(-5, 6, (P, m))
    ops[..., 3] = RNG.integers(-5, 6, (P, m))
    mask = jnp.asarray(RNG.random((P, m)) > 0.15)
    ops = jnp.asarray(ops)
    old_ref, loc_ref = kops.amo_apply(local, ops, mask, use_pallas=False)
    for use_pallas in (False, True):
        old_c, loc_c = kops.amo_apply(local, ops, mask,
                                      use_pallas=use_pallas,
                                      combine_runs=True)
        np.testing.assert_array_equal(np.asarray(old_ref),
                                      np.asarray(old_c))
        np.testing.assert_array_equal(np.asarray(loc_ref),
                                      np.asarray(loc_c))
    # the sequential-oracle composition agrees too
    for p in range(P):
        old_s, loc_s = ref.amo_apply_combined(local[p], ops[p], mask[p])
        np.testing.assert_array_equal(np.asarray(old_ref[p]),
                                      np.asarray(old_s))
        np.testing.assert_array_equal(np.asarray(loc_ref[p]),
                                      np.asarray(loc_s))


def test_combine_runs_actually_shortens_hot_lists():
    """Structure check: a single-variable FAA hammer combines to ONE
    surviving op per shard with the summed operand."""
    from repro.kernels.amo_apply import combine_runs
    m = 24
    ops = np.zeros((m, 4), np.int32)
    ops[:, 1] = 3  # OP_FAA
    ops[:, 2] = np.arange(1, m + 1)
    mask = jnp.ones((m,), bool)
    ops2, mask2, run_start, prefix = combine_runs(jnp.asarray(ops), mask)
    assert int(mask2.sum()) == 1
    assert int(ops2[0, 2]) == m * (m + 1) // 2
    np.testing.assert_array_equal(np.asarray(run_start), np.zeros(m))
    np.testing.assert_array_equal(np.asarray(prefix),
                                  np.arange(m) * (np.arange(m) + 1) // 2)
