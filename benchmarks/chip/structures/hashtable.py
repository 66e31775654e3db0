"""Drives the program's distributed hash table (`repro.core.hashtable`).

Set-up writes the pre-loaded table straight into the program's window
layout, [flag | key | value words] per slot, in one jitted call
(`bulk_load`): each pre-load key is placed as the reference places it
(`reference.hashtable.place`, copied here for the device) and claims a
slot in probe rounds as the program's fused insert claims them, the
lowest key index winning a contended slot, so every key lies in its
probe window with no empty slot before it, and a key whose whole window
is taken is left out. (The program's own jitted insert takes about 10 s
of device time per call of 65,536 keys per rank on one v5e, 660 s for
the pre-load.) The seed's key base is an argument of every set-up
program, so one compiled program serves every seed.

The window calls the normal front-ends, `hashtable.insert` and
`hashtable.find`, with `backend="auto"` and an `am.AMEngine`, so the
default chooser picks the arm of every batch.

On more than one chip the rank axis is sharded over them: the table,
the pools and every front-end call (under `routing.sharding_hook`) keep
whole rank rows on each chip, so `routing.exchange` is an all-to-all
between chips. The pre-load (`preload_rows`) then runs on each chip in
`shard_map`: each chip walks the key range in chunks, keeps the keys
whose owner row it holds and claims their slots by the same rule, with
row-local indices only. On one chip the same code places every key, in
one chunk where the keys fit it, and no mesh, sharding or hook is built
or issued.
"""
from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from benchmarks.chip import traffic
from benchmarks.chip.reference.hashtable import READY
from repro.core import adaptive, am, hashtable, routing

# pre-load key indices one chip walks at a time (see `preload_rows`)
PRELOAD_CHUNK = 1 << 25


def place_jnp(keys, nranks: int, nslots: int):
    """`reference.hashtable.place` on the device: (owner, first slot)."""
    k = jax.lax.bitcast_convert_type(keys, jnp.uint32)
    k = (k ^ (k >> 16)) * jnp.uint32(0x85EBCA6B)
    k = (k ^ (k >> 13)) * jnp.uint32(0xC2B2AE35)
    h = k ^ (k >> 16)
    owner = (h % jnp.uint32(nranks)).astype(jnp.int32)
    start = ((h // jnp.uint32(nranks)) % jnp.uint32(nslots)).astype(jnp.int32)
    return owner, start


class Driver:
    kind = "hashtable"

    def __init__(self, config: dict, plan: traffic.Plan, devices=None):
        self.config, self.plan = config, plan
        self.P = plan.nranks
        self.nslots = int(config["nslots"])
        self.vw = int(config["val_words"])
        self.max_probes = int(config["max_probes"])
        self.chips = len(devices) if devices else 1
        self.shard = self.hook = None
        if self.chips > 1:
            if self.P % self.chips:
                raise ValueError(f"{self.P} ranks do not split evenly over "
                                 f"{self.chips} chips")
            self.mesh = Mesh(np.array(devices), ("p",))
            self.shard = shard = NamedSharding(self.mesh, PartitionSpec("p"))

            # made once: the AM engine's program cache keys on its id
            def hook(x, role):
                return jax.lax.with_sharding_constraint(x, shard)
            self.hook = hook
        self.engine = am.AMEngine(self.P)
        self.chooser = adaptive.default_engine(self.P,
                                               am_engine=self.engine)

    def _sharded(self):
        """The front-ends' rank axis pinned to the chips, where there are
        several."""
        if self.hook is None:
            return contextlib.nullcontext()
        return routing.sharding_hook(self.hook)

    # -- set-up ---------------------------------------------------------
    def build(self):
        """The pre-loaded table. Returns the device arrays of the
        pre-load's answers, one entry per pre-load key: (placed, probes)."""
        kb = np.uint32(self.plan.key_base)
        placed = {} if self.shard is None else {"sharding": self.shard}
        ht = hashtable.make_hashtable(self.P, self.nslots, self.vw, **placed)
        ht, ok, probes, over = jax.jit(self.bulk_load,
                                       donate_argnums=0)(ht, kb)
        if int(over):
            raise RuntimeError(f"{int(over)} pre-load keys overflowed a "
                               "chip's share of a chunk")
        self.start = self.state = ht
        return ok, probes

    def bulk_load(self, ht, key_base):
        """The pre-load: `preload_rows` on the one chip, or on each chip
        for the rows it holds, each key's answer taken from its owner's
        chip and the answers split over the chips. Returns the table,
        placed and probes (int8) per pre-load key, and the count of keys
        a chip had no room for in its share of a chunk (0, or the
        pre-load is void)."""
        n = self.plan.n_pre
        if self.shard is None:
            data, signed, over = self.preload_rows(ht.win.data, key_base, 0)
        else:
            rows = self.P // self.chips
            n_pad = -(-n // self.chips) * self.chips

            def per_chip(local, key_base):
                row0 = jax.lax.axis_index("p") * rows
                data, signed, over = self.preload_rows(local, key_base, row0,
                                                       n_pad)
                # each key is 0 on every chip but its owner's
                signed = jax.lax.psum_scatter(signed, "p", tiled=True)
                return data, signed, jax.lax.psum(over, "p")

            spec = PartitionSpec("p")
            data, signed, over = jax.shard_map(
                per_chip, mesh=self.mesh, in_specs=(spec, PartitionSpec()),
                out_specs=(spec, spec, PartitionSpec()),
                check_vma=False)(ht.win.data, key_base)
            signed = signed[:n]
        win = dataclasses.replace(ht.win, data=data)
        return (dataclasses.replace(ht, win=win), signed > 0,
                jnp.abs(signed), over)

    def preload_rows(self, local, key_base, row0, n_out=None):
        """Places the pre-load keys whose owner is one of the rows of
        `local`, the window's rows from `row0` on, with row-local indices
        only. Keys go in chunks of `PRELOAD_CHUNK` indices, one chunk
        when they fit; the rows' share of a chunk (all of it on one chip,
        about 1/chips of it with room to spare on several) is compacted
        in index order and claims slots in probe rounds as the program's
        fused insert claims them, the lowest key index winning a
        contended slot. A key claims only slots that no earlier chunk
        took, and a slot once taken stays taken, so every placed key lies
        in its probe window with no empty slot before it, and a refused
        key's window is full. Returns the rows, each pre-load key's
        probes as int8 (negative if refused, 0 for keys of other rows),
        `n_out` entries of them (the pre-load's count by default, 0
        after it), and the count of keys the compacted chunk had no room
        for."""
        P, ns, mp, vw = self.P, self.nslots, self.max_probes, self.vw
        rows, n, rec_w = P // self.chips, self.plan.n_pre, 2 + vw
        local_slots = rows * ns
        chunk = min(PRELOAD_CHUNK, 1 << max(n - 1, 1).bit_length())
        nchunks = -(-n // chunk)
        n_out = n_out or n
        size = max(nchunks * chunk, n_out)
        cap = chunk if self.chips == 1 else min(
            chunk, chunk // self.chips + max(chunk // 64, 1024))

        def one_chunk(c, carry):
            flat, taken, signed, over = carry
            lane = jnp.arange(cap, dtype=jnp.int32)
            idx = c * chunk + jnp.arange(chunk, dtype=jnp.int32)
            owner, _ = place_jnp(traffic.key_jnp(idx, key_base), P, ns)
            mine = (idx < n) & (owner >= row0) & (owner < row0 + rows)
            pos = jnp.where(mine, jnp.cumsum(mine, dtype=jnp.int32) - 1, cap)
            sel = jnp.full(cap, -1, jnp.int32).at[pos].set(idx, mode="drop")
            over = over + jnp.maximum(jnp.sum(mine, dtype=jnp.int32) - cap, 0)
            live = sel >= 0
            keys = traffic.key_jnp(jnp.maximum(sel, 0), key_base)
            owner, start = place_jnp(keys, P, ns)
            base = (owner - row0) * ns
            slot = jnp.zeros(cap, jnp.int32)
            probes = jnp.zeros(cap, jnp.int32)
            pending = live
            for j in range(mp):
                s = jnp.where(pending, base + (start + j) % ns, 0)
                bid = jnp.where(pending & ~taken[s], lane, cap)
                first = jnp.full(local_slots, cap, jnp.int32).at[s].min(bid)
                won = pending & (first[s] == lane)
                taken = taken.at[jnp.where(won, s, local_slots)].set(
                    True, mode="drop")
                slot = jnp.where(won, s, slot)
                probes = probes + pending.astype(jnp.int32)
                pending = pending & ~won
            placed = live & ~pending
            # 1-D scatters only: a (slots, 3) array would be padded to 128
            # lanes on the TPU
            at = jnp.where(placed, slot, local_slots) * rec_w
            flat = flat.at[at].set(READY, mode="drop")
            flat = flat.at[at + 1].set(keys, mode="drop")
            vals = traffic.value_jnp(keys)
            for w in range(vw):
                flat = flat.at[at + 2 + w].set(vals, mode="drop")
            signed = signed.at[jnp.where(live, sel, size)].set(
                jnp.where(placed, probes, -probes).astype(jnp.int8),
                mode="drop")
            return flat, taken, signed, over

        carry = (local.reshape(-1), jnp.zeros(local_slots, bool),
                 jnp.zeros(size, jnp.int8), jnp.int32(0))
        flat, _, signed, over = jax.lax.fori_loop(0, nchunks, one_chunk,
                                                  carry)
        return flat.reshape(local.shape), signed[:n_out], over

    @staticmethod
    def insert_pool(idx, key_base):
        """Keys and values of the insert batches with key indices `idx`,
        one (keys, values) pair per batch."""
        keys = traffic.key_jnp(idx, key_base)
        vals = traffic.value_jnp(keys)[..., None]
        return tuple(zip(list(keys), list(vals)))

    @staticmethod
    def find_pool(idx, key_base):
        """Keys of the find batches with key indices `idx`, one per batch."""
        return tuple(traffic.key_jnp(idx, key_base))

    def make_pools(self):
        """The window's inputs, one device array per pool batch, made in
        one jitted call per op."""
        plan, kb = self.plan, np.uint32(self.plan.key_base)
        placed = {} if self.shard is None else {"out_shardings": self.shard}
        self.pools = {}
        if "insert" in plan.pattern:
            idx = np.stack([plan.insert_index(s) for s in range(plan.pool)])
            self.pools["insert"] = jax.jit(self.insert_pool, **placed)(
                jnp.asarray(idx, jnp.int32), kb)
        if "find" in plan.pattern:
            self.find_idx = plan.find_pool_indices()
            self.pools["find"] = jax.jit(self.find_pool, **placed)(
                jnp.asarray(self.find_idx), kb)
        jax.block_until_ready(self.pools)

    # -- the window -----------------------------------------------------
    def issue(self, op: str, slot: int, **kw):
        """One front-end call. Returns (ops issued, device outputs);
        the outputs' first entry is the new state."""
        with self._sharded():
            if op == "insert":
                keys, vals = self.pools["insert"][slot]
                out = hashtable.insert(self.state, keys, vals,
                                       engine=self.engine,
                                       max_probes=self.max_probes, **kw)
            elif op == "find":
                out = hashtable.find(self.state, self.pools["find"][slot],
                                     engine=self.engine,
                                     max_probes=self.max_probes, **kw)
            else:
                raise ValueError(f"hash table has no op {op!r}")
        return self.plan.per_batch, out

    def accept(self, out):
        """Make the call's state the current one; return its answers."""
        self.state = out[0]
        return out[1:]

    def restore(self):
        """Back to the pre-loaded table, which set-up holds."""
        self.state = self.start

    def tally(self, op, outs, counters):
        """Fold one batch's answers into the counters; return how many of
        its ops failed (inserts that found no free slot)."""
        if op != "insert":
            return 0
        ok, probes = outs
        counters["insert_ops"] = counters.get("insert_ops", 0) + ok.size
        counters["insert_probes"] = counters.get("insert_probes", 0) + \
            int(probes.sum())
        return int((~ok).sum())

    def record(self, built):
        """What the reference needs besides the batches: the pre-load's
        answers, the find pool's key indices and the table read back."""
        ok, probes = built
        return {"preload_ok": np.asarray(ok),
                "preload_probes": np.asarray(probes),
                "find_idx": getattr(self, "find_idx", None),
                "final": np.asarray(self.state.win.data)}

    def arms(self):
        return [d.arm for d in self.chooser.log]

    def free(self):
        self.state = self.start = self.pools = None
