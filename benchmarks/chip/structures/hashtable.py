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
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import traffic
from benchmarks.chip.reference.hashtable import READY
from repro.core import adaptive, am, hashtable


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

    def __init__(self, config: dict, plan: traffic.Plan):
        self.config, self.plan = config, plan
        self.P = plan.nranks
        self.nslots = int(config["nslots"])
        self.vw = int(config["val_words"])
        self.max_probes = int(config["max_probes"])
        self.engine = am.AMEngine(self.P)
        self.chooser = adaptive.default_engine(self.P,
                                               am_engine=self.engine)

    # -- set-up ---------------------------------------------------------
    def build(self):
        """The pre-loaded table. Returns the device arrays of the
        pre-load's (placed, probes), one entry per pre-load key."""
        ht = hashtable.make_hashtable(self.P, self.nslots, self.vw)
        ht, ok, probes = jax.jit(self.bulk_load, donate_argnums=0)(
            ht, np.uint32(self.plan.key_base))
        self.start = self.state = ht
        return ok, probes

    def bulk_load(self, ht, key_base):
        plan, P, ns, mp = self.plan, self.P, self.nslots, self.max_probes
        n = plan.n_pre
        idx = jnp.arange(n, dtype=jnp.int32)
        keys = traffic.key_jnp(idx, key_base)
        owner, start = place_jnp(keys, P, ns)
        base = owner * ns
        taken = jnp.zeros(P * ns, bool)
        slot = jnp.zeros(n, jnp.int32)
        probes = jnp.zeros(n, jnp.int32)
        pending = jnp.ones(n, bool)
        for j in range(mp):
            s = base + (start + j) % ns
            bid = jnp.where(pending & ~taken[s], idx, n)
            first = jnp.full(P * ns, n, jnp.int32).at[s].min(bid)
            won = pending & (first[s] == idx)
            taken = taken.at[jnp.where(won, s, P * ns)].set(True,
                                                            mode="drop")
            slot = jnp.where(won, s, slot)
            probes = probes + pending.astype(jnp.int32)
            pending = pending & ~won
        # 1-D scatters only: a (slots, 3) array would be padded to 128
        # lanes on the TPU
        rec_w = 2 + self.vw
        at = jnp.where(pending, P * ns, slot) * rec_w
        data = jnp.zeros(P * ns * rec_w, jnp.int32)
        data = data.at[at].set(READY, mode="drop")
        data = data.at[at + 1].set(keys, mode="drop")
        vals = traffic.value_jnp(keys)
        for w in range(self.vw):
            data = data.at[at + 2 + w].set(vals, mode="drop")
        win = dataclasses.replace(ht.win, data=data.reshape(P, ns * rec_w))
        return dataclasses.replace(ht, win=win), ~pending, probes

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
        self.pools = {}
        if "insert" in plan.pattern:
            idx = np.stack([plan.insert_index(s) for s in range(plan.pool)])
            self.pools["insert"] = jax.jit(self.insert_pool)(
                jnp.asarray(idx, jnp.int32), kb)
        if "find" in plan.pattern:
            self.find_idx = plan.find_pool_indices()
            self.pools["find"] = jax.jit(self.find_pool)(
                jnp.asarray(self.find_idx), kb)
        jax.block_until_ready(self.pools)

    # -- the window -----------------------------------------------------
    def issue(self, op: str, slot: int, **kw):
        """One front-end call. Returns (ops issued, device outputs);
        the outputs' first entry is the new state."""
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
