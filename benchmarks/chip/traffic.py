"""The one traffic generator: turns a mix's data file and a seed into the
keys and values a run issues.

Every key and value is a pure function of an index and of bases drawn
from the seed, so the reference recomputes them on the host
(`*_np`) exactly as the device makes them (`*_jnp`):

    key(i)   = mix32(i + key_base)           i = 0 .. pre-load + window
    value(k) = mix32(k ^ VALUE_SALT)
    qval(s)  = mix32(s + queue_base)         s = push sequence number

`mix32` is a bijection of uint32, so distinct indices give distinct keys;
`key_base` keeps `i + key_base` off 0, so no key is 0. Find keys follow
YCSB's scrambled-zipfian request distribution over the loaded keys
(Gray et al.'s method, YCSB `ScrambledZipfianGenerator`), or a uniform
one. Every seed gives the same sizes and arrivals; only the keys, values
and which keys a find asks for differ.
"""
from __future__ import annotations

import json
import pathlib

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
VALUE_SALT = 0x5BD1E995

# YCSB ScrambledZipfianGenerator: a zipfian over ITEM_COUNT items with the
# precomputed zeta for theta 0.99, hashed onto the key space by FNV-1a-64.
YCSB_ITEM_COUNT = 10_000_000_000
YCSB_ZETAN = 26.46902820178302
FNV_OFFSET_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211


def load_mix(name: str) -> dict:
    """The traffic mix `traffic/<name>.json`."""
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def load_config(name: str) -> dict:
    """The configuration `configs/<name>.json`."""
    return json.loads((HERE / "configs" / f"{name}.json").read_text())


# ---------------------------------------------------------------------------
# bijective 32-bit mix (lowbias32), on the host and on the device
# ---------------------------------------------------------------------------
def mix32_np(x):
    x = np.asarray(x).astype(np.uint32)
    x = x ^ (x >> np.uint32(16))
    x = x * np.uint32(0x7FEB352D)
    x = x ^ (x >> np.uint32(15))
    x = x * np.uint32(0x846CA68B)
    return x ^ (x >> np.uint32(16))


def mix32_jnp(x):
    import jax.numpy as jnp
    x = x.astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def key_np(idx, key_base: int):
    return mix32_np(np.asarray(idx, np.uint64).astype(np.uint32)
                    + np.uint32(key_base)).view(np.int32)


def value_np(keys):
    return mix32_np(np.asarray(keys, np.int32).view(np.uint32)
                    ^ np.uint32(VALUE_SALT)).view(np.int32)


def key_jnp(idx, key_base):
    """`key_np` on the device. Pass `key_base` as a uint32 array argument
    of the jitted caller, not as a constant, so that one compiled program
    serves every seed."""
    import jax
    import jax.numpy as jnp
    u = mix32_jnp(idx.astype(jnp.uint32)
                  + jnp.asarray(key_base, dtype=jnp.uint32))
    return jax.lax.bitcast_convert_type(u, jnp.int32)


def value_jnp(keys):
    import jax
    import jax.numpy as jnp
    u = jax.lax.bitcast_convert_type(keys, jnp.uint32)
    return jax.lax.bitcast_convert_type(
        mix32_jnp(u ^ jnp.uint32(VALUE_SALT)), jnp.int32)


def qval_np(seq, queue_base: int):
    return mix32_np(np.asarray(seq, np.uint64).astype(np.uint32)
                    + np.uint32(queue_base)).view(np.int32)


def qval_jnp(seq, queue_base):
    """`qval_np` on the device; `queue_base` as in `key_jnp`."""
    import jax
    import jax.numpy as jnp
    u = mix32_jnp(seq.astype(jnp.uint32)
                  + jnp.asarray(queue_base, dtype=jnp.uint32))
    return jax.lax.bitcast_convert_type(u, jnp.int32)


# ---------------------------------------------------------------------------
# request distributions
# ---------------------------------------------------------------------------
def scrambled_zipfian(rng, n_items: int, size, theta: float = 0.99):
    """Indices in [0, n_items) drawn as YCSB's ScrambledZipfianGenerator
    draws them: Gray et al.'s zipfian over YCSB_ITEM_COUNT items, then
    FNV-1a-64 of the rank, modulo the item count."""
    if theta != 0.99:
        raise ValueError("YCSB's scrambled zipfian is defined for 0.99")
    items, zetan = YCSB_ITEM_COUNT, YCSB_ZETAN
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    u = rng.random(size)
    uz = u * zetan
    rank = (items * np.power(eta * u - eta + 1.0, alpha)).astype(np.int64)
    rank = np.where(uz < 1.0, 0, np.where(uz < zeta2, 1, rank))
    h = np.full(rank.shape, FNV_OFFSET_64, np.uint64)
    v = rank.astype(np.uint64)
    for _ in range(8):
        h = (h ^ (v & np.uint64(0xFF))) * np.uint64(FNV_PRIME_64)
        v = v >> np.uint64(8)
    h = np.abs(h.view(np.int64))
    return h % np.int64(n_items)


def find_indices(rng, mix: dict, n_items: int, size):
    dist = mix["find_keys"]
    if dist == "scrambled_zipfian":
        return scrambled_zipfian(rng, n_items, size, mix["zipf_theta"])
    if dist == "uniform":
        return rng.integers(0, n_items, size)
    raise ValueError(f"unknown find key distribution {dist!r}")


# ---------------------------------------------------------------------------
class Plan:
    """What one run issues, from (config, mix, seed): sizes, the op pattern
    and the bases of every key and value. Holds no device array."""

    def __init__(self, config: dict, mix: dict, seed: int):
        self.config, self.mix, self.seed = config, mix, int(seed)
        self.nranks = int(config["nranks"])
        self.batch = int(mix["batch_per_rank"])
        self.per_batch = self.nranks * self.batch
        self.pattern = list(mix["pattern"])
        self.pool = int(mix["pool_batches"])
        # with `restart_each_pool` the window goes back to the state set-up
        # built after every pass over the pool: an epoch is one pass
        self.epoch = self.pool if mix.get("restart_each_pool") else 0
        self.rng = np.random.default_rng(np.random.SeedSequence(self.seed))
        if config["structure"] == "hashtable":
            slots = self.nranks * int(config["nslots"])
            self.n_pre = int(round(mix["preload_load"] * slots))
            n_ins = self.pool * self.per_batch if "insert" in self.pattern \
                else 0
            total = self.n_pre + n_ins
            self.key_base = 1 + int(self.rng.integers(0, (1 << 32) - total - 2))
        else:
            self.prefill = int(mix["prefill"])
            self.queue_base = int(self.rng.integers(0, 1 << 32))

    def op(self, k: int) -> str:
        """The op of the k-th batch of the window."""
        return self.pattern[k % len(self.pattern)]

    def slot(self, k: int) -> int:
        """Which batch of the op's pool the k-th batch issues."""
        per_op = self.pattern.count(self.op(k))
        nth = (k // len(self.pattern)) * per_op + \
            self.pattern[:k % len(self.pattern)].count(self.op(k))
        return nth % self.pool

    def insert_index(self, slot: int):
        """Key indices of insert batch `slot`, (P, n)."""
        lo = self.n_pre + slot * self.per_batch
        return np.arange(lo, lo + self.per_batch).reshape(self.nranks,
                                                         self.batch)

    def find_pool_indices(self):
        """Key indices of every find batch of the pool, (pool, P, n):
        drawn over the pre-loaded keys."""
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, 1]))
        return find_indices(rng, self.mix, self.n_pre,
                            (self.pool, self.nranks, self.batch)
                            ).astype(np.int32)

    def push_seq(self, slot: int):
        """Push sequence numbers of push batch `slot`, (P, n): after the
        prefill, in (rank, slot) order."""
        lo = self.prefill + slot * self.per_batch
        return np.arange(lo, lo + self.per_batch).reshape(self.nranks,
                                                         self.batch)
