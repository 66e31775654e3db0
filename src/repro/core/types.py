"""Core types for the PGAS data-structure layer.

Concurrency *promises* (paper §II-C, "concurrency promises"): the caller
declares which operations may run concurrently with the one being issued,
which selects the cheapest correct implementation (paper Tables II/III).

AMO opcodes: the fixed-function "NIC" operations available in RDMA style.
Anything richer must go through the RPC/active-message backend.
"""
from __future__ import annotations

import enum
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import trace


def host_value(x) -> Optional[np.ndarray]:
    """Host copy of `x`, or None when `x` is absent or a jit tracer.

    The front-ends read batch signals (skew, dedup, cache keys) on the
    host when the batch is concrete and degrade to static choices under
    tracing. Any other failure to read a device value (a failed or
    out-of-memory computation) propagates. Reading a device array is
    the span `ds.host_read` and the counter `host_reads`."""
    if x is None or isinstance(x, jax.core.Tracer):
        return None
    if not isinstance(x, jax.Array):
        return np.asarray(x)
    with trace.span("host_read"):
        trace.count("host_reads")
        return np.asarray(x)


def device_wait(x):
    """`jax.block_until_ready(x)`, traced as a host read: the span
    `ds.host_read` and the counter `host_reads`."""
    with trace.span("host_read"):
        trace.count("host_reads")
        return jax.block_until_ready(x)


class Promise(enum.Enum):
    """Concurrency promise levels, paper notation C_RW / C_W / C_R / C_l."""

    CRW = "concurrent_read_write"  # fully atomic
    CW = "concurrent_write"        # phasal: only writes (inserts/pushes) concurrent
    CR = "concurrent_read"         # phasal: only reads (finds/pops) concurrent
    CL = "concurrent_local"        # local-only access (queue is host-local)


class Backend(enum.Enum):
    RDMA = "rdma"   # one-sided component ops (put/get/CAS/FAO phases)
    RPC = "rpc"     # aggregated active messages (one round trip + handler)
    AUTO = "auto"   # cost-model-selected


def as_backend(backend) -> "Backend":
    """Coerce a Backend or its string value ("rdma"/"rpc"/"auto")."""
    return Backend(backend) if isinstance(backend, str) else backend


class AmoKind(enum.IntEnum):
    """Fixed-function atomics. Integer codes shared with the Pallas kernel.

    Codes 0-6 are the primitive single-word AMOs (one per network phase).
    Codes 7-9 are FUSED component descriptors (DESIGN.md §2): one request
    phase carries a compound op that the owner lane applies as a single
    serialized step — the Active-Access / Storm-style composite remote op.
    """

    PUT = 0    # unconditional store, returns previous value
    GET = 1    # read, no modification
    CAS = 2    # compare(a)-and-swap(b), returns previous value
    FAA = 3    # fetch-and-add(a)
    FOR = 4    # fetch-and-or(a)
    FAND = 5   # fetch-and-and(a)
    FXOR = 6   # fetch-and-xor(a)
    # Fused descriptors [off | kind | a | b | aux0 | aux1 | vals...]:
    CAS_PUT = 7       # CAS(a->b) at off; on success put vals at aux0
    CAS_PUT_PUB = 8   # CAS_PUT, then on success mem[off] ^= aux1 (publish)
    FAO_GET = 9       # fetch-and-op(a, subkind b) at off; gather from aux0


# Hash-table slot flag states (stored in the flag word of each slot).
FLAG_EMPTY = jnp.int32(0)
FLAG_RESERVED = jnp.int32(1)
FLAG_READY = jnp.int32(2)
# Reader counting for C_RW find: readers add READ_UNIT to the flag word.
# (The paper uses fetch-and-OR on per-reader bits; a counter has identical
# cost (one A_FAO) and avoids a static reader limit.)
READ_UNIT = jnp.int32(256)
STATE_MASK = jnp.int32(255)

EMPTY_KEY = jnp.int32(-0x7FFFFFFF)  # sentinel for "no key present"


def f32_to_words(x: jax.Array) -> jax.Array:
    """Bitcast float32 payloads into int32 words for word-addressed windows."""
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def words_to_f32(x: jax.Array) -> jax.Array:
    return jax.lax.bitcast_convert_type(x, jnp.float32)


@dataclass(frozen=True)
class OpStats:
    """Workload statistics fed to the cost model's backend chooser."""

    ops_per_rank: int = 1
    payload_bytes: int = 8
    expected_probes: float = 1.0     # hash-table collision factor (round trips)
    contention: float = 1.0          # expected CAS attempts for persistent CAS
    target_busy_us: float = 0.0      # interspersed compute between dispatch points
    progress_thread: bool = False    # dedicated servicing channel (paper Fig. 6 "PT")
    skew: float = 1.0                # batch owner-load skew: max owner load / mean
                                     # (1.0 = uniform; P = single hot owner).
                                     # High skew serializes RDMA atomics in one
                                     # owner's apply lane while AM aggregation
                                     # amortizes the round trip (DESIGN.md §4).
    dedup: float = 1.0               # distinct-row fraction of the batch:
                                     # distinct (owner, offset) descriptor rows
                                     # / total rows (1.0 = all distinct; 1/n =
                                     # one hot row). Coalescing (DESIGN.md §6)
                                     # ships only the distinct rows, so dedup
                                     # scales the wire/owner-apply terms of the
                                     # coalesced arms.
    pipeline_depth: int = 1          # in-flight batch windows (DESIGN.md §7):
                                     # 1 = synchronous lock-step engine, 2 =
                                     # double-buffered. Depth > 1 overlaps
                                     # batch k+1's route+send with batch k's
                                     # owner-apply+reply, so predict_arm
                                     # prices a pipelined op at
                                     # max(A, B) + min(A, B)/depth instead of
                                     # A + B (A = origin-side, B = owner-side).
    hit_rate: float = 0.0            # hot-bucket cache hit fraction
                                     # (DESIGN.md §8): fraction of a find
                                     # batch expected to be served from the
                                     # origin-local bucket cache, paying only
                                     # the host lookup. Only the cached find
                                     # arm (rdma_fused under CR) consults it;
                                     # 0.0 = no cache attached.
    loss_rate: float = 0.0           # measured per-attempt delivery-failure
                                     # probability (DESIGN.md §10): fraction
                                     # of transmissions the fault plane (or a
                                     # real lossy fabric) drops, as tracked by
                                     # AdaptiveEngine.loss_ewma. Each op pays
                                     # an expected lr/(1-lr) retransmissions
                                     # of its smallest retryable unit — a
                                     # whole AM round trip for the RPC arms
                                     # vs. one wire phase for the one-sided
                                     # arms — so loss tilts the model toward
                                     # RDMA (the paper's trade flips again
                                     # under loss). 0.0 = lossless: every
                                     # prediction is bit-identical to the
                                     # §9 model.
    abort_rate: float = 0.0          # measured transaction abort probability
                                     # (DESIGN.md §11): fraction of txn
                                     # attempts that fail optimistic
                                     # validation (lock/probe conflict) and
                                     # must re-run the whole round, as
                                     # tracked by AdaptiveEngine.abort_ewma
                                     # (the sixth online signal). Each txn
                                     # pays an expected ar/(1-ar) retry
                                     # rounds of its FULL per-arm cost plus
                                     # the retry_penalty bookkeeping —
                                     # contention multiplies whichever arm
                                     # runs the round, unlike wire loss
                                     # whose unit is arm-asymmetric. 0.0 =
                                     # uncontended: predictions identical
                                     # to the §10 model.
    nranks: int = 0                  # shard count P the batch runs at
                                     # (DESIGN.md §9): scales the per-rank
                                     # occupancy-exchange and AM reply fan-out
                                     # terms of the cost model. 0 = unknown —
                                     # the model applies no P-dependence, so
                                     # every P=8-era prediction is unchanged.
