"""Drive the distributed data structures once on a TPU and check every
answer against a plain numpy reference.

    python chip_smoke.py [--seed N]      # one chip
    python chip_smoke.py --four-chips     # the mesh phase

One chip, through the normal front-ends (`hashtable` / `queue` / `txn`,
via `adaptive` and `pipeline`):

  a. hash table: 8 virtual ranks over a (8, 3 * 2**24) int32 window
     (1.5 GiB), loaded in batches of 65,536 keys per rank through the
     adaptive chooser, then uniform and zipfian find batches (half hits)
     checked against a map of every inserted key;
  b. pipeline: insert+find batches at depth 2 equal depth 1;
  c. hosted queue: push/pop on the one-sided and AM arms against a deque;
  d. transactions: the hot-key mix on the rdma_fused and am arms, checked
     by serial replay and a serializability search;
  e. the Pallas owner-lane kernels, compiled, bit-equal to the XLA lane.

`--four-chips` runs only the mesh phase: the same rank count sharded two
per chip over four chips, a few insert and find batches bit-equal to one
chip and to the reference, with the compiled batch's all-to-all count.

Exits non-zero, printing no result, unless the first device is a TPU.
Any mismatch raises. The last line of stdout is one JSON object with the
device. Timings printed on the way are smoke timings, not metrics.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from benchmarks import common  # noqa: E402
from benchmarks.components import hot_key_txn_mix  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core import adaptive, am, hashtable, pipeline, queue  # noqa: E402
from repro.core import routing, txn, window  # noqa: E402
from repro.kernels import amo_apply, hash_probe  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.launch import hlo_stats  # noqa: E402

P = 8                    # virtual ranks
NSLOTS = 1 << 24         # records per rank: (8, 3 * 2**24) int32 = 1.5 GiB
VAL_WORDS = 1
BATCH = 1 << 16          # keys per rank per batch
LOAD_KEYS = 1 << 22      # keys loaded in phase a
FIND_BATCHES = (("uniform", 2), ("zipfian", 2))
PIPE_BATCHES = 2
QUEUE_CAPACITY = 1 << 20
QUEUE_N = 1 << 12        # pushes / pops per rank per batch
TXN_BATCHES = 2
KERNEL_P = 8             # owner-lane kernels: shards resident in VMEM
KERNEL_WORDS = 1 << 18   # 1 MiB per owner
KERNEL_SLOTS = 1 << 16   # hash records per owner (3 words each)
KERNEL_OPS = 2048        # ops per owner
MESH_BATCHES = 2
MESH_BATCH = 1 << 10     # keys per rank per mesh batch, rdma arm only:
                         # 1/64 of BATCH, bounded by compile time


def say(*parts) -> None:
    print("smoke:", *parts, flush=True)


def check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(f"mismatch: {what}")


class CompileClock:
    """Seconds spent in XLA backend compiles, from JAX's own events."""

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration


def timed(clock, name, fn, *args, **kw):
    c0, t0 = clock.seconds, time.perf_counter()
    out = fn(*args, **kw)
    say(f"smoke timing (not a metric) {name}: "
        f"compile_s={clock.seconds - c0} wall_s={time.perf_counter() - t0}")
    return out


# ---------------------------------------------------------------------------
# Reference
# ---------------------------------------------------------------------------
class KeyMap:
    """Plain reference of a hash table's contents: key -> value words."""

    def __init__(self, vw: int):
        self.keys = np.zeros(0, np.int32)
        self.vals = np.zeros((0, vw), np.int32)

    def add(self, keys, vals) -> None:
        keys = np.concatenate([self.keys, np.asarray(keys, np.int32)])
        vals = np.concatenate([self.vals, np.asarray(vals, np.int32)])
        order = np.argsort(keys, kind="stable")
        self.keys, self.vals = keys[order], vals[order]
        check(np.all(self.keys[1:] != self.keys[:-1]), "keys inserted twice")

    def lookup(self, q):
        """(found, values) of each key in q; values are zeros if absent."""
        q = np.asarray(q, np.int32)
        if not len(self.keys):
            return (np.zeros(q.shape, bool),
                    np.zeros(q.shape + self.vals.shape[1:], np.int32))
        i = np.minimum(np.searchsorted(self.keys, q), len(self.keys) - 1)
        found = self.keys[i] == q
        return found, np.where(found[..., None], self.vals[i], 0)

    def __len__(self):
        return len(self.keys)


def distinct_keys(rng, n: int, exclude: KeyMap | None = None):
    """n distinct non-zero int32 keys in random order, none in `exclude`."""
    out = np.zeros(0, np.int64)
    while out.size < n:
        cand = rng.integers(1, (1 << 31) - 1, size=n - out.size + 1024)
        out = np.unique(np.concatenate([out, cand]))
        if exclude is not None and len(exclude):
            out = out[~exclude.lookup(out.astype(np.int32))[0]]
    return rng.permutation(out)[:n].astype(np.int32)


def find_batch(rng, ref: KeyMap, nranks: int, n: int, scenario: str):
    """(nranks, n) find keys with the scenario's owner skew: fresh keys
    from `gen_batch_keys`, about half replaced by inserted keys of the
    same owner."""
    keys = common.gen_batch_keys(nranks, n, scenario, rng)
    owners = common.owner_of(keys, nranks)
    stored = common.owner_of(ref.keys, nranks)
    hit = rng.random(keys.shape) < 0.5
    for o in range(nranks):
        pool = ref.keys[stored == o]
        rows = hit & (owners == o)
        if pool.size and rows.any():
            keys[rows] = pool[rng.integers(0, pool.size, int(rows.sum()))]
    return keys


def check_failures(ok, probes, max_probes: int = 8) -> None:
    """An insert may fail only when its whole probe window was full."""
    ok, probes = np.asarray(ok), np.asarray(probes)
    check(np.all(probes[~ok] == max_probes),
          "an insert failed before probing its whole window")


def peak_bytes():
    return (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------
def phase_hashtable(rng, *, nranks=P, nslots=NSLOTS, load_keys=LOAD_KEYS,
                    batch=BATCH, find_batches=FIND_BATCHES):
    """(a) Load a table through the adaptive chooser, then find."""
    ht = hashtable.make_hashtable(nranks, nslots, VAL_WORDS)
    say(f"hash table window {ht.win.data.shape} int32:",
        f"table_bytes={ht.win.data.nbytes}")
    eng = am.AMEngine(nranks)
    ad = adaptive.AdaptiveEngine(nranks, am_engine=eng, measure=True)
    per = nranks * batch
    nb = max(1, load_keys // per)
    keys = distinct_keys(rng, nb * per)
    vals = rng.integers(-(1 << 31), (1 << 31) - 1, size=(nb * per, VAL_WORDS),
                        dtype=np.int64).astype(np.int32)
    ok_all = np.zeros(nb * per, bool)
    arms = collections.Counter()
    for b in range(nb):
        sl = slice(b * per, (b + 1) * per)
        ht, ok, probes = hashtable.insert(
            ht, jnp.asarray(keys[sl].reshape(nranks, batch)),
            jnp.asarray(vals[sl].reshape(nranks, batch, VAL_WORDS)),
            engine=eng, adaptive=ad)
        ok_all[sl] = np.asarray(ok).ravel()
        check_failures(ok, probes)
        arm = ad.last_decision.arm
        arms[arm] += 1
        say(f"insert batch {b}: arm={arm} ok={int(ok_all[sl].sum())}/{per}")
    ref = KeyMap(VAL_WORDS)
    ref.add(keys[ok_all], vals[ok_all])
    failed = int((~ok_all).sum())
    say(f"loaded {len(ref)} keys, {failed} inserts found no empty slot in",
        f"their probe window; load factor {len(ref) / (nranks * nslots)}")

    for scenario, count in find_batches:
        for i in range(count):
            fk = find_batch(rng, ref, nranks, batch, scenario)
            if i == 0 and failed:
                # keys whose insert failed must not be found
                miss = keys[~ok_all][:batch]
                fk[0, :miss.size] = miss
            ht, found, fv = hashtable.find(ht, jnp.asarray(fk), engine=eng,
                                           adaptive=ad)
            want_f, want_v = ref.lookup(fk)
            got_f = np.asarray(found)
            check(np.array_equal(got_f, want_f), f"{scenario} find flags")
            check(np.array_equal(np.asarray(fv), want_v),
                  f"{scenario} find values")
            say(f"find {scenario} batch {i}: arm={ad.last_decision.arm}",
                f"hits={int(got_f.sum())}/{got_f.size}")
    say(f"arms picked for inserts: {dict(arms)}")
    say(f"peak_bytes_in_use={peak_bytes()}")
    return ht, ref


def phase_pipeline(rng, ht, ref, *, nranks=P, batch=BATCH,
                   nbatches=PIPE_BATCHES):
    """(b) Insert+find batches through Pipeline: depth 2 equals depth 1
    and the reference."""
    per = nranks * batch
    keys = distinct_keys(rng, nbatches * per, exclude=ref)
    vals = rng.integers(-(1 << 31), (1 << 31) - 1,
                        size=(nbatches * per, VAL_WORDS),
                        dtype=np.int64).astype(np.int32)
    ins = [(keys[b * per:(b + 1) * per].reshape(nranks, batch),
            vals[b * per:(b + 1) * per].reshape(nranks, batch, VAL_WORDS))
           for b in range(nbatches)]
    # each find batch asks for the keys just inserted, older keys, misses
    finds = []
    for k, _ in ins:
        fk = find_batch(rng, ref, nranks, batch, "uniform")
        fk[:, ::2] = k[:, ::2]
        finds.append(fk)

    def run(depth):
        eng = am.AMEngine(nranks)
        ad = adaptive.AdaptiveEngine(nranks, am_engine=eng)
        pipe = pipeline.Pipeline(ht, depth=depth, am_engine=eng)
        handles = []
        for (k, v), fk in zip(ins, finds):
            handles.append(hashtable.insert_async(
                pipe, jnp.asarray(k), jnp.asarray(v), adaptive=ad))
            handles.append(hashtable.find_async(pipe, jnp.asarray(fk),
                                                adaptive=ad))
        outs = [tuple(np.asarray(x) for x in h.result()) for h in handles]
        final = pipe.flush()
        return outs, final, [d.arm for d in ad.log]

    outs1, final1, arms1 = run(1)
    outs2, final2, arms2 = run(2)
    say(f"pipeline arms depth1={arms1} depth2={arms2}")
    for a, b in zip(outs1, outs2):
        for x, y in zip(a, b):
            check(np.array_equal(x, y), "depth 2 result differs from depth 1")
    if arms1 == arms2:
        check(bool(jnp.array_equal(final1.win.data, final2.win.data)),
              "depth 2 table differs from depth 1")
    for b, (k, v) in enumerate(ins):
        ok = outs1[2 * b][0]
        ref.add(k[ok], v[ok])
        check_failures(ok, outs1[2 * b][1])
        want_f, want_v = ref.lookup(finds[b])
        check(np.array_equal(outs1[2 * b + 1][0], want_f),
              "pipelined find flags")
        check(np.array_equal(outs1[2 * b + 1][1], want_v),
              "pipelined find values")
    say(f"pipeline: {2 * nbatches} batches per depth agree;",
        f"peak_bytes_in_use={peak_bytes()}")
    return final2


def phase_queue(rng, *, nranks=P, capacity=QUEUE_CAPACITY, n=QUEUE_N):
    """(c) Hosted queue push/pop on the one-sided and AM arms."""
    q = queue.make_queue(nranks, host=0, capacity=capacity, val_words=1)
    say(f"queue window {q.win.data.shape} int32:",
        f"table_bytes={q.win.data.nbytes}")
    eng = am.AMEngine(nranks)
    queue.build_am_handlers(q, eng)
    ref = collections.deque()
    for backend in ("rdma", "rpc"):
        vals = rng.integers(-(1 << 31), (1 << 31) - 1, size=(nranks, n, 1),
                            dtype=np.int64).astype(np.int32)
        q, ok = queue.push(q, jnp.asarray(vals), backend=backend, engine=eng)
        check(bool(np.asarray(ok).all()), f"{backend} push refused")
        ref.extend(vals.ravel().tolist())   # tickets in (rank, slot) order
        say(f"push {backend}: {nranks * n} values")
    # the last pop batch asks for more than is left: those report nothing
    for backend in ("rdma", "rpc", "rdma"):
        q, got, pv = queue.pop(q, n, backend=backend, engine=eng)
        want_got = np.zeros(nranks * n, bool)
        want_v = np.zeros(nranks * n, np.int32)
        for i in range(nranks * n):
            if ref:
                want_got[i], want_v[i] = True, ref.popleft()
        check(np.array_equal(np.asarray(got).ravel(), want_got),
              f"{backend} pop flags")
        check(np.array_equal(np.asarray(pv).ravel(), want_v),
              f"{backend} pop values")
        say(f"pop {backend}: {int(want_got.sum())} values")


def phase_txn(*, nranks=P, batches=TXN_BATCHES, nops=4):
    """(d) The hot-key transaction mix on the rdma_fused and am arms."""
    init, txns = hot_key_txn_mix(nranks, nops=nops, batches=batches)
    for arm in ("rdma_fused", "am"):
        eng = txn.TxnEngine(nranks, am_engine=am.AMEngine(nranks))
        win = window.Window(data=jnp.asarray(init))
        for b, t in enumerate(txns):
            st0 = {"ht": np.asarray(win.data)}
            res = eng.run(win, t, arm=arm)
            win = res.wins["ht"]
            final = {"ht": np.asarray(win.data)}
            rep, st = txn.serial_apply(st0, t, [p for _, p in res.order])
            check(np.array_equal(st["ht"], final["ht"]),
                  f"{arm} txn window vs serial replay")
            check(np.array_equal(rep, res.replies),
                  f"{arm} txn replies vs serial replay")
            witness = txn.find_serial_order(st0, t, res.committed,
                                            res.replies, final)
            check(witness is not None, f"{arm} txn history not serializable")
            say(f"txn {arm} batch {b}: rounds={res.rounds}",
                f"commits={res.commits} aborts={res.aborts}")


def phase_kernels(rng, *, nranks=KERNEL_P, words=KERNEL_WORDS,
                  slots=KERNEL_SLOTS, m=KERNEL_OPS):
    """(e) Pallas owner-lane kernels (compiled on a TPU) vs the XLA lane."""
    say(f"kernels: interpret={jax.default_backend() == 'cpu'}",
        f"shard words={words} ops per owner={m}")
    local = jnp.asarray(rng.integers(-100, 100, (nranks, words)), jnp.int32)
    mask = jnp.asarray(rng.random((nranks, m)) < 0.9)
    span = words // 64     # concentrated offsets: many same-word chains
    ops = np.zeros((nranks, m, 4), np.int32)
    ops[..., 0] = rng.integers(0, span, (nranks, m))
    ops[..., 1] = rng.integers(0, 7, (nranks, m))
    ops[..., 2:] = rng.integers(-5, 6, (nranks, m, 2))
    ops = jnp.asarray(ops)
    got = amo_apply.amo_apply(local, ops, mask)
    want = kops.amo_apply(local, ops, mask, use_pallas=False)
    for x, y in zip(got, want):
        check(np.array_equal(np.asarray(x), np.asarray(y)), "amo_apply")

    V, G = 2, 3
    fops = np.zeros((nranks, m, 6 + V), np.int32)
    fops[..., 0] = rng.integers(0, span, (nranks, m))
    fops[..., 1] = rng.integers(0, 10, (nranks, m))
    fops[..., 2] = rng.integers(-5, 6, (nranks, m))
    fops[..., 3] = rng.integers(0, 10, (nranks, m))
    fops[..., 4] = rng.integers(-2, words + 2, (nranks, m))
    fops[..., 5] = rng.integers(-5, 6, (nranks, m))
    fops[..., 6:] = rng.integers(0, 100, (nranks, m, V))
    fops = jnp.asarray(fops)
    got = amo_apply.fused_apply(local, fops, mask, reply_width=1 + G)
    want = kops.fused_apply(local, fops, mask, reply_width=1 + G,
                            use_pallas=False)
    for x, y in zip(got, want):
        check(np.array_equal(np.asarray(x), np.asarray(y)), "fused_apply")

    rec_w = 2 + VAL_WORDS
    table = jnp.zeros((nranks, slots * rec_w), jnp.int32)
    starts = jnp.asarray(rng.integers(0, slots, (nranks, m)), jnp.int32)
    hkeys = jnp.asarray(rng.integers(1, 4 * m, (nranks, m)), jnp.int32)
    hvals = jnp.asarray(rng.integers(0, 100, (nranks, m, VAL_WORDS)),
                        jnp.int32)
    kw = dict(nslots=slots, rec_w=rec_w)
    got = hash_probe.hash_insert(table, starts, hkeys, hvals, mask, **kw)
    want = kops.hash_insert(table, starts, hkeys, hvals, mask,
                            use_pallas=False, **kw)
    for x, y in zip(got, want):
        check(np.array_equal(np.asarray(x), np.asarray(y)), "hash_insert")
    got = hash_probe.hash_find(want[2], starts, hkeys, mask, **kw)
    want = kops.hash_find(want[2], starts, hkeys, mask, use_pallas=False,
                          **kw)
    for x, y in zip(got, want):
        check(np.array_equal(np.asarray(x), np.asarray(y)), "hash_find")
    say("kernels: amo_apply, fused_apply, hash_insert, hash_find bit-equal",
        "to the XLA lane")


# ---------------------------------------------------------------------------
# Mesh phase: ranks sharded over chips
# ---------------------------------------------------------------------------
def _insert_batch(ht, keys, vals):
    return hashtable.insert(ht, keys, vals, backend="rdma")


def _find_batch(ht, keys):
    return hashtable.find(ht, keys, backend="rdma")[1:]


def phase_mesh(rng, devices, *, nranks=P, nslots=NSLOTS, batch=MESH_BATCH,
               nbatches=MESH_BATCHES):
    """The rank axis sharded over `devices`; the same batches on the first
    device alone and the reference give the same answers."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(devices), ("p",))
    shard = NamedSharding(mesh, PartitionSpec("p"))

    def hook(x, role):
        return jax.lax.with_sharding_constraint(x, shard)

    per = nranks * batch
    keys = distinct_keys(rng, nbatches * per)
    vals = rng.integers(-(1 << 31), (1 << 31) - 1,
                        size=(nbatches * per, VAL_WORDS),
                        dtype=np.int64).astype(np.int32)

    def run(sharding, sharded):
        ht = hashtable.make_hashtable(nranks, nslots, VAL_WORDS,
                                      sharding=sharding)
        batches = [(jax.device_put(keys[b * per:(b + 1) * per].reshape(
                        nranks, batch), sharding),
                    jax.device_put(vals[b * per:(b + 1) * per].reshape(
                        nranks, batch, VAL_WORDS), sharding))
                   for b in range(nbatches)]
        with routing.sharding_hook(hook if sharded else (lambda x, r: x)):
            ins = jax.jit(_insert_batch, donate_argnums=0).lower(
                ht, *batches[0]).compile()
            fnd = jax.jit(_find_batch).lower(ht, batches[0][0]).compile()
        outs = []
        for k, v in batches:
            ht, ok, probes = ins(ht, k, v)
            found, fv = fnd(ht, k)
            outs.append(tuple(np.asarray(x) for x in (ok, probes, found, fv)))
        a2a = None
        if sharded:
            used = [(d.memory_stats() or {}).get("bytes_in_use")
                    for d in devices]
            shards = [s.data.nbytes for s in ht.win.data.addressable_shards]
            say(f"mesh: window shard bytes per device={shards}",
                f"bytes_in_use per device={used}")
            st = hlo_stats.HloStats(ins.as_text(),
                                    world=len(devices)).summary()
            a2a = st["collectives"].get("all-to-all", {"count": 0})["count"]
        return outs, np.asarray(ht.win.data), a2a

    outs4, data4, a2a = run(shard, True)
    say(f"mesh: all-to-alls in the compiled insert batch={a2a}")
    check(a2a is not None and a2a > 0, "no all-to-all in the sharded batch")
    outs1, data1, _ = run(jax.sharding.SingleDeviceSharding(devices[0]),
                          False)
    for a, b in zip(outs4, outs1):
        for x, y in zip(a, b):
            check(np.array_equal(x, y), "sharded batch vs one device")
    check(np.array_equal(data4, data1), "sharded window vs one device")
    ref = KeyMap(VAL_WORDS)
    for b, (ok, probes, found, fv) in enumerate(outs4):
        sl = slice(b * per, (b + 1) * per)
        k = keys[sl].reshape(nranks, batch)
        check_failures(ok, probes)
        ref.add(k[ok], vals[sl].reshape(nranks, batch, VAL_WORDS)[ok])
        want_f, want_v = ref.lookup(k)
        check(np.array_equal(found, want_f), "mesh find flags")
        check(np.array_equal(fv, want_v), "mesh find values")
    say(f"mesh: {nbatches} insert+find batches over {len(devices)} devices",
        "equal one device and the reference")


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the mesh phase, over four chips")
    args = ap.parse_args(argv)

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {devs[0].platform}",
              file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    want = 4 if args.four_chips else 1
    if len(devs) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devs)}",
              file=sys.stderr)
        return 1
    say(f"device kind={devs[0].device_kind} count={len(devs)}",
        f"jax={jax.__version__} compile cache={cache_dir}")
    say(f"owner lane={'pallas' if kops.use_pallas_default() else 'xla'}")
    clock = CompileClock()
    rng = np.random.default_rng(args.seed)
    if args.four_chips:
        timed(clock, "mesh", phase_mesh, rng, devs[:4])
    else:
        say(f"sizes: ranks={P} slots per rank={NSLOTS}",
            f"keys per rank per batch={BATCH} load keys={LOAD_KEYS}")
        ht, ref = timed(clock, "a hashtable", phase_hashtable, rng)
        timed(clock, "b pipeline", phase_pipeline, rng, ht, ref)
        del ht
        timed(clock, "c queue", phase_queue, rng)
        timed(clock, "d txn", phase_txn)
        timed(clock, "e kernels", phase_kernels, rng)
        say(f"peak_bytes_in_use={peak_bytes()}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
