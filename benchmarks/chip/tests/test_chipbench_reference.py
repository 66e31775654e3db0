"""Each plain reference finds nothing wrong in a sound record and flags
one corrupted answer. Records are built here with numpy alone."""
import numpy as np

from chipbench_toy import ROOT  # noqa: F401  (puts the repo on sys.path)

from benchmarks.chip import traffic
from benchmarks.chip.reference import hashtable as ref_ht
from benchmarks.chip.reference import queue as ref_q

HT_CONFIG = {"structure": "hashtable", "nranks": 8, "nslots": 256,
             "val_words": 1, "max_probes": 8}
HT_MIX = {"structure": "hashtable", "pattern": ["insert"],
          "batch_per_rank": 8, "pool_batches": 2, "restart_each_pool": True,
          "preload_load": 0.25}
Q_CONFIG = {"structure": "queue", "nranks": 8, "host": 0, "capacity": 256,
            "val_words": 1}
Q_MIX = {"structure": "queue", "pattern": ["push", "pop"],
         "batch_per_rank": 4, "pool_batches": 4, "prefill": 64}


def _insert_seq(table, keys, vals, P, nslots, mp=8):
    """Sequential linear-probing insert into a (P, nslots, 3) numpy table;
    returns (ok, probes) as the program reports them."""
    owner, start = ref_ht.place(keys, P, nslots)
    ok = np.zeros(keys.shape, bool)
    probes = np.zeros(keys.shape, np.int32)
    for i in np.ndindex(keys.shape):
        for j in range(mp):
            s = (start[i] + j) % nslots
            probes[i] = j + 1
            if table[owner[i], s, 0] == 0:
                table[owner[i], s] = (2, keys[i], vals[i])
                ok[i] = True
                break
    return ok, probes


def _ht_record(find=False):
    mix = dict(HT_MIX)
    if find:
        mix.update(pattern=["find"], find_keys="uniform")
        mix.pop("restart_each_pool")
    plan = traffic.Plan(HT_CONFIG, mix, 7)
    P, ns = plan.nranks, HT_CONFIG["nslots"]
    table = np.zeros((P, ns, 3), np.int32)
    pk = traffic.key_np(np.arange(plan.n_pre), plan.key_base)
    pre_ok, pre_pr = _insert_seq(table, pk, traffic.value_np(pk), P, ns)
    rec = {"preload_ok": pre_ok, "preload_probes": pre_pr, "batches": [],
           "final": None, "find_idx": None}
    if find:
        rec["find_idx"] = plan.find_pool_indices()
        for s in range(plan.pool):
            keys = traffic.key_np(rec["find_idx"][s], plan.key_base)
            found = pre_ok[rec["find_idx"][s]]
            vals = np.where(found, traffic.value_np(keys), 0)[..., None]
            rec["batches"].append({"op": "find", "slot": s, "epoch": 0,
                                   "out": (found, vals)})
        rec["final"] = table.reshape(P, -1)
        return plan, rec
    start = table.copy()
    for ep in (0, 1):
        table = start.copy()
        for s in range(plan.pool):
            keys = traffic.key_np(plan.insert_index(s), plan.key_base)
            ok, pr = _insert_seq(table, keys, traffic.value_np(keys), P, ns)
            rec["batches"].append({"op": "insert", "slot": s, "epoch": ep,
                                   "out": (ok, pr)})
    rec["final"] = table.reshape(P, -1)
    return plan, rec


def test_hashtable_sound_record_passes():
    for find in (False, True):
        plan, rec = _ht_record(find)
        counts = ref_ht.check(plan, HT_CONFIG, rec)
        assert counts and all(v == 0 for v in counts.values()), counts


def test_hashtable_flags_one_lost_insert():
    plan, rec = _ht_record()
    b = rec["batches"][-1]
    ok, pr = b["out"]
    i = np.argwhere(ok)[0]
    keys = traffic.key_np(plan.insert_index(b["slot"]), plan.key_base)
    owner, start = ref_ht.place(keys, plan.nranks, HT_CONFIG["nslots"])
    slot = (start[tuple(i)] + pr[tuple(i)] - 1) % HT_CONFIG["nslots"]
    rec["final"] = rec["final"].copy()
    rec["final"][owner[tuple(i)], slot * 3 + 2] += 1     # the value word
    assert ref_ht.check(plan, HT_CONFIG, rec)["acked_lost"] == 1


def test_hashtable_counts_a_lost_preload_record():
    """A pre-loaded record gone from the table read back, as when an insert
    writes over it: the READY count is one short."""
    for find in (False, True):
        plan, rec = _ht_record(find)
        final = rec["final"].reshape(plan.nranks, -1, 3).copy()
        pk = traffic.key_np(np.arange(plan.n_pre), plan.key_base)
        o, s = np.argwhere(np.isin(final[..., 1], pk)
                           & (final[..., 0] == ref_ht.READY))[0]
        final[o, s] = 0
        rec["final"] = final.reshape(plan.nranks, -1)
        assert ref_ht.check(plan, HT_CONFIG, rec)["ready_gap"] == 1


def test_hashtable_flags_one_wrong_answer():
    plan, rec = _ht_record()
    ok, pr = rec["batches"][0]["out"]
    pr = pr.copy()
    pr[0, 0] += 1
    rec["batches"][0]["out"] = (ok, pr)
    assert ref_ht.check(plan, HT_CONFIG, rec)["epoch_differs"] == 1
    plan, rec = _ht_record(find=True)
    found, vals = rec["batches"][1]["out"]
    vals = vals.copy()
    vals[3, 2, 0] ^= 1
    rec["batches"][1]["out"] = (found, vals)
    assert ref_ht.check(plan, HT_CONFIG, rec)["find_wrong"] == 1


def _q_record():
    plan = traffic.Plan(Q_CONFIG, Q_MIX, 9)
    cap = Q_CONFIG["capacity"]
    fifo = list(traffic.qval_np(np.arange(plan.prefill), plan.queue_base))
    ring = np.zeros(4 + cap, np.int32)
    ring[4:4 + plan.prefill] = fifo
    head = 0
    tail = plan.prefill
    rec = {"prefill_ok": np.ones(plan.prefill, bool), "batches": []}
    for k in range(6):
        op, slot = plan.op(k), plan.slot(k)
        if op == "push":
            vals = traffic.qval_np(plan.push_seq(slot), plan.queue_base)
            for v in vals.reshape(-1):
                ring[4 + tail % cap] = v
                tail += 1
            fifo += list(vals.reshape(-1))
            out = (np.ones(vals.shape, bool),)
        else:
            n = plan.per_batch
            got = np.ones(n, bool)
            vals = np.array(fifo[head:head + n], np.int32)
            head += n
            out = (got.reshape(plan.nranks, -1),
                   vals.reshape(plan.nranks, -1, 1))
        rec["batches"].append({"op": op, "slot": slot, "epoch": 0,
                               "out": out})
    ring[:4] = (tail, tail, head, head)
    rec["final"] = ring
    return plan, rec


def test_queue_sound_record_passes():
    plan, rec = _q_record()
    counts = ref_q.check(plan, Q_CONFIG, rec)
    assert all(v == 0 for v in counts.values()), counts


def test_queue_flags_one_wrong_pop_and_state():
    plan, rec = _q_record()
    got, vals = rec["batches"][3]["out"]
    vals = vals.copy()
    vals[5, 1, 0] += 1
    rec["batches"][3]["out"] = (got, vals)
    assert ref_q.check(plan, Q_CONFIG, rec)["pop_wrong"] == 1
    plan, rec = _q_record()
    rec["final"] = rec["final"].copy()
    rec["final"][2] += 1                                 # head
    assert ref_q.check(plan, Q_CONFIG, rec)["state_wrong"] == 1
