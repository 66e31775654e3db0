"""Plain reference of the distributed hash table (paper §III-B1), numpy
only. Imports nothing of the program.

Placement, as the paper's table places a key: a 32-bit xorshift-multiply
mix h of the key, owner = h mod P, first slot = (h div P) mod nslots,
linear probing over at most `max_probes` slots within the owner's rows.
A record is [flag | key | value words]; flag 2 marks it READY.

What it checks, each an exact count whose limit is 0:
  acked_lost     an insert that returned ok is not READY at the slot its
                 probe count names, with its key and value, in the table
                 read back after the window;
  bad_failure    an insert that returned not-ok had not probed its whole
                 window, or the read-back table has an empty or same-key
                 slot in that window;
  epoch_differs  a batch repeated in a later epoch (same inputs, same
                 starting table) answered differently;
  preload_lost   of a sample of the pre-load drawn from the seed, a key
                 the pre-load acknowledged is not in its window with its
                 value, or a refused key is there or its window is not
                 full of other keys; or a refusal had not probed its
                 whole window;
  ready_gap      the READY records of the table read back differ in number
                 from the inserts acknowledged into it: the pre-load's and
                 those of the window's last epoch (an insert that wrote over
                 another key's record, or a record written twice, shows
                 here whichever key it hit);
  find_wrong     a find's flag or value differs from the map of
                 acknowledged keys.
"""
from __future__ import annotations

import numpy as np

from benchmarks.chip import traffic

READY, STATE_MASK = 2, 0xFF
PRELOAD_SAMPLE = 1 << 16


def _mix(keys):
    k = np.asarray(keys).astype(np.uint32)
    k = (k ^ (k >> np.uint32(16))) * np.uint32(0x85EBCA6B)
    k = (k ^ (k >> np.uint32(13))) * np.uint32(0xC2B2AE35)
    return k ^ (k >> np.uint32(16))


def place(keys, nranks: int, nslots: int):
    h = _mix(keys)
    owner = (h % np.uint32(nranks)).astype(np.int64)
    start = ((h // np.uint32(nranks)) % np.uint32(nslots)).astype(np.int64)
    return owner, start


class Table:
    """Read-only view of a read-back table, (P, nslots * rec_w) int32."""

    def __init__(self, data, nslots: int, val_words: int):
        self.rec_w = 2 + val_words
        self.vw = val_words
        self.nslots = nslots
        self.recs = np.asarray(data).reshape(data.shape[0], nslots,
                                             self.rec_w)

    def ready(self) -> int:
        """How many records are READY."""
        return int(np.count_nonzero((self.recs[..., 0] & STATE_MASK)
                                    == READY))

    def record(self, owner, slot):
        return self.recs[owner, slot % self.nslots]

    def holds(self, owner, slot, keys, vals):
        r = self.record(owner, slot)
        return (((r[..., 0] & STATE_MASK) == READY) & (r[..., 1] == keys)
                & np.all(r[..., 2:] == vals, axis=-1))

    def window_full_of_others(self, owner, start, keys, max_probes):
        """True where every slot of the key's probe window is taken by a
        record of another key."""
        full = np.ones(np.shape(keys), bool)
        for j in range(max_probes):
            r = self.record(owner, start + j)
            full &= ((r[..., 0] & STATE_MASK) != 0) & (r[..., 1] != keys)
        return full

    def find(self, owner, start, keys, max_probes):
        """(found, first value word) by a plain linear probe."""
        found = np.zeros(np.shape(keys), bool)
        val = np.zeros(np.shape(keys), np.int32)
        for j in range(max_probes):
            r = self.record(owner, start + j)
            hit = ~found & ((r[..., 0] & STATE_MASK) == READY) & \
                (r[..., 1] == keys)
            val = np.where(hit, r[..., 2], val)
            found |= hit
        return found, val


def _inserts(plan, config, batches, table, counts) -> int:
    """Checks every insert batch; returns how many inserts the table read
    back should hold (those acknowledged in the last epoch)."""
    P, nslots = plan.nranks, int(config["nslots"])
    mp = int(config["max_probes"])
    ins = [b for b in batches if b["op"] == "insert"]
    if not ins:
        return 0
    held = 0
    last_epoch = max(b["epoch"] for b in ins)
    last = {b["slot"]: b for b in ins if b["epoch"] == last_epoch}
    for b in ins:
        ok, probes = b["out"][0], b["out"][1]
        keys = traffic.key_np(plan.insert_index(b["slot"]), plan.key_base)
        if b["epoch"] != last_epoch:
            ref = last.get(b["slot"])
            if ref is not None:
                counts["epoch_differs"] += int(np.sum(
                    (ref["out"][0] != ok) | (ref["out"][1] != probes)))
            counts["bad_failure"] += int(np.sum(~ok & (probes != mp)))
            counts["acked_lost"] += int(np.sum(ok & ((probes < 1)
                                                     | (probes > mp))))
            continue
        held += int(np.sum(ok))
        owner, start = place(keys, P, nslots)
        vals = traffic.value_np(keys)[..., None]
        in_range = (probes >= 1) & (probes <= mp)
        at = table.holds(owner, start + np.clip(probes, 1, mp) - 1,
                         keys, vals)
        counts["acked_lost"] += int(np.sum(ok & ~(in_range & at)))
        full = table.window_full_of_others(owner, start, keys, mp)
        counts["bad_failure"] += int(np.sum(~ok & ~((probes == mp) & full)))
    return held


def check(plan, config, record) -> dict:
    """Counts of every violation in one run; each must be 0."""
    counts = {"acked_lost": 0, "bad_failure": 0, "epoch_differs": 0,
              "preload_lost": 0, "ready_gap": 0, "find_wrong": 0}
    P, nslots = plan.nranks, int(config["nslots"])
    mp = int(config["max_probes"])
    pre_ok = record["preload_ok"].reshape(-1)
    pre_probes = record["preload_probes"].reshape(-1)
    counts["preload_lost"] += int(np.sum(~pre_ok & (pre_probes != mp)))
    table = Table(record["final"], nslots, int(config["val_words"]))
    held = _inserts(plan, config, record["batches"], table, counts)
    counts["ready_gap"] = abs(table.ready() - int(pre_ok.sum()) - held)
    # a sample of the pre-load, looked up by the reference's own probe:
    # an acknowledged key is there with its value, a refused one is absent
    # and its window full of other keys
    rng = np.random.default_rng(np.random.SeedSequence([plan.seed, 2]))
    idx = rng.integers(0, plan.n_pre, PRELOAD_SAMPLE)
    keys = traffic.key_np(idx, plan.key_base)
    owner, start = place(keys, P, nslots)
    found, val = table.find(owner, start, keys, mp)
    acked = pre_ok[idx]
    full = table.window_full_of_others(owner, start, keys, mp)
    counts["preload_lost"] += int(np.sum(
        np.where(acked, ~found | (val != traffic.value_np(keys)),
                 found | ~full)))
    for b in record["batches"]:
        if b["op"] != "find":
            continue
        found, vals = b["out"]
        idx = record["find_idx"][b["slot"]]
        keys = traffic.key_np(idx, plan.key_base)
        want_found = pre_ok[idx]
        want_vals = np.where(want_found, traffic.value_np(keys), 0)
        counts["find_wrong"] += int(np.sum(
            (found != want_found) | (vals[..., 0] != want_vals)))
    return counts
