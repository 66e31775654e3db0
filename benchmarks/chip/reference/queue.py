"""Plain reference of the hosted circular queue (paper §III-B2), numpy
only. Imports nothing of the program.

A FIFO in ticket order: the values of acknowledged pushes, prefill first,
then each push batch in (rank, slot) order, come out of the pops in that
order, each once. Ring layout on the host rank: words 0-3 are tail,
tail_ready, head, head_ready; slot i of the data region starts at word
4 + (i mod capacity) * val_words.

What it checks, each an exact count whose limit is 0:
  pop_wrong       a pop's value differs from the reference FIFO's next
                  value, or a pop came back empty while the FIFO held one;
  push_refused    a push was refused while the ring had room;
  state_wrong     the control words or the backlog held in the ring,
                  read back after the window, differ from the FIFO's.
"""
from __future__ import annotations

import numpy as np

from benchmarks.chip import traffic

CTRL = 4


def check(plan, config, record) -> dict:
    counts = {"pop_wrong": 0, "push_refused": 0, "state_wrong": 0}
    cap, vw = int(config["capacity"]), int(config["val_words"])
    prefill = np.asarray(record["prefill_ok"]).astype(bool)
    fifo = [traffic.qval_np(np.arange(plan.prefill), plan.queue_base)[prefill]]
    counts["push_refused"] += int(np.sum(~prefill))
    pushed, n_popped = int(prefill.sum()), 0
    popped = []
    for b in record["batches"]:
        if b["op"] == "push":
            ok = b["out"][0].reshape(-1)
            vals = traffic.qval_np(plan.push_seq(b["slot"]), plan.queue_base)
            # a refusal is due only to pushes past the ring's room
            room = max(cap - (pushed - n_popped), 0)
            counts["push_refused"] += int(np.sum(~ok[:room]))
            fifo.append(vals.reshape(-1)[ok])
            pushed += int(ok.sum())
        else:
            got, vals = b["out"][0].reshape(-1), b["out"][1][..., 0].reshape(-1)
            want_got = np.arange(got.size) < pushed - n_popped
            counts["pop_wrong"] += int(np.sum(got != want_got))
            popped.append(vals[got])
            n_popped += int(got.sum())
    want = np.concatenate(fifo)
    got = np.concatenate(popped) if popped else np.zeros(0, np.int32)
    n = min(len(got), len(want))
    counts["pop_wrong"] += int(np.sum(got[:n] != want[:n])) + \
        (len(got) - n)
    final = record.get("final")
    if final is not None:
        tail, tail_ready, head, head_ready = (int(x) for x in final[:CTRL])
        n_pushed, n_popped = len(want), len(got)
        counts["state_wrong"] += int(tail != n_pushed) + \
            int(tail_ready != n_pushed) + int(head != n_popped) + \
            int(head_ready != n_popped)
        left = want[n_popped:]
        tickets = np.arange(n_popped, n_popped + len(left))
        words = CTRL + (tickets % cap)[:, None] * vw + np.arange(vw)
        counts["state_wrong"] += int(np.sum(
            np.asarray(final)[words][:, 0] != left))
    return counts
