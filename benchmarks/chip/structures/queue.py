"""Drives the program's hosted queue (`repro.core.queue`).

Set-up builds the ring on its host rank and pre-fills it with the
program's `queue.push_local` under `jax.jit`. The window calls the normal
front-ends, `queue.push` and `queue.pop`, with `backend="auto"` and an
`am.AMEngine`, so the default chooser picks the arm of every batch. The
seed's value base is an argument of every set-up program, so one
compiled program serves every seed. The ring lives on one chip: a cell
that asks for more is refused.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import traffic
from repro.core import adaptive, am, queue


class Driver:
    kind = "queue"

    def __init__(self, config: dict, plan: traffic.Plan, devices=None):
        if devices is not None and len(devices) > 1:
            raise ValueError(
                f"the queue driver runs on one chip, not {len(devices)}: "
                "pushers spread over chips are an open question (PERF.md "
                "section 7, the queue on 4 chips)")
        self.config, self.plan = config, plan
        self.P = plan.nranks
        self.engine = am.AMEngine(self.P)
        self.chooser = adaptive.default_engine(self.P,
                                               am_engine=self.engine)

    def build(self):
        """The ring, pre-filled with push sequence numbers 0 .. prefill-1.
        Returns the pre-fill's acks."""
        cfg = self.config
        q = queue.make_queue(self.P, int(cfg["host"]), int(cfg["capacity"]),
                             int(cfg["val_words"]))
        q, ok = jax.jit(self.fill, donate_argnums=0)(
            q, np.uint32(self.plan.queue_base))
        jax.block_until_ready(q)
        self.state = q
        return ok

    def fill(self, q, queue_base):
        """The ring `q` with the prefill pushed on its host rank."""
        seq = jnp.arange(self.plan.prefill, dtype=jnp.int32)
        return queue.push_local(q, traffic.qval_jnp(seq, queue_base)[:, None])

    @staticmethod
    def push_pool(seq, queue_base):
        """Values of the push batches with sequence numbers `seq`."""
        return tuple(traffic.qval_jnp(seq, queue_base)[..., None])

    def make_pools(self):
        plan = self.plan
        seq = np.stack([plan.push_seq(s) for s in range(plan.pool)])
        self.pools = {"push": jax.jit(self.push_pool)(
            jnp.asarray(seq, jnp.int32), np.uint32(plan.queue_base))}
        jax.block_until_ready(self.pools)

    def issue(self, op: str, slot: int, **kw):
        """One front-end call. Returns (ops issued, device outputs);
        the outputs' first entry is the new state."""
        if op == "push":
            out = queue.push(self.state, self.pools["push"][slot],
                             engine=self.engine, **kw)
        elif op == "pop":
            out = queue.pop(self.state, self.plan.batch, engine=self.engine,
                            **kw)
        else:
            raise ValueError(f"queue has no op {op!r}")
        return self.plan.per_batch, out

    def accept(self, out):
        self.state = out[0]
        return out[1:]

    def restore(self):
        raise ValueError("the queue keeps its backlog; it has no epochs")

    def tally(self, op, outs, counters):
        """Return how many of the batch's ops failed: pushes refused and
        pops that came back empty."""
        return int((~outs[0]).sum())

    def record(self, built):
        """The prefill's acks and the host rank's row read back."""
        return {"prefill_ok": np.asarray(built),
                "final": np.asarray(self.state.win.data[self.state.host])}

    def arms(self):
        return [d.arm for d in self.chooser.log]

    def free(self):
        self.state = self.pools = None
