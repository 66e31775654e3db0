"""Adaptive hybrid backend: cost-model-driven RDMA/RPC arm selection per
batch (DESIGN.md §4).

The paper's punchline is not that RDMA always wins — it is that the
analytical model *orders* the implementations correctly, "allowing us to
choose the best implementation" (§VI). This module operationalizes that at
runtime: every data-structure op batch (hash-table insert/find, queue
push/pop) picks one of four *arms*

    rdma        seed per-component one-sided engine (fused=False/planned=False)
    rdma_fused  planned + fused-descriptor one-sided engine (DESIGN.md §2)
    am          aggregated active messages
    am_pt       active messages serviced by a progress thread (Fig. 6 "PT")

driven by `costmodel.predict_arm` over calibrated ComponentCosts plus three
online signals the engine maintains itself:

  * an EWMA of measured per-batch latency per (op, arm), fed back from the
    engine's own timed executions or from `benchmarks/components.py`-style
    probes (`observe`) — measured numbers on THIS host dominate the model
    prior once available;
  * a batch *skew statistic* (max owner load / mean owner load, computed
    host-side from the route destinations — the same histogram
    `routing.owner_loads` derives from a RoutePlan's occupancy): high skew
    serializes RDMA atomics in one owner's apply lane while AM aggregation
    amortizes the round trip, so skew tilts the model toward the AM arms;
  * a batch *dedup ratio* (unique key rows / total rows, `batch_dedup` —
    the statistic `routing.Coalescing.dedup_ratio()` measures on the
    wire): duplicate traffic (dedup < 1) turns sender-side coalescing on
    for the fused/AM arms and prices them with the distinct-row factor
    (DESIGN.md §6) — hot-key batches collapse toward O(distinct) wire
    rows, tilting the model back toward the coalesced one-sided arms.

Every choice is recorded as a `Decision` in `AdaptiveEngine.log`; the RDMA
arms run inside `window.decision_scope`, so their phases are attributed
to the arm that issued them.

Under `jax.jit` tracing the destinations are abstract: the skew falls back
to 1.0, timing is skipped, and the decision degrades to the pure
(deterministic) cost-model choice — safe to stage.
"""
from __future__ import annotations

import collections
import time
from dataclasses import dataclass, replace
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import costmodel as cm
from . import faults as flt
from . import trace
from .costmodel import ARMS, ComponentCosts, DSOp
from .types import OpStats, Promise, device_wait, host_value


@dataclass(frozen=True)
class Decision:
    """One per-batch backend choice — the record kept in
    `AdaptiveEngine.log` and tagged on phases by `window.decision_scope`."""

    op: DSOp
    promise: Promise
    arm: str                      # one of costmodel.ARMS
    skew: float                   # batch owner-load skew (1.0 if unknown)
    scores: Dict[str, float]      # per-arm score (µs/op) the choice used
    source: str                   # "model" | "ewma" | "mixed" | "forced" | ...
    batch_ops: int                # valid ops in the batch (0 if traced)
    dedup: float = 1.0            # distinct-row fraction (1.0 if unknown);
                                  # < 1 turns sender-side coalescing on for
                                  # the fused/AM arms (DESIGN.md §6)
    coalesce: bool = False        # the executed arm ran with coalescing
    cached: bool = False          # the executed arm consulted the
                                  # hot-bucket cache (DESIGN.md §8)
    hit_rate: float = 0.0         # hit-rate EWMA the scores were priced
                                  # with (the fourth online signal)
    depth: int = 1                # pipeline depth the batch runs at
                                  # (DESIGN.md §9): 1 for the synchronous
                                  # front-ends; the async front-ends stamp
                                  # the chooser-picked (or pipe-configured)
                                  # window count here
    quarantined: bool = False     # the cost choice was an AM arm but the
                                  # batch targets a quarantined owner, so
                                  # the decision re-routed to a one-sided
                                  # arm (DESIGN.md §10 graceful
                                  # degradation; source == "quarantine")


def batch_dedup(keys, valid=None) -> float:
    """Distinct-row fraction of a batch: unique key rows / total rows —
    the third online signal (DESIGN.md §6), computed host-side like
    `batch_skew`. Mirrors `routing.Coalescing.dedup_ratio()` for batches
    whose duplicate identity is the key (the hash-table front-ends: equal
    keys place identically, so they share (owner, offset) every probe).
    Returns 1.0 (no duplicates assumed) when `keys` is a tracer."""
    k = host_value(keys)
    if k is None:
        return 1.0
    v = host_value(valid)
    flat = k.ravel() if v is None else k[v.astype(bool)].ravel()
    if flat.size == 0:
        return 1.0
    return float(np.unique(flat).size / flat.size)


def batch_skew(dst, nranks: int, valid=None) -> float:
    """Max owner load / mean owner load over all `nranks` owners.

    1.0 = perfectly uniform, `nranks` = single hot owner. Computed
    host-side with a bincount — the same statistic `routing.plan_skew`
    derives from a RoutePlan's exchanged occupancy mask, without paying the
    plan's occupancy exchange. Returns 1.0 when `dst` is a tracer."""
    d = host_value(dst)
    if d is None:
        return 1.0
    v = host_value(valid)
    flat = d.ravel() if v is None else d[v.astype(bool)].ravel()
    if flat.size == 0:
        return 1.0
    counts = np.bincount(flat, minlength=nranks)
    return float(counts.max() * nranks / counts.sum())


class AdaptiveEngine:
    """Per-batch arm chooser + data-structure front-end wrappers.

    am_engine:  AMEngine servicing the `am` / `am_pt` arms (those arms are
                disabled when absent). Handlers are auto-registered against
                the first structure each wrapper sees (one AMEngine per
                structure, as in `am.AMEngine`).
    params:     ComponentCosts prior for the model scores; `calibrate()`
                replaces it with measured component latencies.
    alpha:      EWMA step for observed per-op latencies.
    policy:     "cost" (argmin score, default) or "round_robin"
                (deterministically cycle arms — conformance testing).
    measure:    time each executed batch and feed the EWMA (forces a device
                sync per op batch; library call sites keep it off).
    explore_every: when > 0, a "cost" decision probes the runner-up arm
                instead of the winner whenever the runner-up's EWMA has not
                been refreshed for this many decisions of the same op —
                bounded-cost exploration that prevents a single bad
                measurement from starving an arm forever. A clear loser
                (score > 2x the winner's) is refreshed at a quarter of
                that rate: probing it buys little information and its
                full cost is charged to the stream.
    hysteresis: relative margin under which a decision STICKS with the
                op's incumbent arm when both its and the winner's scores
                are measured EWMAs. Measured scores carry wall-clock
                noise; without a dead band the argmin flip-flops between
                near-equal arms and every flip executes the (slightly)
                losing one — the median-regret creep of ISSUE 8. Model
                scores are deterministic, so the band never applies to
                them and the model-driven pins are unaffected.
    cache:      optional core/cache.BucketCache (DESIGN.md §8). Explicit
                opt-in, NEVER auto-created: the default engines are shared
                per-nranks across every table, and a cache is coherent for
                exactly one table (its writes flow through this engine's
                `ht_insert`). When attached, CR finds on the fused
                one-sided arm consult it, the observed hit rate feeds a
                fourth online signal (`hit_ewma`, priced via
                OpStats.hit_rate), and a write-fraction EWMA
                (`write_ewma`) disables cache READS under write-heavy
                streams — invalidation always stays on (correctness).
    """

    #: write-fraction EWMA above which cache reads are suspended: at ≥50%
    #: writes the probe-window invalidations churn faster than fills
    #: repopulate, so the lookup is pure overhead.
    WRITE_HEAVY = 0.5

    def __init__(self, nranks: int, am_engine=None,
                 params: ComponentCosts = cm.TPU_V5E_ICI,
                 alpha: float = 0.25, arms: Optional[Tuple[str, ...]] = None,
                 policy: str = "cost", measure: bool = False,
                 explore_every: int = 0, cache=None,
                 hysteresis: float = 0.10):
        if arms is None:
            arms = ARMS if am_engine is not None else ("rdma", "rdma_fused")
        for a in arms:
            if a not in ARMS:
                raise ValueError(f"unknown arm {a!r}")
            if a in ("am", "am_pt") and am_engine is None:
                raise ValueError(f"arm {a!r} needs an am_engine")
        if policy not in ("cost", "round_robin"):
            raise ValueError(f"unknown policy {policy!r}")
        self.nranks = nranks
        self.am_engine = am_engine
        self.params = params
        self.alpha = alpha
        self.arms = tuple(arms)
        self.policy = policy
        self.measure = measure
        self.explore_every = explore_every
        self.hysteresis = hysteresis
        self.force_arm: Optional[str] = None
        self.cache = cache
        self.hit_ewma = 0.0    # observed cache hit rate (4th online signal)
        self.write_ewma = 0.0  # observed write fraction of the op stream
        # sixth online signal (DESIGN.md §10): per-owner fault-pressure
        # EWMA in [0, 1] (0 = healthy), fed by the fault plane's per-owner
        # retry/unserviced counters and by the straggler monitor bridge;
        # owners past QUARANTINE_ON are quarantined — their AM traffic
        # re-routes to the one-sided arms, which need no owner attention
        self.health: Dict[int, float] = {}
        self.quarantined: set = set()
        # measured loss EWMA (retransmits / transmissions) — folded into
        # OpStats.loss_rate so predict_arm prices arms under the observed
        # fault rate (costmodel retry_penalty term)
        self.loss_ewma = 0.0
        # measured txn abort EWMA (aborts / attempts, DESIGN.md §11) —
        # folded into OpStats.abort_rate so predict_arm prices DSOp.TXN
        # batches with the ar/(1-ar) retry-round multiplier. Fed by
        # `ingest_txn_stats` after every TxnEngine.run.
        self.abort_ewma = 0.0
        self.ewma: Dict[Tuple[DSOp, str], float] = {}
        # fifth online signal (DESIGN.md §9): observed per-op batch latency
        # per (op, depth) — overlays the predict_pipelined prior in
        # choose_depth the same way `ewma` overlays predict_arm
        self.depth_ewma: Dict[Tuple[DSOp, int], float] = {}
        # bounded ring: the default AUTO front-ends log every batch here
        # and nothing drains it
        self.log: collections.deque = collections.deque(maxlen=4096)
        self.last_decision: Optional[Decision] = None
        self._rr = 0
        self._op_count: Dict[DSOp, int] = {}        # decisions per op
        self._seen: Dict[Tuple[DSOp, str], int] = {}  # last observe tick
        self._last_arm: Dict[DSOp, str] = {}        # hysteresis incumbent

    # -- signals ------------------------------------------------------------
    def calibrate(self, measured: Dict[str, float]) -> ComponentCosts:
        """Replace the model prior with measured component latencies
        (benchmarks/components.py row dict)."""
        self.params = cm.calibrate(measured, base=self.params)
        return self.params

    #: a single observation may exceed the arm's EWMA by at most this
    #: factor before it is clipped: a contended-host spike (the usual CI
    #: artifact) otherwise inflates the winner's EWMA in one step and the
    #: argmin flips to a genuinely slower arm for the several batches the
    #: EWMA needs to recover. A real slowdown still gets through — each
    #: clipped update raises the EWMA by alpha * (CLIP - 1) * prev, so a
    #: few consecutive slow batches reprice the arm anyway.
    OBSERVE_CLIP = 4.0

    def observe(self, decision: Decision, us_per_op: float) -> None:
        """EWMA-update the measured latency of (op, arm)."""
        key = (decision.op, decision.arm)
        prev = self.ewma.get(key)
        if prev is not None:
            us_per_op = min(us_per_op, self.OBSERVE_CLIP * prev)
        self.ewma[key] = (us_per_op if prev is None
                          else prev + self.alpha * (us_per_op - prev))
        self._seen[key] = self._op_count.get(decision.op, 0)
        if decision.depth > 1 or (decision.op, decision.depth) \
                in self.depth_ewma:
            self.observe_depth(decision.op, decision.depth, us_per_op)

    def observe_depth(self, op: DSOp, depth: int, us_per_op: float) -> None:
        """EWMA-update the measured per-op latency of (op, depth) — the
        fifth online signal. Fed by the pipelined benches and by `observe`
        whenever a Decision carries a depth; `choose_depth` prefers these
        measured numbers over the predict_pipelined prior."""
        key = (op, max(1, int(depth)))
        prev = self.depth_ewma.get(key)
        self.depth_ewma[key] = (us_per_op if prev is None
                                else prev + self.alpha * (us_per_op - prev))

    def attach_cache(self, cache) -> None:
        """Attach a hot-bucket cache (DESIGN.md §8). One cache per table:
        coherence holds only for writes issued through THIS engine."""
        self.cache = cache

    def cache_reads_on(self) -> bool:
        """Whether CR finds should consult the cache right now: one is
        attached, enabled, and the stream is not write-heavy (the chooser
        disables reads — not invalidation — past WRITE_HEAVY, where the
        probe-window version churn outruns the fills)."""
        return (self.cache is not None and self.cache.enabled
                and self.write_ewma < self.WRITE_HEAVY)

    def _observe_rw(self, is_write: bool) -> None:
        self.write_ewma += self.alpha * (float(is_write) - self.write_ewma)

    # -- owner health (sixth online signal, DESIGN.md §10) ------------------
    #: health EWMA at/above which an owner is quarantined; released once
    #: the EWMA decays below half of it (a hysteresis band, like the arm
    #: chooser's — flapping in and out of quarantine re-routes traffic for
    #: no information gain)
    QUARANTINE_ON = 0.5

    def quarantine(self, rank: int, pressure: float = 1.0) -> None:
        """Mark `rank` unhealthy (health EWMA raised to >= `pressure`);
        at QUARANTINE_ON or above its AM traffic re-routes one-sided."""
        self.health[rank] = max(self.health.get(rank, 0.0), float(pressure))
        if self.health[rank] >= self.QUARANTINE_ON:
            self.quarantined.add(rank)

    def ingest_fault_stats(self, plane) -> None:
        """Fold the fault plane's per-owner counters into the health EWMA.

        Pressure per owner = (unserviced + 0.25 * retries) / rows, clamped
        to [0, 1] — a fully dead owner scores 1.0 and, because the first
        sample seeds the EWMA directly, is quarantined after ONE batch.
        Also refreshes the measured loss EWMA (retransmits over total
        transmissions) that prices arms via OpStats.loss_rate."""
        taken = plane.take_owner_stats()
        if not taken:
            return
        for r, st in taken.items():
            rows = max(1, st["rows"])
            pressure = min(1.0, (st["unserviced"] + 0.25 * st["retries"])
                           / rows)
            prev = self.health.get(r)
            h = (pressure if prev is None
                 else prev + self.alpha * (pressure - prev))
            self.health[r] = h
            if h >= self.QUARANTINE_ON:
                self.quarantined.add(r)
            elif h < self.QUARANTINE_ON / 2:
                self.quarantined.discard(r)
        rows = sum(st["rows"] for st in taken.values())
        ret = sum(st["retries"] for st in taken.values())
        lr = ret / max(1, rows + ret)
        self.loss_ewma = (lr if self.loss_ewma == 0.0
                          else self.loss_ewma
                          + self.alpha * (lr - self.loss_ewma))

    def ingest_txn_stats(self, commits: int, aborts: int) -> None:
        """Fold one TxnEngine.run's commit/abort counts into the abort-rate
        EWMA (DESIGN.md §11). Seeds on first observation like `loss_ewma`
        so a single contended run immediately reprices the txn arms."""
        attempts = commits + aborts
        if attempts <= 0:
            return
        ar = aborts / attempts
        self.abort_ewma = (ar if self.abort_ewma == 0.0
                           else self.abort_ewma
                           + self.alpha * (ar - self.abort_ewma))

    def quarantine_from_monitor(self, classes: Dict[int, str],
                                ranks_per_host: int = 1) -> None:
        """Bridge `runtime/straggler.StragglerMonitor.classify()` verdicts
        into the health signal: a slow/replace/dead host marks its ranks
        quarantined (their AM traffic re-routes to the one-sided arms,
        which a distracted or dead host CPU cannot stall); a healthy
        verdict decays the rank back toward release. Host h owns ranks
        [h * ranks_per_host, (h + 1) * ranks_per_host)."""
        severity = {"dead": 1.0, "replace": 0.9, "slow": 0.6}
        for host, cls in classes.items():
            for r in range(host * ranks_per_host,
                           (host + 1) * ranks_per_host):
                if not 0 <= r < self.nranks:
                    continue
                if cls in severity:
                    self.quarantine(r, severity[cls])
                elif r in self.health:
                    h = (1.0 - self.alpha) * self.health[r]
                    self.health[r] = h
                    if h < self.QUARANTINE_ON / 2:
                        self.quarantined.discard(r)

    def _after_am(self) -> Optional[np.ndarray]:
        """Post-execution fault bookkeeping: with a fault plane in scope,
        ingest its per-owner pressure and return the last AM dispatch's
        unserviced-row mask (None when everything was serviced) so the
        wrapper can fail those rows over to the one-sided lane."""
        plane = flt.active_plane()
        if plane is None:
            return None
        uns = plane.take_unserviced()
        self.ingest_fault_stats(plane)
        return uns

    def _fault_stats(self, s: OpStats) -> OpStats:
        """Fold the measured loss EWMA into OpStats.loss_rate (pre-set
        values win) so predict_arm prices arms under the observed fault
        rate — the §10 retry term. The txn abort EWMA folds the same way
        into OpStats.abort_rate (the §11 contention term)."""
        if self.loss_ewma > 0.0 and s.loss_rate == 0.0:
            s = replace(s, loss_rate=min(0.95, self.loss_ewma))
        if self.abort_ewma > 0.0 and s.abort_rate == 0.0:
            s = replace(s, abort_rate=min(0.95, self.abort_ewma))
        return s

    # -- decision -----------------------------------------------------------
    def scores(self, op: DSOp, promise: Promise,
               stats: Optional[OpStats] = None,
               skew: Optional[float] = None) -> Tuple[Dict[str, float], str]:
        """Per-arm score in µs/op: the measured EWMA when one exists for
        (op, arm), else the cost-model prediction. Returns (scores, source)
        with source describing which inputs were used. `skew` (when given)
        overrides stats.skew for the model predictions — `decide` passes
        the host-computed batch skew this way so the OpStats fold is paid
        only on the model path."""
        stats = self._fault_stats(stats or OpStats())
        ew = self.ewma
        out = {}
        for arm in self.arms:
            v = ew.get((op, arm))
            if v is None:
                break
            out[arm] = v
        else:
            # fast path: every arm measured — no OpStats folding, no model
            # evaluation. decide() sits on the application's critical path
            # (charged per batch by the §4 regret accounting), and in the
            # steady state this is the only path taken.
            return out, "ewma"
        s = stats or OpStats()
        if skew is not None and skew != s.skew:
            s = replace(s, skew=skew)
        if s.nranks == 0:
            s = replace(s, nranks=self.nranks)
        out, used = {}, set()
        for arm in self.arms:
            v = ew.get((op, arm))
            if v is not None:
                out[arm] = v
                used.add("ewma")
            else:
                out[arm] = cm.predict_arm(op, promise, arm, s, self.params)
                used.add("model")
        return out, ("mixed" if len(used) > 1 else used.pop())

    def peek_arm(self, op: DSOp, promise: Promise,
                 stats: Optional[OpStats] = None) -> str:
        """The arm `decide` WOULD pick for this (op, promise, stats) —
        without logging a Decision, advancing the round-robin cursor, or
        consuming an exploration probe.

        The async front-ends (hashtable.insert_async & friends, DESIGN.md
        §7) call this at submit time to route AM-arm batches through the
        deferred-dispatch queue (`Pipeline.submit(deferred=True)`); the
        authoritative, logged decision still happens when the batch
        stages. A peek/stage mismatch (an EWMA update landing in between)
        is harmless — deferral only moves WHEN the batch stages, never
        which arm runs it."""
        if self.force_arm is not None:
            return self.force_arm
        if self.policy == "round_robin":
            return self.arms[self._rr % len(self.arms)]
        scores, _ = self.scores(op, promise, stats)
        return self._cost_choice(op, scores)[0]

    # tie-break toward the cheaper-at-runtime engine: the planned + fused
    # arm strictly dominates the seed arm at equal predicted cost (the
    # queue has no fused formula, so they tie there)
    _ARM_RANK = {"rdma_fused": 0, "am": 1, "am_pt": 2, "rdma": 3}

    def _cost_choice(self, op: DSOp, scores: Dict[str, float]):
        """(arm, ranked arms) under the "cost" policy: argmin score with a
        hysteresis dead band — when the incumbent's and the winner's
        scores are BOTH measured EWMAs and the incumbent is within
        `hysteresis` of the winner, keep the incumbent (measured numbers
        jitter; flipping inside the noise band just executes the loser).
        Model scores are deterministic, so they never engage the band."""
        ranked = sorted(scores, key=lambda a: (scores[a], self._ARM_RANK[a]))
        arm = ranked[0]
        last = self._last_arm.get(op)
        if (last is not None and last != arm and last in scores
                and (op, last) in self.ewma and (op, arm) in self.ewma
                and scores[last] <= scores[arm] * (1.0 + self.hysteresis)):
            arm = last
        return arm, ranked

    def choose_depth(self, op: DSOp, promise: Promise,
                     stats: Optional[OpStats] = None,
                     arm: Optional[str] = None,
                     max_depth: Optional[int] = None) -> int:
        """Pipeline depth the engine recommends for this (op, promise,
        stats) — the §9 auto-depth decision. Model prior: argmin of
        `predict_pipelined` over `costmodel.DEPTH_CANDIDATES` for the arm
        `peek_arm` would run (or the given one). Measured overlay: any
        (op, depth) latency recorded via `observe_depth` (the fifth online
        signal) replaces the model's number for that depth, so one bad
        depth — e.g. the depth-4 queueing regression — is learned from a
        single measured sweep even when the calibrated
        `pipe_depth_overhead` underprices it. Ties break toward the
        SHALLOWEST depth (extra windows are never free). Like `peek_arm`,
        this logs nothing — the Decision that records the depth is cut at
        stage time."""
        s = stats or OpStats()
        if s.nranks == 0:
            s = replace(s, nranks=self.nranks)
        if arm is None:
            arm = self.peek_arm(op, promise, s)
        cands = [d for d in sorted(set(int(x) for x in cm.DEPTH_CANDIDATES))
                 if d >= 1 and (max_depth is None or d <= max_depth)]
        model = {d: cm.predict_pipelined(op, promise, arm, s, self.params,
                                         depth=d) for d in cands}
        obs = {d: self.depth_ewma[(op, d)] for d in cands
               if (op, d) in self.depth_ewma}
        # Measured numbers carry host overheads the model doesn't, so an
        # unobserved depth cannot compete on the raw model scale — anchor
        # it by the mean measured/model ratio of the observed depths (the
        # calibration-transfer idiom) before comparing.
        factor = 1.0
        if obs:
            ratios = [obs[d] / model[d] for d in obs if model[d] > 0.0]
            if ratios:
                factor = sum(ratios) / len(ratios)
        best_d, best_t = 1, float("inf")
        for d in cands:
            t = obs.get(d, model[d] * factor)
            if t < best_t - 1e-9:
                best_d, best_t = d, t
        return best_d

    def auto_depth(self, pipe, op: DSOp, promise: Promise,
                   stats: Optional[OpStats] = None) -> OpStats:
        """Submit-time §9 hook shared by the async front-ends: when `pipe`
        opted into auto-depth, pick the window count via `choose_depth`,
        retarget the pipeline (`Pipeline.set_depth`, capped at the pipe's
        constructor depth), and return the stats priced at the chosen
        depth — so the stage-time Decision records `depth` faithfully.
        A fixed-depth pipeline passes through untouched."""
        s = stats or OpStats()
        if not getattr(pipe, "auto_depth", False):
            return s
        d = self.choose_depth(op, promise, s, max_depth=pipe.max_depth)
        pipe.set_depth(d)
        return replace(s, pipeline_depth=d)

    def decide(self, op: DSOp, promise: Promise, dst=None, valid=None,
               stats: Optional[OpStats] = None,
               nops: Optional[int] = None,
               owners: Optional[Tuple[int, ...]] = None) -> Decision:
        """Choose the arm for one batch. `dst` (P, n) feeds the skew
        statistic (skipped when `stats.skew` is already set — e.g. the
        hosted queue's skew is `nranks` by construction, no device read
        needed); `stats` carries the remaining workload signals
        (expected_probes, target_busy_us, ...). `owners`, when given, is
        the static owner set the batch targets (the hosted queue passes
        `(q.host,)`) — used for the §10 quarantine test without reading
        `dst` off the device."""
        s = stats or OpStats()
        skew = s.skew
        # the skew statistic feeds the MODEL's owner-serialization term;
        # once every arm has a measured EWMA the decision never reads it,
        # so the host-side bincount (the single largest decide() cost —
        # this sits on the application's per-batch critical path) is
        # computed only when some arm still needs a model price. Pure-EWMA
        # decisions record the caller's stats.skew as-is.
        ewma_complete = all((op, a) in self.ewma for a in self.arms)
        if not ewma_complete and dst is not None and skew == 1.0:
            skew = batch_skew(dst, self.nranks, valid)
        dedup = s.dedup
        if nops is None:
            v = host_value(valid)
            if v is not None:
                nops = int(v.sum())
            elif dst is not None and not isinstance(dst, jax.core.Tracer):
                # static shape — never materialize dst here: on the §7
                # staging path that would serialize batch k+1 behind
                # batch k's in-flight device work. Traced batches keep
                # the documented batch_ops == 0 sentinel.
                nops = int(dst.size)
            else:
                nops = 0
        scores, source = self.scores(op, promise, s, skew=skew)
        tick = self._op_count.get(op, 0) + 1
        self._op_count[op] = tick
        if self.force_arm is not None:
            arm, source = self.force_arm, "forced"
        elif self.policy == "round_robin":
            arm = self.arms[self._rr % len(self.arms)]
            self._rr += 1
            source = "round_robin"
        else:
            arm, ranked = self._cost_choice(op, scores)
            self._last_arm[op] = arm
            if self.explore_every > 0 and len(ranked) > 1:
                runner = ranked[1] if ranked[0] == arm else ranked[0]
                need = self.explore_every
                if scores[runner] > 2.0 * scores[arm]:
                    # clear loser: its full cost is charged to the stream
                    # and one probe per explore_every buys almost no
                    # information — refresh it at a quarter of the rate
                    need *= 4
                if tick - self._seen.get((op, runner), 0) >= need:
                    arm, source = runner, "explore"
                    # mark the probe attempt NOW: if the caller never
                    # observes a latency, the staleness clock still resets
                    # and exploration stays bounded at 1/explore_every
                    # instead of locking onto the runner-up forever
                    self._seen[(op, runner)] = tick
        # §10 graceful degradation: an AM arm needs the owner's CPU to
        # reach a dispatch point, and a quarantined owner's won't (dead or
        # chronically inattentive). Re-route the batch to the cheapest
        # non-AM arm — the one-sided lane needs only the target NIC, which
        # the fault model keeps live. `force_arm` is exempt (conformance
        # tests pin arms on purpose); tracer batches can't be hit-tested
        # and fall through to the AM-side unserviced failover instead.
        quarantined_flag = False
        if (self.quarantined and source != "forced"
                and arm in ("am", "am_pt")):
            if owners is not None:
                hit = any(int(r) in self.quarantined for r in owners)
            else:
                d = host_value(dst)
                hit = False
                if d is not None:
                    v = host_value(valid)
                    flat = (d.ravel() if v is None
                            else d[v.astype(bool)].ravel())
                    hit = bool(np.isin(
                        flat, np.fromiter(self.quarantined,
                                          dtype=np.int64)).any())
            if hit:
                cands = [a for a in scores if a not in ("am", "am_pt")]
                if cands:
                    arm = min(cands,
                              key=lambda a: (scores[a], self._ARM_RANK[a]))
                    source = "quarantine"
                    quarantined_flag = True
                    self._last_arm[op] = arm
        dec = Decision(op=op, promise=promise, arm=arm, skew=skew,
                       scores=scores, source=source, batch_ops=nops,
                       dedup=dedup,
                       coalesce=cm.arm_coalesces(op, arm, dedup),
                       cached=(self.cache_reads_on()
                               and cm.arm_caches(op, promise, arm)),
                       hit_rate=s.hit_rate,
                       depth=max(1, int(s.pipeline_depth)),
                       quarantined=quarantined_flag)
        self.log.append(dec)
        self.last_decision = dec
        return dec

    # -- execution helpers --------------------------------------------------
    def _timed(self, dec: Decision, fn):
        """Run fn(), feeding the EWMA when measuring is on and the batch is
        concrete. am_pt accounts the progress-thread contention factor on
        top of the measured dispatch (the Fig. 6 "PT" accounting, as in
        benchmarks/attentiveness.py)."""
        if not (self.measure and dec.batch_ops):
            return fn()
        t0 = time.perf_counter()
        out = fn()
        if any(isinstance(x, jax.core.Tracer)
               for x in jax.tree_util.tree_leaves(out)):
            return out  # traced values: skip the observation
        device_wait(out)
        us = (time.perf_counter() - t0) * 1e6 / dec.batch_ops
        if dec.arm == "am_pt":
            us *= self.params.pt_overhead
        self.observe(dec, us)
        return out

    def _host_stats(self, stats: Optional[OpStats]) -> OpStats:
        """Stats for a hosted (single-owner) structure: every op targets
        the host rank, so the skew is `nranks` by construction — no
        destination array needs to leave the device to know it."""
        s = stats or OpStats()
        return s if s.skew != 1.0 else replace(s, skew=float(self.nranks))

    def _need_am(self, name: str, register):
        eng = self.am_engine
        assert eng is not None
        if name not in eng._handlers:
            register(eng)
        return eng

    # -- data-structure wrappers -------------------------------------------
    def _ht_stats(self, keys, valid, stats: Optional[OpStats]) -> OpStats:
        """Hash-table batch stats: fold the observed dedup ratio (unique
        keys / total — the third online signal, DESIGN.md §6) into the
        workload stats; pre-set `stats.dedup` to skip the host read."""
        s = stats or OpStats()
        if s.dedup == 1.0:
            s = replace(s, dedup=batch_dedup(keys, valid))
        return s

    def ht_insert(self, ht, keys, vals, promise: Promise = Promise.CRW,
                  valid=None, max_probes: int = 8,
                  stats: Optional[OpStats] = None):
        """Adaptive hash-table insert: returns (table', ok, probes).

        The skew statistic reads the batch's owner placement on the host
        (one device read per batch); pre-set `stats.skew` to skip it.
        Duplicate-key batches (dedup < 1) run the fused/AM arms with
        sender-side coalescing on. With a cache attached (DESIGN.md §8)
        every insert — ANY arm, the AM insert-or-assign included — bumps
        the probe-window versions of its keys BEFORE executing, so stale
        cached records can never be served after this call returns."""
        from . import hashtable as ht_mod
        from . import window as win_mod
        dst, _ = ht_mod._place(ht, keys)
        with trace.span("chooser.decide"):
            dec = self.decide(DSOp.HT_INSERT, promise, dst, valid,
                              self._ht_stats(keys, valid, stats))
        self._observe_rw(is_write=True)
        if self.cache is not None:
            # authoritative invalidation: versions bump before any write
            # lands, so a racing deferred fill tick-mismatches and drops
            self.cache.on_insert_keys(keys, valid, max_probes)
        if dec.arm in ("am", "am_pt"):
            eng = self._need_am(
                "ht_insert",
                lambda e: ht_mod.build_am_handlers(ht, e,
                                                   max_probes=max_probes))
            ht2, ok, probes = self._timed(dec, lambda: ht_mod.insert_rpc(
                ht, eng, keys, vals, valid=valid, coalesce=dec.coalesce))
            uns = self._after_am()
            if uns is not None:
                # §10 failover: rows whose owner never serviced the AM
                # (dead/stalled) land via the one-sided lane — the target
                # NIC stays live even when the host CPU is inattentive.
                # All of a dead owner's rows move together, so per-owner
                # apply order is preserved and the result matches the
                # fault-free oracle.
                m = jnp.asarray(uns)
                rv = m if valid is None else (valid & m)
                with win_mod.decision_scope(dec), \
                        win_mod.cache_scope(self.cache):
                    # coalesce rides along: duplicate keys in the subset
                    # must collapse to ONE record, exactly as the AM
                    # insert-or-assign handler would have applied them
                    ht2, ok2, pr2 = ht_mod.insert_rdma(
                        ht2, keys, vals, promise=promise, valid=rv,
                        max_probes=max_probes, fused=True,
                        coalesce=dec.coalesce)
                ok = jnp.where(m, ok2, ok)
                probes = jnp.where(m, pr2, probes)
            return ht2, ok, probes

        def run():
            with win_mod.decision_scope(dec), \
                    win_mod.cache_scope(self.cache):
                return ht_mod.insert_rdma(
                    ht, keys, vals, promise=promise, valid=valid,
                    max_probes=max_probes, fused=dec.arm == "rdma_fused",
                    coalesce=dec.coalesce)
        out = self._timed(dec, run)
        self._after_am()  # ingest wire-retry pressure from the phases
        return out

    def ht_find(self, ht, keys, promise: Promise = Promise.CR,
                valid=None, max_probes: int = 8,
                stats: Optional[OpStats] = None, max_stale: int = 0):
        """Adaptive hash-table find: returns (table', found, vals).

        max_stale (DESIGN.md §10): bounded-staleness tolerance for the
        cached arm — cached records at most this many publishes behind
        the authoritative version still count as hits (0 = bit-exact §8
        reads). Only the cache consult is affected; wire reads are always
        authoritative.

        With a cache attached and reads on (see `cache_reads_on`), the
        hit-rate EWMA is folded into the stats (OpStats.hit_rate — the
        fourth online signal) so the chooser prices the cached fused arm
        with the §8 discount, and the executed CR fused find consults the
        cache; the batch's observed hit rate then refreshes the EWMA."""
        from . import hashtable as ht_mod
        from . import window as win_mod
        dst, _ = ht_mod._place(ht, keys)
        with trace.span("chooser.decide"):
            s = self._ht_stats(keys, valid, stats)
            reads_cached = (self.cache_reads_on() and promise == Promise.CR)
            if reads_cached and s.hit_rate == 0.0:
                s = replace(s, hit_rate=self.hit_ewma)
            dec = self.decide(DSOp.HT_FIND, promise, dst, valid, s)
        self._observe_rw(is_write=False)
        if dec.arm in ("am", "am_pt"):
            eng = self._need_am(
                "ht_find",
                lambda e: ht_mod.build_am_handlers(ht, e,
                                                   max_probes=max_probes))
            found, vals = self._timed(dec, lambda: ht_mod.find_rpc(
                ht, eng, keys, valid=valid, coalesce=dec.coalesce))
            uns = self._after_am()
            if uns is not None:
                # §10 failover: unserviced finds re-read one-sided (reply
                # words of undelivered ops are garbage by contract, so the
                # merge below overwrites exactly those rows)
                m = jnp.asarray(uns)
                rv = m if valid is None else (valid & m)
                with win_mod.decision_scope(dec):
                    _, f2, v2 = ht_mod.find_rdma(
                        ht, keys, promise=promise, valid=rv,
                        max_probes=max_probes, fused=True)
                found = jnp.where(m, f2, found)
                vals = jnp.where(m[..., None], v2, vals)
            return ht, found, vals

        def run():
            with win_mod.decision_scope(dec):
                return ht_mod.find_rdma(
                    ht, keys, promise=promise, valid=valid,
                    max_probes=max_probes, fused=dec.arm == "rdma_fused",
                    coalesce=dec.coalesce,
                    cache=self.cache if dec.cached else None,
                    max_stale=max_stale)
        out = self._timed(dec, run)
        self._after_am()  # ingest wire-retry pressure from the phases
        if dec.cached and self.cache.last_hit_rate is not None:
            self.hit_ewma += self.alpha * (self.cache.last_hit_rate
                                           - self.hit_ewma)
        return out

    def q_push(self, q, vals, promise: Promise = Promise.CRW, valid=None,
               max_cas_rounds: int = 8, stats: Optional[OpStats] = None):
        """Adaptive queue push: returns (queue', pushed). The queue's
        `rdma_fused` arm is the planned engine (one RoutePlan shared by all
        component phases — the hosted queue has no compound descriptors)."""
        from . import queue as q_mod
        from . import window as win_mod
        P, n, _ = vals.shape
        with trace.span("chooser.decide"):
            dec = self.decide(DSOp.Q_PUSH, promise, valid=valid,
                              stats=self._host_stats(stats),
                              nops=P * n if valid is None else None,
                              owners=(q.host,))
        if dec.arm in ("am", "am_pt"):
            eng = self._need_am(
                "q_push", lambda e: q_mod.build_am_handlers(q, e))
            q2, ok = self._timed(dec, lambda: q_mod.push_rpc(
                q, eng, vals, valid=valid))
            uns = self._after_am()
            if uns is not None:
                # §10 failover: the queue is hosted on ONE rank, so a dead
                # host leaves the whole batch unserviced and the re-run is
                # a full one-sided push — single-host, so FIFO order is
                # whatever the one-sided reservation hands out, exactly as
                # in the fault-free rdma arm.
                m = jnp.asarray(uns)
                rv = m if valid is None else (valid & m)
                with win_mod.decision_scope(dec):
                    q2, ok2 = q_mod.push_rdma(
                        q2, vals, promise=promise, valid=rv,
                        max_cas_rounds=max_cas_rounds, planned=True)
                ok = jnp.where(m, ok2, ok)
            return q2, ok

        def run():
            with win_mod.decision_scope(dec):
                return q_mod.push_rdma(
                    q, vals, promise=promise, valid=valid,
                    max_cas_rounds=max_cas_rounds,
                    planned=dec.arm == "rdma_fused",
                    coalesce=dec.coalesce)
        out = self._timed(dec, run)
        self._after_am()  # ingest wire-retry pressure from the phases
        return out

    def q_pop(self, q, n: int, promise: Promise = Promise.CR, valid=None,
              max_cas_rounds: int = 8, stats: Optional[OpStats] = None):
        """Adaptive queue pop: returns (queue', got, vals)."""
        from . import queue as q_mod
        from . import window as win_mod
        with trace.span("chooser.decide"):
            dec = self.decide(DSOp.Q_POP, promise, valid=valid,
                              stats=self._host_stats(stats),
                              nops=q.nranks * n if valid is None else None,
                              owners=(q.host,))
        if dec.arm in ("am", "am_pt"):
            eng = self._need_am(
                "q_pop", lambda e: q_mod.build_am_handlers(q, e))
            q2, got, pvals = self._timed(dec, lambda: q_mod.pop_rpc(
                q, eng, n, valid=valid))
            uns = self._after_am()
            if uns is not None:
                # §10 failover: unserviced pops never consumed anything —
                # re-issue them one-sided against the updated queue state
                m = jnp.asarray(uns)
                rv = m if valid is None else (valid & m)
                with win_mod.decision_scope(dec):
                    q2, g2, v2 = q_mod.pop_rdma(
                        q2, n, promise=promise, valid=rv,
                        max_cas_rounds=max_cas_rounds, planned=True)
                got = jnp.where(m, g2, got)
                pvals = jnp.where(m[..., None], v2, pvals)
            return q2, got, pvals

        def run():
            with win_mod.decision_scope(dec):
                return q_mod.pop_rdma(
                    q, n, promise=promise, valid=valid,
                    max_cas_rounds=max_cas_rounds,
                    planned=dec.arm == "rdma_fused",
                    coalesce=dec.coalesce)
        out = self._timed(dec, run)
        self._after_am()  # ingest wire-retry pressure from the phases
        return out


# ---------------------------------------------------------------------------
# Default engines for the `backend="auto"` front-ends, cached so EWMA state
# and the decision log persist across calls that don't pass an explicit
# AdaptiveEngine. The with-AMEngine case hangs the chooser off the AMEngine
# itself (same lifecycle — no global registry pinning dead engines); the
# engine-less case is one chooser per nranks.
# ---------------------------------------------------------------------------
_DEFAULT: Dict[int, AdaptiveEngine] = {}


def default_engine(nranks: int, am_engine=None) -> AdaptiveEngine:
    if am_engine is not None:
        eng = getattr(am_engine, "_default_adaptive", None)
        if eng is None or eng.nranks != nranks:
            eng = AdaptiveEngine(nranks, am_engine=am_engine)
            am_engine._default_adaptive = eng
        return eng
    eng = _DEFAULT.get(nranks)
    if eng is None:
        eng = AdaptiveEngine(nranks)
        _DEFAULT[nranks] = eng
    return eng
