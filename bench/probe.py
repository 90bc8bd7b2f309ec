"""Probes the benchmark places around the system under test.

Nothing here changes what the pool decides.  `Probe` wraps, on one
`PoolService`'s instances only:

  * ``Collector.run_cycle`` / ``flush_staged`` — one host-clock span per
    negotiation pass (build, match and apply, or one fused flush), and a
    seeded sample of the passes with the claims each made, of one kind:
    ``plain`` (one schedd), ``flocking`` (several, in flocking order) or
    ``fairshare`` (several, under the fair-share accountant);
  * ``Provisioner.reconcile`` — one host-clock span per reconcile;
  * the queues' hooks — every job entering or leaving the idle set,
    every claim and every completion, each with its simulated time, from
    the first submission on, so that the check can rebuild a sampled
    pass's idle jobs, each worker's running jobs and each submitter's
    decayed usage without the program's own bookkeeping, and the trace
    record each job was submitted from;
  * the collector's matchmaker — a forwarding wrapper that keeps a
    seeded sample of the device calls (problem in, answer out) for the
    correctness check and, in traced runs, names each call in the
    profiler's trace (``bench.match``, ``bench.match_cycles``,
    ``bench.preview``) and keeps its shape and answer, whose bytes are
    counted once the window has closed.
"""
from __future__ import annotations

import contextlib
import time

import numpy as np

from bench import roofline

#: (cohort, worker) pairs the sampled calls of one kind may hold: the
#: reference's time and the host memory held both grow with them
SAMPLE_PAIRS = 150_000_000
SAMPLE_MIN, SAMPLE_MAX = 4, 64

#: kinds of job events in `Probe.log`: (IDLE_IN, job, t),
#: (IDLE_OUT, job, t), (CLAIM, job, worker, t), (DONE, job, t)
IDLE_IN, IDLE_OUT, CLAIM, DONE = range(4)


def record_key(schedd, arrival_s, runtime_s, user, group) -> tuple:
    """What tells a trace record from the others, as its job shows it."""
    return (schedd, float(arrival_s), float(runtime_s), user, group)


def n_idle_cohorts(queues) -> int:
    """Idle cohorts over one queue or a flocking list of them."""
    if hasattr(queues, "idle_cohorts"):
        queues = [queues]
    return sum(sum(1 for _ in q.idle_cohorts()) for q in queues)


class Reservoir:
    """Uniform sample of at most k items from a stream, drawn from the
    seed; k is fixed at the first offer from the item's size."""

    def __init__(self, rng: np.random.Generator):
        self.rng = rng
        self.k = 0
        self.seen = 0
        self.items: list = []

    def slot(self, pairs) -> int | None:
        """Offer one item of `pairs` (cohort, worker) pairs, a number or
        a function that gives it (called at the first offer only); the
        index the item takes, or None to drop it."""
        if self.k == 0:
            n = pairs() if callable(pairs) else pairs
            self.k = int(np.clip(SAMPLE_PAIRS // max(n, 1),
                                 SAMPLE_MIN, SAMPLE_MAX))
        self.seen += 1
        if len(self.items) < self.k:
            self.items.append(None)
            return len(self.items) - 1
        j = int(self.rng.integers(0, self.seen))
        return j if j < self.k else None


def _annotate(traced: bool, name: str):
    if not traced:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


class MatchmakerProbe:
    """Forwards every attribute to the wrapped matchmaker; samples and
    names the three device entry points."""

    def __init__(self, inner, probe: "Probe"):
        self._inner = inner
        self._probe = probe

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def match(self, problem, *, budget=None, active=None):
        pr = self._probe
        if pr.shapes is not None:
            pr.shapes["match"].add(problem.compat.shape)
        with _annotate(pr.traced, "bench.match"):
            plan = self._inner.match(problem, budget=budget, active=active)
        if pr.recording:
            if pr.traced:
                pr.device_calls.append(("match", problem, [plan.takes]))
            pr.offer_match(problem, plan, budget, active)
        return plan

    def match_cycles(self, problem, deltas):
        pr = self._probe
        with _annotate(pr.traced, "bench.match_cycles"):
            plans = self._inner.match_cycles(problem, deltas)
        if pr.recording:
            if pr.traced:
                pr.device_calls.append(("match_cycles", problem,
                                        [p.takes for p in plans]))
            pr.offer_cycles(problem, deltas, plans)
        return plans

    def preview_many(self, problem, frees, demands=None, **kw):
        pr = self._probe
        if pr.shapes is not None:
            pr.shapes["preview"].add(problem.compat.shape)
        with _annotate(pr.traced, "bench.preview"):
            out = self._inner.preview_many(problem, frees, demands, **kw)
        if pr.recording:
            if pr.traced:
                pr.device_calls.append(("preview", problem, list(out)))
            pr.offer_preview(problem, frees, demands, out)
        return out


class Probe:
    def __init__(self, svc, *, seed: int, traced: bool = False):
        self.sim = svc.sim
        self.traced = traced
        self.recording = False
        #: when a dict, (C, W) of every call by kind ("match", "preview")
        #: and of every plain pass ("pass")
        self.shapes: dict | None = None
        self.passes: list[tuple[float, float]] = []       # wall (t0, t1)
        self.reconciles: list[tuple[float, float]] = []
        self.claims: list[tuple] = []    # (jid, worker, sim t, cohort key)
        #: every job event since the first submission, in order
        self.log: list[tuple] = []
        #: {job: index of its trace record}, filled as each job enters
        #: the queue; `expect` says which records are coming
        self.record_of: dict[int, int] = {}
        self._expected: dict[tuple, list[int]] = {}
        #: traced device calls of the window: (kind, problem, answers)
        self.device_calls: list[tuple] = []
        rng = np.random.default_rng([seed, 0xC0FFEE])
        self.match_samples = Reservoir(rng)
        self.cycle_samples = Reservoir(rng)
        self.preview_samples = Reservoir(rng)
        self.pass_samples = Reservoir(rng)

        col, prov = self.sim.collector, self.sim.provisioner
        col.matchmaker = MatchmakerProbe(col.matchmaker, self)
        self._wrap_pass(col, "run_cycle")
        self._wrap_pass(col, "flush_staged")
        inner_reconcile = prov.reconcile

        def reconcile(now, *a, **kw):
            t0 = time.perf_counter()
            with _annotate(self.traced, "bench.reconcile"):
                out = inner_reconcile(now, *a, **kw)
            if self.recording:
                self.reconciles.append((t0, time.perf_counter()))
            return out

        prov.reconcile = reconcile
        log = self.log
        for q in self.sim.queues:
            q.add_idle_hook(self._on_idle)
            q.add_claim_hook(self._on_claim)
            q.add_release_hook(self._on_release)
            q.add_complete_hook(lambda job: log.append(
                (DONE, job.jid, job.completed_at)))

    def expect(self, records: list[dict], default_schedd: str):
        """The trace about to be submitted: each job that enters a queue
        for the first time is matched to its record by the record's own
        fields (at trace times the service hands back no job ids)."""
        for i, r in enumerate(records):
            self._expected.setdefault(record_key(
                r.get("schedd") or default_schedd, r["arrival_s"],
                r["runtime_s"], r["user"], r["group"]), []).append(i)
        for waiting in self._expected.values():
            waiting.reverse()               # popped from the end: FIFO

    def _wrap_pass(self, col, name: str):
        inner = getattr(col, name)

        def run(*a, **kw):
            if self.shapes is not None and name == "run_cycle":
                # the sizes of every pass, the host walk's too, so that
                # the warm-up covers what a device pass could be given
                now = a[1] if len(a) > 1 else kw["now"]
                self.shapes["pass"].add((
                    n_idle_cohorts(a[0] if a else kw["queues"]),
                    len(col.alive_workers(now))))
            pos = len(self.log)
            n0 = len(self.claims)
            t0 = time.perf_counter()
            with _annotate(self.traced, "bench.pass"):
                out = inner(*a, **kw)
            t1 = time.perf_counter()
            if self.recording:
                self.passes.append((t0, t1))
                # a whole pass over the live queues: its claims can be
                # rebuilt from the job events before it
                if name == "run_cycle" and kw.get("max_submit") is None:
                    now = a[1] if len(a) > 1 else kw["now"]
                    queues = a[0] if a else kw["queues"]
                    kind = ("fairshare" if kw.get("accountant") is not None
                            else "plain" if hasattr(queues, "idle_cohorts")
                            else "flocking")
                    self.offer_pass(col, queues, kind, now, pos, n0)
            return out

        setattr(col, name, run)

    def _on_idle(self, job, d):
        if d < 0:
            # a claim (or a removal): the claim hook stamps its time
            self.log.append((IDLE_OUT, job.jid, None))
        elif job.claimed_by is not None:
            # a release: the release hook stamps its time
            self.log.append((IDLE_IN, job.jid, None))
        else:
            self.log.append((IDLE_IN, job.jid, job.submitted_at))
            waiting = self._expected.get(record_key(
                job.schedd.name, job.submitted_at, job.runtime_s,
                job.ad.get("user"), job.ad.get("accounting_group")))
            if waiting:
                self.record_of[job.jid] = waiting.pop()

    def _stamp(self, jid, now):
        """Give the event just logged for `jid` its time, where the idle
        hook could not know it."""
        ev = self.log[-1] if self.log else None
        if ev is not None and ev[1] == jid and len(ev) == 3 and ev[2] is None:
            self.log[-1] = (ev[0], jid, now)

    def _on_claim(self, job, now):
        self._stamp(job.jid, now)
        self.log.append((CLAIM, job.jid, job.claimed_by, now))
        if self.recording:
            self.claims.append((job.jid, job.claimed_by, now,
                                job.cohort_key))

    def _on_release(self, job, now):
        self._stamp(job.jid, now)

    # -- sampling ----------------------------------------------------------
    def offer_pass(self, col, queues, kind, now, pos, n0):
        workers = col.alive_workers(now)
        slot = self.pass_samples.slot(
            lambda: len(workers) * max(1, n_idle_cohorts(queues)))
        if slot is not None:
            self.pass_samples.items[slot] = {
                "kind": kind, "pos": pos, "now": now,
                "workers": [(w.name, w.ad, w.start_expr.src)
                            for w in workers],
                "claims": [(jid, w) for jid, w, _t, _k in self.claims[n0:]]}

    def offer_match(self, problem, plan, budget, active):
        slot = self.match_samples.slot(problem.compat.size)
        if slot is not None:
            # a fair-share pass hands the next slice the same problem
            # with its demand and free capacity replaced: keep this
            # call's own
            self.match_samples.items[slot] = {
                "problem": problem, "demand": problem.demand,
                "free": problem.free, "takes": plan.takes,
                "free_after": plan.free_after, "budget": budget,
                "active": active}

    def offer_cycles(self, problem, deltas, plans):
        slot = self.cycle_samples.slot(problem.compat.size * len(plans))
        if slot is not None:
            self.cycle_samples.items[slot] = {
                "problem": problem, "deltas": deltas,
                "plans": [(p.takes, p.free_after) for p in plans]}

    def offer_preview(self, problem, frees, demands, out):
        slot = self.preview_samples.slot(problem.compat.size * len(frees))
        if slot is not None:
            self.preview_samples.items[slot] = {
                "problem": problem, "frees": list(frees),
                "demands": None if demands is None else list(demands),
                "absorbed": [np.asarray(a) for a in out]}

    # -- window ----------------------------------------------------------------
    def start(self):
        self.recording = True

    def stop(self):
        self.recording = False

    def bytes_moved(self) -> dict:
        """Least bytes the traced device calls had to move, by kind;
        counted after the window, so the count costs the window nothing."""
        out = {"match": 0, "match_cycles": 0, "preview": 0}
        for kind, p, answers in self.device_calls:
            R = p.requests.shape[1]
            if kind == "preview":
                out[kind] += roofline.preview_bytes(p.compat, R, answers)
            else:
                out[kind] += sum(roofline.match_bytes(p.compat, R, t)
                                 for t in answers)
        return out
