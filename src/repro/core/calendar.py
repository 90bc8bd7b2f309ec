"""Transition-driven worker advancement for the event engine.

`advance_workers` (core/worker.py) walks every advertised worker before
every event: it adds the segment to `alive_s`, runs each claimed job
down by `seg * rate`, and polls the C2 verdict of every idle worker.
Almost none of those visits changes anything anyone reads before the
next one.  `WorkerCalendar` keeps the same state lazily and visits a
worker only at a boundary where the eager walk would change it:

  * a claimed job finishes — a completion calendar keyed on each job's
    finish time, computed once per claim from its run anchor
    (`job.run_t0`, with `job.remaining_s` the work left at that time);
  * a worker boots (its first walk after `booted_at`);
  * an idle worker's C2 clock runs out (`idle_since + idle_timeout`);
  * a worker is woken: drained, or its claims dropped from outside.

Idle workers are grouped by slot shape (`match_key()`).  Their C2
verdict is a pure function of the shape and the idle-cohort set, so it
is recomputed once per shape when `queue.idle_version` moved, and a
flip moves the whole group's clock at once: a member's `idle_since` is
-1 while the group's verdict is true, else the later of the clock it
joined with and the group's last flip to false (`idle_since`).  A flip
visits no worker; a group deadline visits the members whose clocks it
runs out.

A visit runs exactly the eager walk's per-worker body, so due workers
are visited in the walk's order: `collector.workers` insertion order
(a sequence number given at advertise), then claim order.

Accounting is settled on read: `Worker.alive_s` / `busy_s` are
properties over the seconds accrued up to `alive_t` / `busy_t` plus the
time since, at the calendar's clock; a job's remaining work is brought
to the clock only where something reads or rewrites it (`settle`).  The
`Worker` methods that do (`release_claims`, `drop_claim`,
`clear_claims`, the `work_rate` setter) call it; nothing else drives
the calendar but the collector (advertise, invalidate) and the
`Simulation` that installs it.  Reading changes nothing, so a snapshot
taken mid-run continues bit for bit.

Jobs with a `work_fn` are opaque: their workers stay in an eager set
walked at every boundary, as before.  The tick engine keeps the eager
walk (`advance_workers` with `exact_completions=False`)."""
from __future__ import annotations

import heapq
import itertools
import math
from collections import Counter

from repro.observability.profiler import advance_counters

#: the eager walk's tolerance: a job whose remaining work fits in the
#: segment within this completes at this boundary
COMPLETION_SLACK = 1e-9
#: deadline entries pop this early; the eager expression then decides
DEADLINE_SLACK = 1e-6


class WorkerCalendar:
    """Completion, boot and idle-deadline calendars over one collector's
    workers, advanced by the event engine (`Simulation._advance_unchecked`)."""

    def __init__(self, collector, t: float = 0.0):
        self.collector = collector
        #: the time every worker is advanced to
        self.t = float(t)
        self._next_seq = itertools.count()
        self._tie = itertools.count()
        #: per kept worker, by name: its advertise sequence number, the
        #: idle group it is a member of, and the idle_since its pending
        #: deadline entry was pushed for
        self._seq: dict = {}
        self._grp: dict = {}
        self._dl_at: dict = {}
        self._done: list = []        # (t_finish, tie, job, worker)
        self._boots: list = []       # (booted_at, tie, worker)
        #: (idle_since + timeout, tie, worker or _Shape, idle_since)
        self._deadlines: list = []
        self._wake: dict = {}        # seq -> worker, for the next boundary
        self._eager: dict = {}       # seq -> worker holding a work_fn job
        #: match_key -> _Shape of the idle, booted, undrained workers
        self._idle: dict = {}
        #: the `queue.idle_version` the group verdicts were computed at
        self._version = None
        self._timeouts: Counter = Counter()
        self._touched, self._calls = advance_counters(
            collector.telemetry.registry)

    # -- membership -----------------------------------------------------------
    def register(self, w, *, restored: bool = False):
        """Take `w` on at advertise (or restore): from now on its clocks
        and claims are kept here.  A fresh worker accrues alive time
        from its boot, or from now if it is advertised later."""
        if w.cal is self:
            return
        w.cal = self
        self._seq[w.name] = next(self._next_seq)
        if not restored:
            w.alive_t = max(w.booted_at, self.t)
        self._timeouts[w.idle_timeout] += 1
        if w.booted_at >= 0:
            heapq.heappush(self._boots, (w.booted_at, next(self._tie), w))

    def unregister(self, w):
        """`w` left the pool (terminated or invalidated): accrue its
        clocks up to now and stop keeping them."""
        if w.cal is not self:
            return
        self.settle(w)
        if w.booted_at >= 0 and self.t > w.alive_t:
            w.alive_acc += self.t - w.alive_t
            w.alive_t = self.t
        self._leave_idle(w)
        seq = self._seq.pop(w.name)
        self._dl_at.pop(w.name, None)
        self._eager.pop(seq, None)
        self._wake.pop(seq, None)
        self._timeouts[w.idle_timeout] -= 1
        if self._timeouts[w.idle_timeout] <= 0:
            del self._timeouts[w.idle_timeout]
        w.cal = None

    def restore(self, workers, t: float):
        """Take on restored workers (in advertise order) at snapshot time
        `t`: their accrued clocks and run anchors come from the snapshot;
        every one is visited at the first boundary, which rebuilds the
        idle groups (a visit the eager walk would make is a no-op here
        whenever nothing is due)."""
        self.t = float(t)
        for w in workers:
            self.register(w, restored=True)
            for job in w.claimed.values():
                if job.t_finish < math.inf:
                    heapq.heappush(self._done, (job.t_finish,
                                                next(self._tie), job, w))
            if not w.claimed and w.idle_since >= 0:
                self._push_deadline(w)
            self._wake[self._seq[w.name]] = w

    def wake(self, w):
        """Visit `w` at the next boundary (drained, or claims dropped)."""
        if w.cal is self:
            self._wake[self._seq[w.name]] = w

    def forget_verdicts(self):
        """Recompute every idle group's C2 verdict at the next boundary
        (the collector dropped its memoized verdicts)."""
        self._version = None

    def idle_since(self, w) -> float:
        """`w`'s C2 idle clock (`Worker.idle_since`)."""
        g = self._grp.get(w.name)
        if g is None:
            return w.idle_own
        return -1.0 if g.verdict else max(w.idle_own, g.since)

    # -- claims ---------------------------------------------------------------
    def on_claim(self, w, job):
        """`job` was just added to `w`'s claims (`Worker.add_claim`).  Like
        the eager walk, progress counts from the last boundary, or the
        boot if later."""
        t = max(self.t, w.booted_at)
        if len(w.claimed) == 1:
            w.busy_t = t
            self._leave_idle(w)
        w.idle_since = -1.0
        if job.work_fn is not None:
            self._eager[self._seq[w.name]] = w
            return
        job.run_t0 = t
        self._key(w, job)

    def _key(self, w, job):
        rate = w.work_rate
        job.t_finish = (job.run_t0 + job.remaining_s / rate if rate > 0
                        else math.inf)
        if job.t_finish < math.inf:
            heapq.heappush(self._done,
                           (job.t_finish, next(self._tie), job, w))

    def settle(self, w):
        """Bring `w`'s busy seconds and its jobs' remaining work up to
        now, re-anchoring the jobs here — before anything reads or
        rewrites them.  Finish times are kept; `rekey` recomputes them
        after a rewrite."""
        if w.cal is not self or not w.claimed:
            return
        t = self.t
        if t > w.busy_t:
            w.busy_acc += t - w.busy_t
            w.busy_t = t
        rate = w.work_rate
        for job in w.claimed.values():
            if job.work_fn is None and job.run_t0 < t:
                job.remaining_s -= (t - job.run_t0) * rate
                job.run_t0 = t

    def rekey(self, w):
        """Recompute the finish times of `w`'s settled jobs after their
        work or the worker's rate was rewritten."""
        if w.cal is not self:
            return
        for job in w.claimed.values():
            if job.work_fn is None:
                self._key(w, job)

    # -- advancing ------------------------------------------------------------
    def advance(self, queue, cluster, t1: float):
        """Advance the pool from `self.t` to `t1`: visit, in the eager
        walk's order, every worker that walk would change."""
        t0 = self.t
        if t1 <= t0:
            return
        self.t = t1
        due = self._wake
        self._wake = {}
        due.update(self._eager)
        done, boots, deadlines = self._done, self._boots, self._deadlines
        lim = t1 + COMPLETION_SLACK
        while done and done[0][0] <= lim:
            tf, _, job, w = heapq.heappop(done)
            if (w.cal is self and job.t_finish == tf
                    and w.claimed.get(job.jid) is job):
                due[self._seq[w.name]] = w
        while boots and boots[0][0] < t1:
            _b, _, w = heapq.heappop(boots)
            if w.cal is self:
                due[self._seq[w.name]] = w
        lim = t1 + DEADLINE_SLACK
        while deadlines and deadlines[0][0] <= lim:
            entry = self._pop_deadline()
            if not self._live(entry):
                continue
            _dl, _, w, since = entry
            if isinstance(w, _Shape):
                for seq, m in w.members.items():
                    if m.idle_since == since:
                        due[seq] = m
            else:
                due[self._seq[w.name]] = w
        col = self.collector
        version = getattr(queue, "idle_version", None)
        if version is None or version != self._version:
            self._version = version
            for g in self._idle.values():
                hit = col.any_cohort_matches(
                    next(iter(g.members.values())), queue)
                if hit != g.verdict:
                    g.verdict = hit
                    if not hit:     # every member's clock starts here
                        g.since = t0
                        heapq.heappush(deadlines, (
                            t0 + g.timeout, next(self._tie), g, t0))
        self._touched.value += len(due)
        self._calls.value += 1
        for seq in sorted(due):
            self._visit(due[seq], t0, t1, queue, cluster)

    def _visit(self, w, t0, t1, queue, cluster):
        """The eager walk's body for one worker (`advance_workers` with
        exact completions), on the lazy clocks.  (Twin of that body:
        change both.)"""
        if (w.cal is not self or w.terminated or w.booted_at < 0
                or w.booted_at >= t1):
            return
        seg0 = max(t0, w.booted_at)
        seg = t1 - seg0
        if seg <= 0:
            return
        idle_from = seg0            # idleness cannot predate the boot
        opaque = False
        if w.claimed:
            busy_until = seg0
            for jid, job in list(w.claimed.items()):
                if job.work_fn is not None:
                    done = job.work_fn(job, seg)
                    t_done = t1
                    opaque = opaque or not done
                elif job.t_finish <= t1 + COMPLETION_SLACK:
                    job.remaining_s = 0.0
                    done = True
                    t_done = min(job.t_finish, t1)
                else:
                    done = False
                    t_done = t1
                if done:
                    # route to the owning schedd (flocking)
                    (job.schedd or queue).complete(jid, t_done)
                    w.pop_claim(jid)
                busy_until = max(busy_until, t_done)
            if not w.claimed:
                w.busy_acc += busy_until - w.busy_t
                idle_from = busy_until  # the exact last completion
        if not opaque:
            self._eager.pop(self._seq[w.name], None)
        if w.claimed:
            w.idle_since = -1.0
            return
        if w.draining:
            # backend drain: claims done — retire now
            self._terminate(w, t1, cluster)
            return
        has_match = self.collector.any_cohort_matches(w, queue)
        # (no member is visited at the boundary that sees its group flip
        # to false: its own and the group's deadlines are stale until then,
        # and only a drain, which retires it above, wakes an idle worker)
        since = w.idle_since
        if has_match:
            since = -1.0            # negotiator will claim next cycle
        elif since < 0:
            since = idle_from
        elif t1 - since >= w.idle_timeout:
            self._terminate(w, t1, cluster)
            return
        # a joining worker's clock is at least its group's last flip, so
        # the member keeps the clock just computed
        w.idle_own = since
        if w.name not in self._grp:
            self._join(w, has_match)
        if not has_match and self._dl_at.get(w.name) != since:
            self._push_deadline(w)

    def _terminate(self, w, t1, cluster):
        w.terminated = True
        self.collector.invalidate(w.name)       # -> unregister
        if w.pod_name is not None and cluster is not None:
            cluster.succeed_pod(w.pod_name, t1)

    def _push_deadline(self, w):
        since = w.idle_since
        self._dl_at[w.name] = since
        heapq.heappush(self._deadlines,
                       (since + w.idle_timeout, next(self._tie), w, since))

    def _pop_deadline(self):
        entry = heapq.heappop(self._deadlines)
        w, since = entry[2], entry[3]
        if not isinstance(w, _Shape) and self._dl_at.get(w.name) == since:
            del self._dl_at[w.name]
        return entry

    def _live(self, entry) -> bool:
        """Can this deadline entry still run a clock out?  One that
        cannot never will again: a clock that moved never moves back."""
        _dl, _, w, since = entry
        if isinstance(w, _Shape):
            return (self._idle.get(w.key) is w and not w.verdict
                    and w.since == since)
        return w.cal is self and not w.claimed and w.idle_since == since

    def _join(self, w, verdict: bool):
        key = w.match_key()
        g = self._idle.get(key)
        if g is None:
            g = self._idle[key] = _Shape(key, verdict)
        g.members[self._seq[w.name]] = w
        g.timeout = min(g.timeout, w.idle_timeout)
        self._grp[w.name] = g

    def _leave_idle(self, w):
        """`w` leaves its idle group (claimed, terminated) keeping the
        clock the group gave it."""
        g = self._grp.get(w.name)
        if g is None:
            return
        w.idle_own = self.idle_since(w)
        del self._grp[w.name]
        del g.members[self._seq[w.name]]
        if not g.members:
            del self._idle[g.key]

    # -- queries --------------------------------------------------------------
    def quiet(self, span: float, horizon: float, margin: float) -> bool:
        """Can nothing change in the pool before `horizon` (`span`
        seconds from now)?  No opaque `work_fn` job runs, no worker's
        idle timeout is as short as the span, no claimed job finishes
        and no undrained idle worker's clock runs out by the horizon —
        the live-fusion deferral test (`Simulation._defer_ok`)."""
        if self._eager:
            return False
        if self._timeouts and min(self._timeouts) <= span + margin:
            return False
        done = self._done
        while done:
            tf, _, job, w = done[0]
            if (w.cal is self and job.t_finish == tf
                    and w.claimed.get(job.jid) is job):
                if tf <= horizon:
                    return False
                break
            heapq.heappop(done)
        deadlines = self._deadlines
        while (deadlines and deadlines[0][0] <= horizon
                and not self._live(deadlines[0])):
            self._pop_deadline()
        if not deadlines or deadlines[0][0] > horizon:
            return True
        for entry in deadlines:
            dl, _, w, since = entry
            if dl > horizon or not self._live(entry):
                continue
            if isinstance(w, _Shape):
                if any(not m.draining and m.idle_since == since
                       and since + m.idle_timeout <= horizon
                       for m in w.members.values()):
                    return False
            elif not w.draining:
                return False
        return True


class _Shape:
    """The idle, booted, undrained workers of one slot shape: their C2
    verdict, the time of its last flip to false (-1 before any), and the
    least idle timeout among them (the group deadline's offset)."""

    __slots__ = ("key", "members", "verdict", "since", "timeout")

    def __init__(self, key, verdict: bool):
        self.key = key
        self.members: dict = {}          # advertise seq -> worker
        self.verdict = verdict
        self.since = -1.0
        self.timeout = math.inf
