"""Negotiation-cycle profiler.

Attributes wall-clock per negotiation cycle to problem-build /
matchmaker `match` / plan-apply, and per provisioner reconcile to
collector-preview vs the rest — the phase split the million-job
roadmap item needs to know where a drain actually spends its time.

The collector/provisioner hot paths guard every timing site with a
single `if prof is not None:` check, so a simulation built without
telemetry pays one attribute load per cycle and nothing else.

Matchmaker-backend detail rides along: the jax backend reports, per
call, its padding bucket and whether that bucket was seen before
(first sight == XLA trace+compile, repeats == cached executable), and
`flush_staged` reports fused-batch size or the fallback reason.

Wall times land in registry histograms (scrapeable) and in bounded
per-cycle deques whose offsets are relative to profiler creation —
those deques feed the Chrome-trace exporter and are deliberately
*excluded* from snapshots: wall-clock measurements of a dead process
are not worth resuming, so a restore starts the profiler log empty
while the cumulative histograms carry over.

Spans.  The same profiler keeps the program's spans: the event engine
(each fired event, the continuous-state advance, the service driver's
waits, injections and running wall), each negotiation pass and
reconcile, and their phases.  A span does two things.  Its wall seconds
go to the registry on the host clock above — a stack of open spans
gives each its SELF time (its wall less its child spans'), so the parts
of `repro_engine_seconds_total` partition the driver's running wall —
and, only while a JAX profiler session is collecting
(`TraceMe.is_enabled()`), it opens a TraceMe named `repro.<layer>` on
the xplane's host plane, on the device trace's clock, beside the device
ops.  TraceMe metadata carries `pass_id` (shared by a pass and its
phases), `cause` (the enclosing span's part) and the span's own labels.
`trace_me` imports nothing: without jaxlib loaded no session can be
collecting, so the numpy path stays JAX-free.  The stack assumes the
simulation's single-writer model: one thread runs it at a time.
"""
from __future__ import annotations

import contextlib
import sys
import time
from collections import deque

from .registry import MetricRegistry, WALL_SECONDS_BUCKETS


def trace_me(name: str, **meta):
    """The TraceMe half of a span: open and return a TraceMe `name`
    (close it with ``__exit__``) when a JAX profiler session is
    collecting, else None.  Costs one dict lookup with JAX not loaded
    and one `is_enabled()` call (about 0.15 us) with it."""
    mod = sys.modules.get("jaxlib._profiler")
    if mod is None or not mod.TraceMe.is_enabled():
        return None
    tm = mod.TraceMe(name, **meta)
    tm.__enter__()
    return tm


#: what a call site enters in place of a span when telemetry is off
NO_SPAN = contextlib.nullcontext()


def advance_counters(registry: MetricRegistry):
    """The event engine's advancement counters: workers visited, and
    advance calls (core/calendar.py counts them; cycle records carry
    both)."""
    return (registry.counter(
                "repro_advance_workers_touched_total",
                "Workers the event engine visited while advancing "
                "continuous state before events"),
            registry.counter(
                "repro_advance_calls_total",
                "Advances of the event engine's continuous state (one "
                "before each event time)"))


class _Span:
    """Context manager over `CycleProfiler.enter`/`exit`."""

    __slots__ = ("prof", "part", "name", "key")

    def __init__(self, prof, part, name, key=None):
        self.prof, self.part, self.name, self.key = prof, part, name, key

    def __enter__(self):
        self.prof.enter(self.part, self.name, key=self.key)
        return self

    def __exit__(self, *exc):
        self.prof.exit()


class CycleProfiler:
    def __init__(self, registry: MetricRegistry, *,
                 cycle_log_max: int = 4096):
        self.phase_h = registry.histogram(
            "repro_cycle_phase_seconds",
            "Wall seconds per negotiation-cycle phase",
            ("phase",), WALL_SECONDS_BUCKETS)
        self.cycles_c = registry.counter(
            "repro_cycles_total", "Negotiation cycles by kind", ("kind",))
        # labelled by entry path: "cycle" covers match/match_cycles
        # dispatches from negotiation, "preview" the provisioner dry-run
        # dispatches.  The split exists because the preview path owns
        # its own jit (vmapped, guard-free) and its buckets grow outside
        # any recorded cycle — an unlabelled, cycle-only count read 0 on
        # the 2k replay while preview buckets grew.
        self.jit_compiles = registry.counter(
            "repro_matchmaker_jit_compiles_total",
            "Matchmaker calls that hit a fresh padding bucket (XLA "
            "trace), by entry path", ("path",))
        self.reconcile_h = registry.histogram(
            "repro_reconcile_seconds",
            "Wall seconds per provisioner reconcile",
            (), WALL_SECONDS_BUCKETS)
        self.preview_h = registry.histogram(
            "repro_reconcile_preview_seconds",
            "Wall seconds spent in collector.preview per reconcile",
            (), WALL_SECONDS_BUCKETS)
        self.pass_h = registry.histogram(
            "repro_pass_seconds",
            "Wall seconds per negotiation pass, entry to exit of "
            "run_cycle or flush_staged (no-op passes included)",
            (), WALL_SECONDS_BUCKETS)
        self.engine_c = registry.counter(
            "repro_engine_seconds_total",
            "Wall seconds of the engine by part: self time of advance, "
            "event:<kind>, wait, inject, pass and reconcile spans; run "
            "is the service driver's whole running wall",
            ("part",))
        self.events_c = registry.counter(
            "repro_events_total",
            "Events fired, by kind (the event name up to its first "
            "space)", ("kind",))
        self.xfer_c = registry.counter(
            "repro_device_transfer_bytes_total",
            "Bytes copied between host and device by matchmaker calls, "
            "by entry path and direction", ("path", "direction"))
        self.fs_slices_c = registry.counter(
            "repro_fairshare_slices_total",
            "Fair-share slices negotiated (one (schedd, user) entry's "
            "quantum-capped water-fill each)")
        self.fs_calls_c = registry.counter(
            "repro_fairshare_device_calls_total",
            "Matchmaker calls fair-share passes made (one per slice on "
            "the sequential path, one per pass on a fused one)")
        self._advance = advance_counters(registry)
        self.cycle_log_max = int(cycle_log_max)
        self.cycles: deque = deque(maxlen=self.cycle_log_max)
        self.reconciles: deque = deque(maxlen=self.cycle_log_max)
        self._t0 = time.perf_counter()
        # open spans, innermost last: [part, t0, child wall, TraceMe,
        # phase TraceMe, (id key, id) or None]
        self._stack: list[list] = []
        self._parts: dict = {}         # part -> engine_c child
        self._events: dict = {}        # kind -> (part, count child, meta)
        self._run_t0: float | None = None
        self._run_tm = None
        self._seq = 0
        #: id of the open negotiation pass (None outside one)
        self.pass_id: int | None = None
        # round trip and bytes of the pass's device calls since its last
        # cycle record
        self._device = [0.0, 0, 0]

    @staticmethod
    def now() -> float:
        return time.perf_counter()

    # -- spans ---------------------------------------------------------------
    def enter(self, part: str, name: str, meta: dict | None = None, *,
              key: str | None = None, t0: float | None = None):
        """Open a span: `part` names its self-time counter, `name` its
        TraceMe; `key` ("pass_id", "reconcile_id") gives it a fresh id
        that its phases repeat; `t0` is its start if the caller read the
        clock already.  Close with `exit`.  The span's clock starts
        before its own bookkeeping, so what tracing costs is counted
        inside the span, not as time no span covers."""
        if t0 is None:
            t0 = time.perf_counter()
        ident = None
        if key is not None:
            self._seq += 1
            ident = (key, self._seq)
            if key == "pass_id":
                self.pass_id = self._seq
        tm = None
        mod = sys.modules.get("jaxlib._profiler")
        if mod is not None and mod.TraceMe.is_enabled():
            kw = dict(meta or ())
            if ident is not None:
                kw[key] = ident[1]
            kw["cause"] = (self._stack[-1][0] if self._stack else
                           "run" if self._run_t0 is not None else "")
            tm = trace_me(name, **kw)
        self._stack.append([part, t0, 0.0, tm, None, ident])

    def exit(self) -> float:
        """Close the innermost span; returns its wall seconds."""
        t = time.perf_counter()
        part, t0, child, tm, phase_tm, ident = self._stack.pop()
        if phase_tm is not None:
            phase_tm.__exit__(None, None, None)
        if tm is not None:
            tm.__exit__(None, None, None)
        wall = t - t0
        if self._stack:
            self._stack[-1][2] += wall
        self._part(part).value += wall - child
        if ident is not None and ident[0] == "pass_id":
            self.pass_id = None
            self.pass_h.observe(wall)
        return wall

    def span(self, part: str, name: str) -> _Span:
        return _Span(self, part, name)

    def pass_span(self) -> _Span:
        """`repro.pass`: one negotiation pass, into `repro_pass_seconds`."""
        return _Span(self, "pass", "repro.pass", "pass_id")

    def reconcile_span(self) -> _Span:
        return _Span(self, "reconcile", "repro.reconcile", "reconcile_id")

    def enter_event(self, name: str, t0: float):
        """Open `repro.event` for one fired event, counted by kind; `t0`
        is when the loop began to pop it."""
        kind = name.partition(" ")[0] or "unnamed"
        ent = self._events.get(kind)
        if ent is None:
            ent = self._events[kind] = (
                "event:" + kind, self.events_c.labels(kind), {"kind": kind})
        ent[1].value += 1
        self.enter(ent[0], "repro.event", ent[2], t0=t0)

    def phase(self, name: str | None = None) -> float:
        """A phase boundary inside the innermost span: close its open
        phase TraceMe and, while that span is traced, open `name` (None
        opens nothing).  Returns `now()`, so a call site times the phase
        with the same read."""
        if self._stack:
            f = self._stack[-1]
            if f[4] is not None:
                f[4].__exit__(None, None, None)
                f[4] = None
            if name is not None and f[3] is not None:
                ident = f[5]
                f[4] = trace_me(name, **({ident[0]: ident[1]} if ident
                                         else {}))
        return time.perf_counter()

    def run_begin(self):
        """The service driver's loop starts running (`run` part)."""
        self._run_tm = trace_me("repro.driver.run")
        self._run_t0 = time.perf_counter()

    def run_end(self):
        t = time.perf_counter()
        if self._run_tm is not None:
            self._run_tm.__exit__(None, None, None)
            self._run_tm = None
        self._part("run").value += t - self._run_t0
        self._run_t0 = None

    def _part(self, part: str):
        c = self._parts.get(part)
        if c is None:
            c = self._parts[part] = self.engine_c.labels(part)
        return c

    def engine_seconds(self) -> dict:
        """Cumulative engine seconds by part, open spans included up to
        now: the snapshot a cycle record carries."""
        t = time.perf_counter()
        out = {k[0]: c.value for k, c in self.engine_c.children.items()}
        inner = 0.0
        for part, t0, child, _tm, _ptm, _id in reversed(self._stack):
            el = t - t0
            out[part] = out.get(part, 0.0) + el - child - inner
            inner = el
        if self._run_t0 is not None:
            out["run"] = out.get("run", 0.0) + t - self._run_t0
        return out

    def note_device(self, path: str, lc: dict | None):
        """Count one matchmaker call's host-device bytes from its
        `last_call`, by entry path ("cycle" or "preview"); a negotiation
        call's round trip and bytes also go to the pass's next cycle
        record."""
        if not lc or "h2d_bytes" not in lc:
            return
        h2d, d2h = lc["h2d_bytes"], lc["d2h_bytes"]
        self.xfer_c.labels(path, "h2d").value += h2d
        self.xfer_c.labels(path, "d2h").value += d2h
        if path == "cycle":
            d = self._device
            d[0] += lc["roundtrip_s"]
            d[1] += h2d
            d[2] += d2h

    def record_cycle(self, *, t: float, kind: str, w_start: float,
                     build_s: float, match_s: float, apply_s: float,
                     claims: int = 0, backend: str = "",
                     compiled: bool | None = None,
                     fused_k: int | None = None,
                     fallback: str | None = None,
                     fairshare: dict | None = None):
        """One negotiation cycle.  `w_start` is the absolute
        perf_counter at cycle start; durations are wall seconds.  The
        record also carries the open pass's `pass_id`, the round trip
        and bytes of the device calls since the pass's last record,
        `engine_s`, a snapshot of `engine_seconds()`, and beside it the
        advancement counters `advance_touched` and `advance_calls`.  A
        fair-share pass's `fairshare` dict joins the record as it is:
        `slices`, `device_calls`, `entries`, and the unpadded sizes its
        roofline reads (`cohorts` that claimed, live `workers`,
        `resources`, `nonzero_takes`)."""
        self.phase_h.labels("build").observe(build_s)
        self.phase_h.labels("match").observe(match_s)
        self.phase_h.labels("apply").observe(apply_s)
        self.cycles_c.labels(kind).value += 1
        if compiled:
            self.jit_compiles.labels("cycle").value += 1
        rt, h2d, d2h = self._device
        self._device = [0.0, 0, 0]
        rec = {"t": t, "kind": kind, "w0": w_start - self._t0,
               "build_s": build_s, "match_s": match_s, "apply_s": apply_s,
               "claims": claims, "backend": backend,
               "pass_id": self.pass_id, "roundtrip_s": rt,
               "h2d_bytes": h2d, "d2h_bytes": d2h,
               "engine_s": self.engine_seconds(),
               "advance_touched": self._advance[0].value,
               "advance_calls": self._advance[1].value}
        top = self._stack[-1] if self._stack else None
        if top is not None and top[0] == "pass" and top[3] is not None:
            top[3].set_metadata(kind=kind)
        if compiled is not None:
            rec["compiled"] = compiled
        if fused_k is not None:
            rec["fused_k"] = fused_k
        if fallback is not None:
            rec["fallback"] = fallback
        if fairshare is not None:
            self.fs_slices_c.value += fairshare["slices"]
            self.fs_calls_c.value += fairshare["device_calls"]
            rec.update(fairshare)
        self.cycles.append(rec)

    def note_compile(self, path: str):
        """Attribute one fresh-bucket XLA trace to an entry path
        ("preview" from the collector dry run; record_cycle attributes
        the "cycle" path itself)."""
        self.jit_compiles.labels(path).value += 1

    def record_reconcile(self, *, t: float, w_start: float, wall_s: float,
                         preview_s: float, submitted: int = 0):
        self.reconcile_h.observe(wall_s)
        self.preview_h.observe(preview_s)
        self.reconciles.append(
            {"t": t, "w0": w_start - self._t0, "wall_s": wall_s,
             "preview_s": preview_s, "submitted": submitted})

    # -- aggregate view (compare.py phase-attribution columns) ---------------
    def phase_totals(self) -> dict:
        out = {}
        for phase in ("build", "match", "apply"):
            h = self.phase_h.labels(phase)
            out[phase + "_s"] = h.sum
        out["reconcile_s"] = self.reconcile_h.sum
        out["preview_s"] = self.preview_h.sum
        out["cycles"] = {k[0]: int(c.value)
                         for k, c in self.cycles_c.children.items()}
        by_path = {k[0]: int(c.value)
                   for k, c in self.jit_compiles.children.items()}
        # "jit_compiles" stays the all-paths total (pre-label surface)
        out["jit_compiles"] = sum(by_path.values())
        out["jit_compiles_by_path"] = by_path
        return out

    # -- Chrome-trace rows (wall offsets -> microseconds) --------------------
    def chrome_events(self, pid: int = 2) -> list:
        out = [{"ph": "M", "pid": pid, "name": "process_name",
                "args": {"name": "negotiation wall clock"}}]
        for rec in self.cycles:
            w = rec["w0"] * 1e6
            args = {"sim_t": rec["t"], "kind": rec["kind"],
                    "backend": rec["backend"], "claims": rec["claims"]}
            for key in ("compiled", "fused_k", "fallback"):
                if key in rec:
                    args[key] = rec[key]
            for phase in ("build", "match", "apply"):
                dur = rec[phase + "_s"] * 1e6
                out.append({"ph": "X", "pid": pid, "tid": 1,
                            "name": phase, "cat": "negotiation",
                            "ts": w, "dur": dur, "args": args})
                w += dur
        for rec in self.reconciles:
            w = rec["w0"] * 1e6
            out.append({"ph": "X", "pid": pid, "tid": 2,
                        "name": "reconcile", "cat": "provisioner",
                        "ts": w, "dur": rec["wall_s"] * 1e6,
                        "args": {"sim_t": rec["t"],
                                 "preview_s": rec["preview_s"],
                                 "submitted": rec["submitted"]}})
        return out
