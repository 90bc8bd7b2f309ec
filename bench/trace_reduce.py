"""From a profiler trace of the window to device metrics.

`extract` reads an ``.xplane.pb`` with JAX's own reader and keeps three
kinds of events, on the trace's clock (ns from the trace's start):

  * device operations and the programs they belong to: the "XLA Ops"
    and "XLA Modules" lines of each ``/device:TPU:<i>`` plane, for the
    chips the run uses, with short names (``while``, ``jit_fn``);
  * the benchmark's own host annotations (names starting ``bench.``),
    from any host thread: the window, passes, reconciles and the three
    device entry points;

`reduce` turns them into the window's length, the device's busy time
(the union of operation intervals, averaged over chips), the device
time of the programs run inside each device entry point, the
operations that took most time of their own (as ``program:op``, less
the ops nested in them), and the idle time, cut at annotation
boundaries and attributed to the innermost annotation open ("host:
other" where none is).
"""
from __future__ import annotations

import bisect
from collections import defaultdict

WINDOW = "bench.window"
ENTRY_POINTS = ("bench.match", "bench.match_cycles", "bench.preview")


def extract(path: str, n_devices: int) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: dict[str, list] = {}
    modules: dict[str, list] = {}
    notes: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            try:
                idx = int(plane.name.rsplit(":", 1)[1])
            except ValueError:
                continue
            if idx >= n_devices:
                continue
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    out = ops if line.name == "XLA Ops" else modules
                    out[str(idx)] = [[_short(ev.name), ev.start_ns,
                                      ev.duration_ns] for ev in line.events]
        elif plane.name.startswith("/host:"):
            notes.extend(
                [ev.name, ev.start_ns, ev.duration_ns]
                for line in plane.lines for ev in line.events
                if ev.name.startswith("bench."))
    return {"ops": ops, "modules": modules, "annotations": notes}


def _short(name: str) -> str:
    """``%while.3 = f32[...] while(...)`` -> ``while``;
    ``jit_fn(123)`` -> ``jit_fn``."""
    head = name.split(" = ", 1)[0].lstrip("%").split("(", 1)[0]
    base, _, tail = head.rpartition(".")
    return base if base and tail.isdigit() else head


def _self_times(ops: list) -> list:
    """Each op's duration less that of the ops nested directly inside it
    (a ``while`` holds its body's ops on the same line)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    out = [float(d) for _n, _s, d in ops]
    stack: list = []                      # (end, index)
    for i in order:
        _n, s, d = ops[i]
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack and s + d <= stack[-1][0]:
            out[stack[-1][1]] -= d
        stack.append((s + d, i))
    return out


def _union(intervals: list) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


class _Covers:
    """Innermost annotation (latest start) covering a point in time."""

    def __init__(self, notes):
        self.notes = sorted((s, s + d, n) for n, s, d in notes)
        self.starts = [s for s, _e, _n in self.notes]
        self.edges = sorted({x for s, e, n in self.notes if n != WINDOW
                             for x in (s, e)})

    def split(self, a: float, b: float) -> list:
        """(a, b) cut at every annotation start or end inside it."""
        i = bisect.bisect_right(self.edges, a)
        j = bisect.bisect_left(self.edges, b)
        cuts = [a] + self.edges[i:j] + [b]
        return list(zip(cuts[:-1], cuts[1:]))

    def at(self, t: float) -> str | None:
        i = bisect.bisect_right(self.starts, t)
        for s, e, n in reversed(self.notes[max(0, i - 64):i]):
            if s <= t < e and n != WINDOW:
                return n
        return None


def reduce(events: dict, *, top: int = 10) -> dict:
    notes = events["annotations"]
    windows = [(s, s + d) for n, s, d in notes if n == WINDOW]
    if not windows:
        raise ValueError("the trace holds no bench.window annotation")
    lo, hi = windows[0]
    window_s = (hi - lo) * 1e-9
    entries = {name: _union([(s, s + d) for n, s, d in notes if n == name])
               for name in ENTRY_POINTS}
    covers = _Covers(notes)

    busy = []
    inside = defaultdict(float)
    by_op = defaultdict(float)
    gaps = defaultdict(lambda: [0.0, 0])
    for dev, dev_ops in events["ops"].items():
        mods = sorted(events.get("modules", {}).get(dev, []),
                      key=lambda m: m[1])
        mod_starts = [m[1] for m in mods]
        spans = _clip([(s, s + d) for _n, s, d in dev_ops], lo, hi)
        merged = _union(spans)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        for (name, s, d), own in zip(dev_ops, _self_times(dev_ops)):
            if s + d <= lo or s >= hi:
                continue
            mid = s + d / 2
            k = bisect.bisect_right(mod_starts, mid) - 1
            if k >= 0 and mods[k][1] <= mid < mods[k][1] + mods[k][2]:
                name = f"{mods[k][0]}:{name}"
            by_op[name] += own * 1e-9
        for _name, s, d in mods:
            if s + d <= lo or s >= hi:
                continue
            mid = s + d / 2
            for entry, ivals in entries.items():
                j = bisect.bisect_right(ivals, [mid, float("inf")]) - 1
                if j >= 0 and ivals[j][0] <= mid < ivals[j][1]:
                    inside[entry] += d * 1e-9
                    break
        edges = [lo] + [x for s, e in merged for x in (s, e)] + [hi]
        for a0, b0 in zip(edges[0::2], edges[1::2]):
            for a, b in covers.split(a0, b0):
                if b > a:
                    label = covers.at((a + b) / 2) or "host: other"
                    gaps[label][0] += (b - a) * 1e-9
                    gaps[label][1] += 1
    n_dev = max(len(events["ops"]), 1)
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / n_dev if busy else None,
        "entry_device_s": {k: v / n_dev for k, v in inside.items()},
        "device_ops": sorted(([k, v / n_dev] for k, v in by_op.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([f"{k} ({n} gaps)", s / n_dev]
                             for k, (s, n) in gaps.items()),
                            key=lambda kv: -kv[1])[:top],
    }
