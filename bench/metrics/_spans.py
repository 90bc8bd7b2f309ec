"""Arithmetic the readers of the program's own spans share.

Each profiler cycle record carries ``engine_s``, the cumulative wall
seconds of the engine by part (``repro_engine_seconds_total``: self time
of ``advance``, ``event:<kind>``, ``wait``, ``inject``, ``pass`` and
``reconcile``; ``run`` is the service driver's running wall), and
``roundtrip_s``, ``h2d_bytes`` and ``d2h_bytes`` of its pass's device
calls.  A program without these spans writes records without them, and
every reader then returns None."""
from __future__ import annotations

import numpy as np


def engine_delta(win) -> dict | None:
    """Engine seconds by part between the window's first and last cycle
    records that carry them.  ``run`` stops while the driver is stopped,
    so a traced run's pause while the trace is written is left out."""
    recs = [c for c in win.cycles if "engine_s" in c]
    if len(recs) < 2:
        return None
    first, last = recs[0]["engine_s"], recs[-1]["engine_s"]
    delta = {k: v - first.get(k, 0.0) for k, v in last.items()}
    if delta.get("run", 0.0) <= 0.0:
        return None
    return delta


def engine_pct(win, parts) -> float | None:
    """Share of the driver's running wall, in %, that the parts for
    which `parts(name)` is true took."""
    delta = engine_delta(win)
    if delta is None:
        return None
    run = delta.pop("run")
    return 100.0 * sum(v for k, v in delta.items() if parts(k)) / run


def mean_device(win, value) -> float | None:
    """Mean of `value(record)` over the window's device-path passes
    (every kind but the legacy host walk) that carry device counts."""
    vals = [value(c) for c in win.cycles
            if c["kind"] != "legacy" and "h2d_bytes" in c]
    return float(np.mean(vals)) if vals else None
