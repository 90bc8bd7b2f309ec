"""Arithmetic the metric readers share."""
from __future__ import annotations

import numpy as np


def p95_ms(spans) -> float | None:
    """95th percentile of span lengths, in ms, over every span given."""
    if not spans:
        return None
    return float(np.percentile([b - a for a, b in spans], 95)) * 1e3


def union_s(spans) -> float:
    total, end = 0.0, -np.inf
    for a, b in sorted(spans):
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


def mean_phase_ms(win, phase: str) -> float | None:
    """Mean of a profiler phase over the window's device-path passes
    (every kind but the legacy host walk)."""
    vals = [c[phase] for c in win.cycles if c["kind"] != "legacy"]
    return float(np.mean(vals)) * 1e3 if vals else None
