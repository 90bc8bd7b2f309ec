"""Bytes that any implementation of a water-fill call has to move, and
the chip's peaks they are held against.

A lower bound, from the call's shapes and its answer only: a cohort
that claims nothing may be skipped unread (a drain guard does), so only
cohorts that claimed count.  Per call:

  * the free matrix read once, and written once if anything was taken
    (4 bytes a quantity);
  * each claiming cohort's request vector and demand (4 bytes each);
  * its compatibility row at one bit per worker;
  * 4 bytes per nonzero take written back.

For a preview of N candidates the free matrix and the demand are
counted once per candidate, and the answer is the nonzero absorbed
counts.  A dense (C, W) int32 takes write, a byte-wide mask or a
re-upload of constants is waste against this bound, so a correct
implementation never reads above 100%.
"""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"
QUANTITY_BYTES = 4


def match_bytes(compat: np.ndarray, n_resources: int,
                takes: np.ndarray) -> int:
    takes = np.asarray(takes)
    W = compat.shape[1]
    live = int(np.count_nonzero(takes.any(axis=1)))
    nnz = int(np.count_nonzero(takes))
    free = W * n_resources * QUANTITY_BYTES
    return (free * (2 if nnz else 1)
            + live * (n_resources + 1) * QUANTITY_BYTES
            + (live * W + 7) // 8
            + nnz * QUANTITY_BYTES)


def preview_bytes(compat: np.ndarray, n_resources: int,
                  absorbed: list) -> int:
    W = compat.shape[1]
    if not absorbed:
        return 0
    stack = np.stack([np.asarray(a) for a in absorbed])
    live = int(np.count_nonzero(stack.any(axis=0)))
    nnz = int(np.count_nonzero(stack))
    per_candidate = (W * n_resources + live) * QUANTITY_BYTES
    return (len(absorbed) * per_candidate
            + live * n_resources * QUANTITY_BYTES
            + (live * W + 7) // 8
            + nnz * QUANTITY_BYTES)


def dense_match_bytes(C: int, W: int, n_resources: int) -> int:
    """What a dense implementation moves: a byte-wide mask in, every
    request and demand, the free matrix in and out, and an int32 takes
    matrix out."""
    return (C * W + C * (n_resources + 1) * QUANTITY_BYTES
            + 2 * W * n_resources * QUANTITY_BYTES
            + C * W * QUANTITY_BYTES)


def peaks(device_kind: str) -> dict:
    """The table's entry for this device; an unknown kind is an error."""
    table = json.loads(PEAKS_FILE.read_text())
    try:
        return table[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}") from None
