"""Bytes that any implementation of a fair-share pass has to move, from
the unpadded sizes its cycle record carries (``cohorts`` that claimed,
live ``workers``, ``resources``, ``nonzero_takes``, ``entries``).

`bench.roofline`'s convention for one water-fill call, once per pass:
the free matrix read once, and written once if anything was taken; each
claiming cohort's request vector and demand; its compatibility row at
one bit per worker; 4 bytes per nonzero take written back.  A pass also
reads each (schedd, user) entry's key, 8 bytes.  The slices share one
free matrix and one set of cohorts, so a pass that re-reads them per
slice moves more than this bound, never less: a correct implementation
never reads above 100%."""
from __future__ import annotations

from bench.roofline import QUANTITY_BYTES

ENTRY_KEY_BYTES = 8


def pass_bytes(rec: dict) -> int:
    W, R = rec["workers"], rec["resources"]
    live, nnz = rec["cohorts"], rec["nonzero_takes"]
    free = W * R * QUANTITY_BYTES
    return (free * (2 if nnz else 1)
            + live * (R + 1) * QUANTITY_BYTES
            + (live * W + 7) // 8
            + nnz * QUANTITY_BYTES
            + rec["entries"] * ENTRY_KEY_BYTES)
