"""Plain reference of the matchmaking water-fill, and the control.

Written from the stated semantics of one negotiation pass, and sharing
no code with the system under test:

  cohorts are taken in ``order``; each takes, from the workers in index
  order, ``min(fits, remaining demand)`` jobs per worker, where ``fits``
  is the floor of the smallest ratio free/request over the resources
  the cohort requests (a cohort that requests nothing fits anywhere, up
  to its demand), and only on workers its compatibility row allows.
  Every take shrinks that worker's free capacity before the next
  cohort looks.  ``budget`` caps the pass's total claims; ``active``
  leaves cohorts out.

`ControlMatchmaker` is the control: this reference put in the pool's
matchmaker's place, taking cohorts largest demand first instead of in
the pass's FIFO order.  It breaks the deployment's guarantee that
cohorts are served in the order of their oldest idle job, and it is a
step that would tempt a faster pass (big cohorts first drain the pool
in fewer steps, and no host sort is needed).  The correctness check has
to tell it apart.
"""
from __future__ import annotations

import numpy as np

#: added before the floor so that e.g. 7.6/0.4 counts 19 whole slots
EPS = 1e-9


def fits_row(free: np.ndarray, want: np.ndarray, demand: int) -> np.ndarray:
    """Whole jobs of size `want` that each worker row of `free` holds."""
    pos = want > 0
    if not pos.any():
        return np.full(free.shape[0], demand, dtype=np.int64)
    ratio = (free[:, pos] / want[pos]).min(axis=1)
    return np.maximum(np.floor(ratio + EPS), 0).astype(np.int64)


def waterfill(requests, demand, order, compat, free, *, budget=None,
              active=None):
    """One pass: (takes (C, W) int64, free_after (W, R) float64)."""
    requests = np.asarray(requests, dtype=np.float64)
    compat = np.asarray(compat, dtype=bool)
    free = np.array(free, dtype=np.float64, copy=True)
    C, W = compat.shape
    takes = np.zeros((C, W), dtype=np.int64)
    left = None if budget is None else int(budget)
    most = free.max(axis=0) if W else np.zeros(free.shape[1])
    for c in np.asarray(order, dtype=np.int64):
        if left is not None and left <= 0:
            break
        if active is not None and not active[c]:
            continue
        d = int(demand[c])
        if left is not None:
            d = min(d, left)
        if d <= 0:
            continue
        want = requests[c]
        if np.any(want > most):
            continue            # no worker has that much of some resource
        fits = np.where(compat[c], fits_row(free, want, d), 0)
        before = np.cumsum(fits) - fits          # taken by earlier workers
        take = np.clip(d - before, 0, fits)
        if take.any():
            takes[c] = take
            free -= take[:, None] * want[None, :]
            most = free.max(axis=0)
        if left is not None:
            left -= int(take.sum())
    return takes, free


def waterfill_cycles(requests, demand, order, compat, free, deltas):
    """K passes in a row: before each, ``demand += arrivals`` and
    ``free += free_add``; after it, demand and free carry over.
    `deltas` are (arrivals, free_add or None, budget or None)."""
    demand = np.asarray(demand, dtype=np.int64).copy()
    free = np.array(free, dtype=np.float64, copy=True)
    out = []
    for arrivals, free_add, budget in deltas:
        demand = demand + np.asarray(arrivals, dtype=np.int64)
        if free_add is not None:
            free = free + free_add
        takes, free = waterfill(requests, demand, order, compat, free,
                                budget=budget)
        demand = demand - takes.sum(axis=1)
        out.append((takes, free))
    return out


class Plan:
    """A pass's answer in the shape the pool applies: takes, free after."""

    def __init__(self, takes, free_after):
        self.takes = takes
        self.free_after = free_after

    def per_cohort(self):
        return self.takes.sum(axis=1)


class ControlMatchmaker:
    """`waterfill` taking cohorts largest demand first, behind the pool's
    matchmaker calls; a sound check reports it incorrect."""

    name = "control"

    @staticmethod
    def _order(p, demand):
        order = np.asarray(p.order, dtype=np.int64)
        return order[np.argsort(-np.asarray(demand)[order], kind="stable")]

    def match(self, p, *, budget=None, active=None):
        return Plan(*waterfill(p.requests, p.demand,
                               self._order(p, p.demand), p.compat, p.free,
                               budget=budget, active=active))

    def match_cycles(self, p, deltas):
        return [Plan(*out) for out in waterfill_cycles(
            p.requests, p.demand, self._order(p, p.demand), p.compat,
            p.free, [(d.arrivals, d.free_add, d.budget) for d in deltas])]

    def preview_many(self, p, frees, demands=None, **_kw):
        out = []
        for i, f in enumerate(frees):
            d = p.demand if demands is None else demands[i]
            out.append(waterfill(p.requests, d, self._order(p, d),
                                 p.compat, f)[0].sum(axis=1))
        return out
