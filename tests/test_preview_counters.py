"""Preview observability pins (ISSUE 10 satellites 1+2).

  * `repro_matchmaker_jit_compiles_total` is labelled by entry path —
    the dedicated vmapped preview dispatch ("preview") compiles its own
    executable, separately from the negotiation-cycle jit ("cycle") —
    and `phase_totals()` exposes both the per-path split and the
    pre-label all-paths total;
  * `repro_preview_legacy_total` counts previews forced onto the legacy
    live-offer walk by quantity-reading expressions;
  * the legacy walk's documented error bound — over-count at most one
    cohort slice (`fits(live free)`) per worker, under-count never —
    pinned deterministically and on randomized threshold pools.
"""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.core.classad import ClassAdExpr
from repro.core.jobqueue import Job, JobQueue
from repro.core.worker import Collector, Worker


def add_worker(col, name, ad, start="true", booted=0.0):
    w = Worker(name=name, ad=dict(ad), start_expr=ClassAdExpr(start),
               startup_delay=0.0)
    w.booted_at = booted
    col.advertise(w)
    return w


def n_claimed(q):
    return sum(1 for j in q.jobs() if j.claimed_by)


# -- satellite 1: path-labelled jit-compile counter ---------------------------

def test_jit_compiles_labelled_by_entry_path():
    col = Collector(matchmaker="jax", telemetry=True)
    prof = col.profiler
    assert prof is not None
    for i in range(3):
        add_worker(col, f"w{i}", {"cpus": 8, "memory": 32})
    q = JobQueue()
    for i in range(20):
        q.submit(Job(ad={"request_cpus": 1 + i % 2, "request_memory": 2},
                     runtime_s=60), float(i))

    col.preview(q, 0.0)          # fresh preview bucket -> XLA trace
    by_path = prof.phase_totals()["jit_compiles_by_path"]
    assert by_path.get("preview", 0) >= 1
    n_preview = by_path.get("preview", 0)

    col.preview(q, 0.0)          # warm bucket: no new trace
    by_path = prof.phase_totals()["jit_compiles_by_path"]
    assert by_path.get("preview", 0) == n_preview

    col.run_cycle(q, 0.0)        # negotiation jit is a separate program
    totals = prof.phase_totals()
    by_path = totals["jit_compiles_by_path"]
    assert by_path.get("cycle", 0) >= 1
    # the pre-label surface stays the all-paths total
    assert totals["jit_compiles"] == sum(by_path.values())


# -- satellite 2: legacy-walk counter -----------------------------------------

def test_preview_legacy_counter_counts_quantity_forced_walks():
    col = Collector(matchmaker="numpy")
    add_worker(col, "w0", {"cpus": 8, "memory": 32})
    q = JobQueue()
    q.submit(Job(ad={"request_cpus": 1}, runtime_s=60), 0.0)
    assert col.preview_legacy == 0
    col.preview(q, 0.0)                      # quantity-blind: fast path
    assert col.preview_legacy == 0

    col2 = Collector(matchmaker="numpy")
    add_worker(col2, "w0", {"cpus": 8, "memory": 32}, start="cpus >= 2")
    col2.preview(q, 0.0)                     # START reads offered cpus
    assert col2.preview_legacy == 1
    # a batched candidate preview is still ONE forced walk
    col2.preview_candidates(q, 0.0, frees=[np.array([[8., 0, 32, 0, 0, 0]]),
                                           np.array([[4., 0, 32, 0, 0, 0]])])
    assert col2.preview_legacy == 2


# -- satellite 2: the documented error bound ----------------------------------

def quantity_pool(n_jobs=4):
    """The shrinking-offer classic: 'gpus >= 2' on a 4-GPU slot admits
    only 3 one-GPU claims live (4->3->2, then the offer of 1 fails
    START), but a dry run evaluating the FULL ad admits the whole
    cohort slice."""
    q = JobQueue()
    for _ in range(n_jobs):
        q.submit(Job(ad={"request_gpus": 1}, runtime_s=10), 0.0)
    col = Collector()
    add_worker(col, "w0", {"cpus": 8, "gpus": 4}, start="gpus >= 2")
    return q, col


def test_preview_legacy_error_bound_deterministic():
    qa, ca = quantity_pool()
    (per_q,) = ca.preview(qa, 0.0)
    assert ca.preview_legacy == 1
    previewed = sum(per_q.values())
    assert previewed == 4         # one full cohort slice, stale verdict

    qb, cb = quantity_pool()
    actual = cb.run_cycle(qb, 0.0)
    assert actual == 3            # live offers shrink 4 -> 3 -> 2 -> fail
    over = previewed - actual
    assert over == 1
    # the documented bound: over-count <= the first mis-admitted slice,
    # fits(live free) jobs, per worker — here fits(4 gpus, 1/job) = 4
    assert 0 < over <= 4


def test_preview_legacy_never_undercounts_threshold_pools():
    """Monotone (>= threshold) quantity expressions: preview >= actual,
    and over-count per pool stays under the per-worker slice bound."""
    rng = np.random.default_rng(59)
    for trial in range(10):
        n_workers = int(rng.integers(1, 5))
        thresholds = [int(rng.integers(1, 4)) for _ in range(n_workers)]
        caps = [int(rng.integers(2, 9)) for _ in range(n_workers)]

        def build():
            col = Collector()
            for i in range(n_workers):
                add_worker(col, f"w{i}", {"cpus": caps[i], "memory": 64},
                           start=f"cpus >= {thresholds[i]}")
            q = JobQueue()
            for c in range(int(rng.integers(1, 4))):
                for _ in range(int(rng.integers(1, 7))):
                    q.submit(Job(ad={"request_cpus": 1 + c % 2,
                                     "request_memory": 1 + c},
                                 runtime_s=30), float(c))
            return q, col

        state = rng.bit_generator.state
        qa, ca = build()
        rng.bit_generator.state = state      # identical twin pool
        qb, cb = build()
        (per_q,) = ca.preview(qa, 0.0)
        previewed = sum(per_q.values())
        actual = cb.run_cycle(qb, 0.0)
        assert previewed >= actual, f"trial={trial} under-count"
        # loose form of the bound: one slice of at most cap jobs/worker
        assert previewed - actual <= sum(caps), f"trial={trial}"
