"""A whole run, on the CPU: the entry point refuses to report without a
TPU, and the rest of a run (everything after the look for a chip) says
`correct` for the pool as it is and not for the control or for a pool
whose device answers are broken."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from bench.cells import ROOT, Catalog
from bench.reference import ControlMatchmaker, Plan
from bench.run import run_cell

SMALL = {"warmup_s": 3600.0, "horizon_s": 43200.0}


def _run(wrap=None, seed=11, cell="federation.blind-mix"):
    return run_cell(Catalog(), cell, seed, 1.5, False, overrides=SMALL,
                    wrap_matchmaker=wrap)


def test_entry_point_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "federation.blind-mix",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"correct": true' not in proc.stdout
    assert "TPU" in proc.stderr


def test_sound_run_is_correct_and_reports_its_metrics():
    out = _run()
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {
        "claims_per_s", "negotiation_p95_ms", "reconcile_p95_ms", "setup_s"}
    assert out["window"]["sampled"]["match"] > 0
    assert list(out)[-1] == "checks"
    json.dumps(out)


def test_control_in_the_matchmakers_place_is_not_correct():
    out = _run(lambda _mm: ControlMatchmaker())
    assert not out["correct"]
    assert out["checks"]["plan_cells_differing"]["value"] > 0


class _Broken:
    """The pool's matchmaker with one fault planted in its answers."""

    def __init__(self, inner, fault):
        self._inner, self.fault = inner, fault

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def match(self, p, **kw):
        plan = self._inner.match(p, **kw)
        takes = np.array(plan.takes)
        free = np.array(plan.free_after)
        if self.fault == "unchanged":
            takes[:] = 0
            free = np.array(p.free, copy=True)
        elif self.fault == "half_left_out":
            takes[1::2] = 0
        elif self.fault == "answer_altered":
            nz = np.argwhere(takes > 0)
            if len(nz):
                c, w = nz[0]
                takes[c, w] -= 1
                takes[c, (w + 1) % takes.shape[1]] += 1
        return Plan(takes, free)


@pytest.mark.parametrize("fault", ["unchanged", "half_left_out",
                                   "answer_altered"])
def test_broken_device_answers_are_not_correct(fault):
    out = _run(lambda mm: _Broken(mm, fault))
    assert not out["correct"], (fault, out["checks"])


def test_host_order_fault_is_not_correct(monkeypatch):
    """Cohorts served newest first by the host's build: the device sees
    a consistent problem, so only the pass rebuilt from the trace can
    tell."""
    from repro.core.jobqueue import JobQueue

    first = JobQueue.cohort_first_submit
    monkeypatch.setattr(JobQueue, "cohort_first_submit",
                        lambda q, key: tuple(-x for x in first(q, key)))
    out = _run()
    assert not out["correct"]
    assert out["checks"]["claims_differing"]["value"] > 0
    assert out["checks"]["plan_cells_differing"]["value"] == 0


def test_legacy_host_passes_are_checked_and_correct():
    out = run_cell(Catalog(), "federation.osg-mix", 13, 1.5, True,
                   overrides=SMALL)
    assert out["correct"], out["checks"]
    assert out["metrics"]["legacy_pass_pct"]["value"] > 0
    assert out["window"]["sampled"]["passes"] > 0


def test_traced_run_reports_the_layer_metrics():
    out = run_cell(Catalog(), "federation.blind-mix", 12, 1.5, True,
                   overrides=SMALL)
    assert out["correct"], out["checks"]
    assert {"build_ms", "match_ms", "apply_ms", "preview_ms",
            "legacy_pass_pct", "window_compiles",
            "loop_other_pct"} <= set(out["metrics"])
    assert out["metrics"]["legacy_pass_pct"]["value"] == 0.0
    assert "window_s" in out["device"] and "breakdown" in out
