"""Batched-preview differential: `preview_many` on the jax backend is
bit-identical to the sequential numpy reference (ISSUE 10 tentpole).

The batched path evaluates N INDEPENDENT candidate pools in one jitted
vmap dispatch — no drain guard, device-resident cohort constants —
so every layer that could diverge from the per-candidate loop gets a
pin here:

  * random problems (integer and fractional requests) for N in {1,2,8};
  * per-candidate demand overrides;
  * the `session=` device-constant cache, including reuse across calls
    and invalidation when the cohort processing `order` changes under
    an unchanged session token;
  * padding-bucket edges (chunk and lane boundaries);
  * the base-module dispatcher falling back to the sequential loop for
    backends without a vectorised implementation.
"""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from test_matchmaker_differential import random_problem

from repro.core.matchmaker import NumpyMatchmaker, make_matchmaker
from repro.core.matchmaker.base import preview_many, sequential_preview_many


def random_frees(rng, p, n):
    """N candidate pools shaped like the problem's, scaled/perturbed so
    candidates genuinely differ (including an all-zeros pool)."""
    out = []
    for i in range(n):
        f = p.free * rng.choice([0.0, 0.5, 1.0, 2.0], size=(p.n_workers, 1))
        out.append(np.ascontiguousarray(f))
    return out


def assert_batches_equal(got, want, label):
    assert len(got) == len(want), label
    for i, (g, w) in enumerate(zip(got, want)):
        np.testing.assert_array_equal(
            np.asarray(g), np.asarray(w), err_msg=f"{label} cand={i}")


@pytest.mark.parametrize("fractional", [False, True])
def test_preview_many_matches_sequential_numpy(fractional):
    jaxmm = make_matchmaker("jax")
    ref = NumpyMatchmaker()
    rng = np.random.default_rng(101 + fractional)
    for trial in range(15):
        p = random_problem(rng, fractional=fractional)
        for n in (1, 2, 8):
            frees = random_frees(rng, p, n)
            want = sequential_preview_many(ref, p, frees)
            got = jaxmm.preview_many(p, frees)
            assert_batches_equal(
                got, want, f"trial={trial} n={n} fractional={fractional}")


def test_preview_many_per_candidate_demands():
    jaxmm = make_matchmaker("jax")
    ref = NumpyMatchmaker()
    rng = np.random.default_rng(113)
    for trial in range(10):
        p = random_problem(rng)
        n = int(rng.integers(1, 9))
        frees = random_frees(rng, p, n)
        demands = [rng.integers(0, 40, size=p.n_cohorts).astype(np.int64)
                   for _ in range(n)]
        want = sequential_preview_many(ref, p, frees, demands)
        got = jaxmm.preview_many(p, frees, demands)
        assert_batches_equal(got, want, f"trial={trial} n={n}")


def test_preview_many_session_reuse_and_order_invalidation():
    """A stable session token keeps cohort constants on device across
    calls; results must stay identical to fresh dispatches, and a
    changed processing order under the SAME token must be detected (the
    session validates `problem.order`, not just the token)."""
    jaxmm = make_matchmaker("jax")
    ref = NumpyMatchmaker()
    rng = np.random.default_rng(127)
    p = random_problem(rng, C=37, W=21)
    token = ("pool", "fingerprint")
    for call in range(4):
        frees = random_frees(rng, p, 3)
        want = sequential_preview_many(ref, p, frees)
        got = jaxmm.preview_many(p, frees, session=token)
        assert_batches_equal(got, want, f"session call={call}")
    # same token, permuted order: constants must be rebuilt
    p2 = random_problem(rng, C=37, W=21)
    p2.order = np.roll(p.order, 5)
    p2.requests = p.requests
    p2.demand = p.demand
    p2.free = p.free
    p2.compat = p.compat
    frees = random_frees(rng, p2, 2)
    want = sequential_preview_many(ref, p2, frees)
    got = jaxmm.preview_many(p2, frees, session=token)
    assert_batches_equal(got, want, "order change under stable token")


def test_preview_many_padding_boundaries():
    jaxmm = make_matchmaker("jax")
    ref = NumpyMatchmaker()
    rng = np.random.default_rng(131)
    for C in (1, 63, 64, 65):
        for W in (1, 127, 128, 129):
            p = random_problem(rng, C=C, W=W)
            frees = random_frees(rng, p, 2)
            want = sequential_preview_many(ref, p, frees)
            got = jaxmm.preview_many(p, frees)
            assert_batches_equal(got, want, f"C={C} W={W}")


def test_preview_many_marks_preview_call():
    """The backend self-reports the dedicated preview entry path (the
    profiler's path-labelled jit counter reads this)."""
    jaxmm = make_matchmaker("jax")
    rng = np.random.default_rng(137)
    p = random_problem(rng)
    jaxmm.preview_many(p, [p.free])
    assert jaxmm.last_call["kind"] == "preview"
    assert "compiled" in jaxmm.last_call


def test_dispatcher_routes_jax_and_falls_back_sequential():
    rng = np.random.default_rng(139)
    p = random_problem(rng)
    frees = random_frees(rng, p, 4)
    ref = NumpyMatchmaker()
    want = sequential_preview_many(ref, p, frees)
    # numpy has no vectorised preview: the dispatcher must loop
    assert_batches_equal(preview_many(ref, p, frees), want, "numpy route")
    assert_batches_equal(preview_many(make_matchmaker("jax"), p, frees),
                         want, "jax route")
