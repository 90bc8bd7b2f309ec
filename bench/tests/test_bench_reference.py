"""The plain reference agrees with the pool's own numpy matchmaker, the
control does not, and the byte count stays a lower bound."""
import numpy as np
import pytest

from bench import reference, roofline
from repro.core.matchmaker import MatchProblem, make_matchmaker


def _problem(rng, C, W, R=6, dense=0.6):
    requests = rng.integers(0, 5, size=(C, R)).astype(float)
    free = rng.integers(0, 12, size=(W, R)).astype(float)
    return MatchProblem(
        keys=[(0, c) for c in range(C)], requests=requests,
        demand=rng.integers(0, 9, size=C), order=rng.permutation(C),
        free=free, capacity=free.copy(),
        compat=rng.random((C, W)) < dense)


@pytest.mark.parametrize("seed", range(6))
def test_reference_equals_the_pools_numpy_matchmaker(seed):
    rng = np.random.default_rng(seed)
    p = _problem(rng, 40, 30)
    want = make_matchmaker("numpy").match(p)
    takes, free = reference.waterfill(p.requests, p.demand, p.order,
                                      p.compat, p.free)
    assert np.array_equal(takes, want.takes)
    assert np.array_equal(free, want.free_after)
    budget = int(want.claimed // 2)
    want_b = make_matchmaker("numpy").match(p, budget=budget)
    takes_b, _ = reference.waterfill(p.requests, p.demand, p.order,
                                     p.compat, p.free, budget=budget)
    assert np.array_equal(takes_b, want_b.takes)


def test_reference_cycles_equal_sequential_passes():
    from repro.core.matchmaker.base import CycleDelta, sequential_match_cycles

    rng = np.random.default_rng(3)
    p = _problem(rng, 20, 16)
    deltas = [CycleDelta(arrivals=rng.integers(0, 3, size=20),
                         free_add=rng.integers(0, 2, size=(16, 6)) * 1.0,
                         budget=None if k % 2 else 7) for k in range(3)]
    want = sequential_match_cycles(make_matchmaker("numpy"), p, deltas)
    got = reference.waterfill_cycles(
        p.requests, p.demand, p.order, p.compat, p.free,
        [(d.arrivals, d.free_add, d.budget) for d in deltas])
    for w, (t, f) in zip(want, got):
        assert np.array_equal(w.takes, t) and np.array_equal(w.free_after, f)


def test_control_serves_big_cohorts_first():
    rng = np.random.default_rng(4)
    p = _problem(rng, 60, 20)
    takes, _ = reference.waterfill(p.requests, p.demand, p.order,
                                   p.compat, p.free)
    ctl = reference.ControlMatchmaker().match(p).takes
    assert not np.array_equal(takes, ctl)
    big = int(np.argmax(p.demand))
    assert ctl[big].sum() >= takes[big].sum()


@pytest.mark.parametrize("seed", range(5))
def test_byte_count_never_exceeds_a_dense_implementation(seed):
    rng = np.random.default_rng(seed)
    C, W = int(rng.integers(1, 300)), int(rng.integers(1, 200))
    p = _problem(rng, C, W, dense=rng.random())
    takes, free = reference.waterfill(p.requests, p.demand, p.order,
                                      p.compat, p.free)
    dense = roofline.dense_match_bytes(C, W, 6)
    assert 0 < roofline.match_bytes(p.compat, 6, takes) <= dense
    absorbed = [takes.sum(axis=1), takes.sum(axis=1) // 2]
    assert roofline.preview_bytes(p.compat, 6, absorbed) <= 2 * dense


def test_unknown_device_kind_has_no_peaks():
    assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
