"""Matchmaker protocol surface: registry, selection plumbing, the
LRU-bounded eval caches, and the deprecation shims (ISSUE 6 tentpole +
satellites 1/3)."""
import json
import sys
import warnings
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.core.classad import ClassAdExpr
from repro.core.config import ProvisionerConfig, dump_ini, load_ini
from repro.core.jobqueue import Job, JobQueue
from repro.core.matchmaker import (
    MatchPlan, MatchProblem, Matchmaker, NumpyMatchmaker,
    ScanMatchmaker, make_matchmaker, matchmaker_names,
)
from repro.core.simulation import Simulation
from repro.core.worker import Collector, LRUCache, Worker


def mk_problem(requests, demand, free, compat=None, order=None):
    requests = np.asarray(requests, dtype=np.float64)
    demand = np.asarray(demand, dtype=np.int64)
    free = np.asarray(free, dtype=np.float64)
    C, W = len(demand), len(free)
    if compat is None:
        compat = np.ones((C, W), dtype=bool)
    return MatchProblem(
        keys=[(0, i) for i in range(C)], requests=requests,
        demand=demand,
        order=np.arange(C, dtype=np.int64) if order is None
        else np.asarray(order, dtype=np.int64),
        free=free.copy(), capacity=free.copy(),
        compat=np.asarray(compat, dtype=bool))


def mk_pool(n_workers=3, cpus=4, matchmaker=None):
    col = Collector(matchmaker=matchmaker)
    for i in range(n_workers):
        w = Worker(name=f"w{i}", ad={"cpus": cpus, "memory": 16},
                   start_expr=ClassAdExpr("true"))
        w.booted_at = 0.0
        col.advertise(w)
    return col


def mk_queue(n=10, **ad):
    q = JobQueue()
    base = {"request_cpus": 1}
    base.update(ad)
    for i in range(n):
        q.submit(Job(ad=dict(base), runtime_s=60), float(i))
    return q


# -- registry / selection ----------------------------------------------------

def test_registry_lists_all_backends():
    names = matchmaker_names()
    assert {"numpy", "scan", "jax"} <= set(names)


def test_make_matchmaker_resolution():
    assert make_matchmaker().name == "numpy"
    assert make_matchmaker(None).name == "numpy"
    assert make_matchmaker("scan").name == "scan"
    inst = NumpyMatchmaker()
    assert make_matchmaker(inst) is inst
    with pytest.raises(ValueError, match="unknown matchmaker"):
        make_matchmaker("no-such-backend")
    with pytest.raises(TypeError):
        make_matchmaker(42)


def test_backends_satisfy_protocol():
    assert isinstance(NumpyMatchmaker(), Matchmaker)
    assert isinstance(ScanMatchmaker(), Matchmaker)


def test_jax_backend_config_validation():
    from repro.core.matchmaker import JaxMatchmaker
    assert isinstance(JaxMatchmaker(), Matchmaker)
    with pytest.raises(ValueError, match="dtype"):
        JaxMatchmaker(dtype="float16")


def test_collector_accepts_instance_and_name():
    assert mk_pool().matchmaker.name == "numpy"
    assert mk_pool(matchmaker="scan").matchmaker.name == "scan"
    inst = NumpyMatchmaker()
    assert Collector(matchmaker=inst).matchmaker is inst


def test_simulation_matchmaker_param_and_ini():
    cfg = ProvisionerConfig()
    sim = Simulation(cfg, nodes=[])
    assert sim.collector.matchmaker.name == "numpy"
    # the INI key flows through Simulation -> Collector
    cfg2 = load_ini("[provision]\nmatchmaker=scan\n")
    assert cfg2.matchmaker == "scan"
    sim2 = Simulation(cfg2, nodes=[])
    assert sim2.collector.matchmaker.name == "scan"
    # explicit arg wins over the config
    sim3 = Simulation(cfg2, nodes=[], matchmaker="numpy")
    assert sim3.collector.matchmaker.name == "numpy"
    # dump/load round-trip keeps the key
    assert load_ini(dump_ini(cfg2)).matchmaker == "scan"


# -- pure semantics ----------------------------------------------------------

def test_numpy_budget_and_active_masks():
    p = mk_problem(requests=[[1.0], [1.0]], demand=[5, 5], free=[[8.0]])
    mm = NumpyMatchmaker()
    full = mm.match(p)
    assert full.claimed == 8 and full.per_cohort().tolist() == [5, 3]
    capped = mm.match(p, budget=3)
    assert capped.claimed == 3 and capped.per_cohort().tolist() == [3, 0]
    only2 = mm.match(p, active=np.array([False, True]))
    assert only2.per_cohort().tolist() == [0, 5]
    # the problem is never mutated
    assert p.free.tolist() == [[8.0]] and p.demand.tolist() == [5, 5]


def test_plan_free_after_consistent():
    p = mk_problem(requests=[[2.0, 1.0]], demand=[3],
                   free=[[5.0, 10.0], [4.0, 1.0]])
    plan = NumpyMatchmaker().match(p)
    spent = plan.takes.T.astype(float) @ p.requests
    np.testing.assert_allclose(plan.free_after, p.free - spent)


def test_fits_eps_fractional_requests():
    # 7.6/0.4 is 18.999...96 in binary floats; the eps must count it 19
    p = mk_problem(requests=[[0.4]], demand=[30], free=[[7.6]])
    assert NumpyMatchmaker().match(p).claimed == 19


def test_plan_application_preserves_fifo_identity():
    """Claims land on FIFO jobs dealt to workers in index order — the
    exact (job, worker) pairs of the legacy walk."""
    col = mk_pool(n_workers=2, cpus=2)
    q = mk_queue(n=5)
    assert col.run_cycle(q, 0.0) == 4
    jid_to_worker = {j.jid: j.claimed_by
                     for j in q.jobs() if j.claimed_by}
    assert jid_to_worker == {0: "w0", 1: "w0", 2: "w1", 3: "w1"}


# -- deprecation shims (satellite 1) -----------------------------------------

def test_deprecated_shims_warn_and_delegate():
    col = mk_pool()
    q = mk_queue(n=6)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        n = col.negotiate(q, 0.0)
        col.preview_matches([q], 0.0)
        col.negotiate_scan(q, 0.0)
    assert n == 6
    cats = [r.category for r in rec]
    assert cats.count(DeprecationWarning) == 3
    assert "run_cycle" in str(rec[0].message)


def test_negotiate_cycle_alias_does_not_warn():
    col = mk_pool()
    q = mk_queue(n=3)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        assert col.negotiate_cycle([q], 0.0) == 3
    assert not [r for r in rec if r.category is DeprecationWarning]


# -- LRU caches (satellite 3) ------------------------------------------------

def test_lru_cache_eviction_order():
    c = LRUCache(3)
    for k in "abc":
        c.put(k, k.upper())
    assert c.get("a") == "A"          # refreshes a
    c.put("d", "D")                    # evicts b (least recent)
    assert "b" not in c
    assert "a" in c and "c" in c and "d" in c
    assert len(c) == 3


def test_lru_cache_invalidate_predicate():
    c = LRUCache(10)
    for i in range(6):
        c.put(("cohort", i % 2, i), i)
    assert c.invalidate(lambda k: k[1] == 0) == 3
    assert len(c) == 3
    assert c.invalidate() == 3
    assert len(c) == 0


def test_collector_match_cache_bounded_lru():
    col = mk_pool(n_workers=1)
    col._match_cache.maxsize = 2
    for i in range(4):
        q = mk_queue(n=1, request_memory=i + 1)
        col.preview([q], 0.0)
    assert len(col._match_cache) <= 2


def test_invalidate_cohort_drops_entries():
    col = mk_pool(n_workers=2)
    qa = mk_queue(n=2, request_memory=1)
    qb = mk_queue(n=2, request_memory=2)
    col.preview([qa], 0.0)
    col.preview([qb], 0.0)
    assert len(col._match_cache) == 2      # one per (cohort, shape)
    rep = next(iter(qa.idle_cohorts()))[0]
    assert col.invalidate_cohort(rep) == 1
    assert len(col._match_cache) == 1
    assert col.invalidate_cohort() == 1    # the rest
    assert len(col._match_cache) == 0


def test_snapshot_json_round_trips():
    """Plans/problems built by the collector survive a JSON round-trip of
    the summary path (the bench writes them out)."""
    col = mk_pool()
    q = mk_queue(n=4)
    prev = col.preview([q], 0.0)
    assert json.loads(json.dumps([{str(k): v for k, v in d.items()}
                                  for d in prev]))
