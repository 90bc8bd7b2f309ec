"""One device program per fair-share pass (`JaxMatchmaker.match_fairshare`)
against the reference slice loop (`base.sequential_match_fairshare` over
the NumPy backend), on the CPU.

The device program must give the host loop's plan claim for claim — the
same summed takes, free capacity and slice count — in float64 and in
float32, where its keys are double-float pairs: keys 1.6e-8 apart, which
float32 alone rounds together, still go in float64's order, and exact
ties go to the schedd's index, then the user's name.  A fair-share pass
of the collector makes one device call, and summed takes dealt once give
each job the worker its slice gave it.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import Accountant, ClassAdExpr, Job, JobQueue, Simulation
from repro.core.matchmaker import JaxMatchmaker, MatchProblem, NumpyMatchmaker
from repro.core.matchmaker.base import (
    KEY_RTOL, FairshareEntries, match_fairshare, sequential_match_fairshare,
)
from repro.core.matchmaker.jax_backend import key_terms
from repro.core.worker import Collector, Worker

R = 6


def random_problem(rng, *, C, W, n_schedds, n_users, quota_spread=True,
                   factor_spread=True):
    """A seeded fair-share pass over whole quantities: C cohorts of
    (schedd, user) entries, W workers, keys in HTCondor's form."""
    requests = np.zeros((C, R))
    requests[:, 0] = rng.integers(1, 9, C)              # cpus
    requests[:, 1] = rng.integers(0, 2, C) * (rng.random(C) < 0.3)
    requests[:, 2] = rng.integers(1, 33, C)              # memory
    requests[:, 3] = rng.integers(1, 9, C)               # disk
    free = np.zeros((W, R))
    free[:, 0] = rng.integers(0, 33, W)
    free[:, 1] = rng.integers(0, 3, W)
    free[:, 2] = rng.integers(0, 129, W)
    free[:, 3] = rng.integers(0, 65, W)
    problem = MatchProblem(
        keys=[(0, c) for c in range(C)], requests=requests,
        demand=rng.integers(1, 7, C).astype(np.int64),
        order=np.arange(C, dtype=np.int64), free=free,
        capacity=free.copy(), compat=rng.random((C, W)) < 0.8)
    schedd_of = rng.integers(0, n_schedds, C)
    user_of = rng.integers(0, n_users, C)
    entry_of: dict = {}
    for g in zip(schedd_of.tolist(), user_of.tolist()):
        entry_of.setdefault(g, len(entry_of))
    schedds = sorted({s for s, _u in entry_of})
    users = sorted({u for _s, u in entry_of})
    s_rank = {s: i for i, s in enumerate(schedds)}
    u_rank = {u: i for i, u in enumerate(users)}
    quota = (rng.uniform(0.1, 1.0, len(schedds)) if quota_spread
             else np.ones(len(schedds)))
    factor = (rng.choice([500.0, 1000.0, 2000.0], len(users))
              if factor_spread else np.ones(len(users)))
    g = rng.uniform(0.0, 300.0, len(schedds))
    u = rng.uniform(0.0, 80.0, len(users))
    entries = FairshareEntries(
        cohort_entry=np.array([entry_of[g_] for g_ in
                               zip(schedd_of.tolist(), user_of.tolist())]),
        cohort_cores=requests[:, 0] + requests[:, 1],
        schedd=np.array([s_rank[s] for s, _u in entry_of]),
        user=np.array([u_rank[u_] for _s, u_ in entry_of]),
        owed=g / quota, owed_slope=1.0 / quota,
        prio=factor * (0.5 + u), prio_slope=factor)
    return problem, entries


def near_ties(problem, entries):
    """Every even-ranked user's key 1.6e-8 above the odd-ranked ones', so
    that the lower name is the worse key by 160x KEY_RTOL: float32 alone
    reads the keys as one and would go by name."""
    U = len(entries.prio)
    prio = 1000.0 * (1.0 + 1.6e-8 * (np.arange(U) % 2 == 0))
    return problem, dataclasses.replace(
        entries, prio=prio, prio_slope=np.full(U, 1000.0),
        owed=np.full(len(entries.owed), 7.0),
        owed_slope=np.full(len(entries.owed), 2.5))


def exact_ties(problem, entries):
    """Every schedd and every user on one key: index, then name."""
    return problem, dataclasses.replace(
        entries, owed=np.full(len(entries.owed), 3.0),
        owed_slope=np.full(len(entries.owed), 1.0),
        prio=np.full(len(entries.prio), 500.0),
        prio_slope=np.full(len(entries.prio), 1000.0))


def retire_at_once(problem, entries):
    """Half the entries fit nowhere: each retires on its first slice."""
    dead = entries.cohort_entry % 2 == 0
    compat = problem.compat.copy()
    compat[dead] = False
    return dataclasses.replace(problem, compat=compat), entries


CASES = {
    "quantum_1": (1, {}, None),
    "quantum_3": (3, {}, None),
    "equal_quotas_and_factors": (1, {"quota_spread": False,
                                     "factor_spread": False}, None),
    "unequal_quotas_and_factors_quantum_2": (2, {}, None),
    "keys_1.6e-8_apart": (1, {}, near_ties),
    "exact_ties": (1, {}, exact_ties),
    "exact_ties_quantum_3": (3, {}, exact_ties),
    "entries_retire_at_once": (2, {}, retire_at_once),
}


def assert_same(ref, got, what):
    np.testing.assert_array_equal(ref.takes, got.takes, err_msg=what)
    np.testing.assert_array_equal(ref.free_after, got.free_after,
                                  err_msg=what)
    assert ref.slices == got.slices, what


@pytest.mark.parametrize("dtype", ["float64", "float32"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_device_pass_equals_host_slice_loop(case, dtype):
    quantum, kw, shape = CASES[case]
    mm = JaxMatchmaker(dtype=dtype)
    for seed in range(6):
        rng = np.random.default_rng([seed, len(case)])
        problem, entries = random_problem(
            rng, C=int(rng.integers(8, 41)), W=int(rng.integers(16, 97)),
            n_schedds=int(rng.integers(2, 6)),
            n_users=int(rng.integers(2, 13)), **kw)
        if shape is not None:
            problem, entries = shape(problem, entries)
        ref = sequential_match_fairshare(NumpyMatchmaker(), problem,
                                         entries, quantum)
        got = mm.match_fairshare(problem, entries, quantum)
        assert_same(ref, got, f"{case} seed={seed}")
        assert got.calls == 1 and ref.calls == ref.slices
        assert ref.slices >= entries.n_entries


def test_near_ties_follow_float64_and_float32_alone_would_not():
    """The case above is a real test of the double-float keys: rounded
    to float32, the user keys tie, and the name would decide."""
    rng = np.random.default_rng(5)
    problem, entries = near_ties(*random_problem(
        rng, C=30, W=60, n_schedds=2, n_users=6))
    assert len(set(np.float32(entries.prio).tolist())) == 1
    low, high = sorted(set(entries.prio.tolist()))
    assert high / low - 1 > 100 * KEY_RTOL
    ref = sequential_match_fairshare(NumpyMatchmaker(), problem, entries, 1)
    got = JaxMatchmaker(dtype="float32").match_fairshare(problem, entries, 1)
    assert_same(ref, got, "near ties")


@pytest.mark.parametrize("a,b", [(0.0, 0.4), (129.19339534973432, 2.5),
                                 (7.0e5, 1000.0), (3.0, 1.0 / 3.0)])
def test_double_float_key_terms_hold_the_key(a, b):
    """Host split of a + b*v: the five float32 terms sum to the float64
    key within 2**-43 relative (the bound `jax_backend` writes down), for
    v up to 2**20."""
    import jax.numpy as jnp

    from repro.core.matchmaker.jax_backend import _dd_affine

    terms = key_terms([a], [b], "float32", 1)
    assert np.all(terms == terms.astype(np.float32))
    for v in (0.0, 1.0, 17.0, 4095.0, 4096.0, 12345.0, 2.0 ** 20):
        hi, lo = _dd_affine(jnp.asarray(terms, jnp.float32),
                            jnp.asarray([v], jnp.float32))
        key = float(hi[0]) + float(lo[0])
        want = a + b * v
        assert abs(key - want) <= 2.0 ** -43 * abs(want), v


# -- the collector's fair-share pass -------------------------------------

def federation(mm, seed=3, quantum=1):
    rng = np.random.default_rng(seed)
    col = Collector(matchmaker=mm)
    for i in range(24):
        w = Worker(name=f"w{i:02d}", ad={"cpus": 8, "memory": 32},
                   start_expr=ClassAdExpr("true"))
        w.booted_at = 0.0
        col.advertise(w)
    acct = Accountant()
    queues = []
    for name, quota in (("cms", 3.0), ("atlas", 1.0), ("ligo", 2.0)):
        q = JobQueue(name=name)
        acct.set_quota(name, quota)
        acct.attach_queue(name, q)
        for i in range(50):
            q.submit(Job(ad={
                "request_cpus": int(rng.integers(1, 4)),
                "request_memory": int(rng.integers(1, 6)),
                "user": str(rng.choice(["alice", "bob", "carol", "dan"])),
            }, runtime_s=300), float(i))
        queues.append(q)
    acct.set_priority_factor("alice", 2.0)
    return col, queues, acct


def claim_map(queues):
    return {j.jid: j.claimed_by for q in queues for j in q.jobs()}


@pytest.mark.parametrize("quantum", [1, 3])
def test_collector_pass_is_one_device_call_with_the_slice_loop_claims(
        quantum):
    calls = {"match": 0, "match_fairshare": 0}
    mm = JaxMatchmaker()
    for name in calls:
        inner = getattr(mm, name)

        def counted(*a, _inner=inner, _name=name, **kw):
            calls[_name] += 1
            return _inner(*a, **kw)
        setattr(mm, name, counted)
    col, queues, acct = federation(mm, quantum=quantum)
    ref_col, ref_queues, ref_acct = federation("numpy", quantum=quantum)
    n = col.run_cycle(queues, 0.0, accountant=acct, quantum=quantum)
    n_ref = ref_col.run_cycle(ref_queues, 0.0, accountant=ref_acct,
                              quantum=quantum)
    assert calls == {"match": 0, "match_fairshare": 1}
    assert n == n_ref > 0
    # summed takes dealt once: the jobs go where the slices put them
    assert claim_map(queues) == claim_map(ref_queues)
    assert acct.snapshot(0.0) == ref_acct.snapshot(0.0)


def test_cycle_record_and_counters_count_slices_and_calls():
    from repro.observability import Telemetry

    for mm, calls_are_slices in (("jax", False), ("numpy", True)):
        col = Collector(matchmaker=mm, telemetry=Telemetry(enabled=True))
        _c, queues, acct = federation("numpy")
        col.workers = _c.workers
        col.run_cycle(queues, 0.0, accountant=acct, quantum=1)
        rec = col.profiler.cycles[-1]
        assert rec["kind"] == "fairshare"
        assert rec["slices"] >= rec["entries"] > 1
        assert rec["device_calls"] == (rec["slices"] if calls_are_slices
                                       else 1)
        assert rec["cohorts"] >= 1 and rec["workers"] == 24
        assert rec["resources"] == R and rec["nonzero_takes"] >= 1
        reg = col.telemetry.registry
        assert (reg.family("repro_fairshare_slices_total").labels().value
                == rec["slices"])
        assert (reg.family("repro_fairshare_device_calls_total")
                .labels().value == rec["device_calls"])


def test_fresh_match_bucket_compiles_its_fairshare_twin():
    """A fair-share pool's warm-up calls `match`; the fair-share program
    of that bucket then meets no fresh bucket in the window."""
    mm = JaxMatchmaker()
    mm.fairshare_twins = True
    rng = np.random.default_rng(11)
    problem, entries = random_problem(rng, C=70, W=130, n_schedds=3,
                                      n_users=5)
    mm.match(dataclasses.replace(problem, demand=np.zeros(70, np.int64)))
    mm.match_fairshare(problem, entries, 1)
    assert mm.last_call["kind"] == "fairshare"
    assert mm.last_call["compiled"] is False
    plain = JaxMatchmaker()
    plain.match_fairshare(problem, entries, 1)
    assert plain.last_call["compiled"] is True


def test_simulation_sets_twins_only_under_fairshare():
    from repro.core import ProvisionerConfig

    cfg = ProvisionerConfig(matchmaker="jax")
    assert Simulation(cfg, schedds=2, fairshare=True
                      ).collector.matchmaker.fairshare_twins
    assert not Simulation(cfg, schedds=2).collector.matchmaker.fairshare_twins


def test_pass_keys_read_the_accountant_affinely():
    acct = Accountant(half_life_s=3600.0, base_priority=0.5,
                      default_factor=1000.0)
    acct.set_quota("cms", 0.4)
    acct.set_priority_factor("prod", 500.0)
    acct.users.add_rate("prod", 12.0, 0.0)
    acct.groups.add_rate("cms", 12.0, 0.0)
    owed, owed_slope, prio, prio_slope = acct.pass_keys(
        ["cms", "ligo"], ["prod", "opp"], 1800.0)
    for v in (0.0, 3.0, 250.0):
        acct.charge_virtual("cms", "prod", v)
        acct.charge_virtual("ligo", "opp", v)
        assert owed[0] + owed_slope[0] * v == pytest.approx(
            acct.group_owed("cms", 1800.0), rel=1e-14)
        assert owed[1] + owed_slope[1] * v == pytest.approx(
            acct.group_owed("ligo", 1800.0), rel=1e-14)
        assert prio[0] + prio_slope[0] * v == pytest.approx(
            acct.effective_priority("prod", 1800.0), rel=1e-14)
        assert prio[1] + prio_slope[1] * v == pytest.approx(
            acct.effective_priority("opp", 1800.0), rel=1e-14)
        acct.reset_cycle()
    assert owed_slope[0] == pytest.approx(1 / 0.4, rel=1e-14)
    assert prio_slope == pytest.approx([500.0, 1000.0], rel=1e-14)
    assert acct._vuser == {} and acct._vgroup == {}


def test_dispatch_takes_the_reference_without_a_fused_pass():
    rng = np.random.default_rng(2)
    problem, entries = random_problem(rng, C=20, W=40, n_schedds=2,
                                      n_users=4)
    plan = match_fairshare(NumpyMatchmaker(), problem, entries, 2)
    ref = sequential_match_fairshare(NumpyMatchmaker(), problem, entries, 2)
    assert_same(ref, plan, "dispatch")
    assert plan.calls == plan.slices
