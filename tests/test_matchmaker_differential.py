"""Differential property tests: every matchmaker backend is claim-for-
claim identical (ISSUE 6 satellite 4).

Three layers:
  * pure problems — seeded-random `MatchProblem`s solved by numpy/jax/
    scan, takes matrices compared exactly (plus hypothesis-driven
    variants when the package is installed);
  * end-to-end collector — identical pools negotiated with
    `matchmaker="numpy"` vs `"jax"`, the (jid -> worker) claim maps must
    coincide;
  * flocking fair-share — a 3-schedd federation with quotas and priority
    factors, water-filled on both backends: identical splits, identical
    accountant books.
"""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from _hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st

from repro.core.classad import ClassAdExpr
from repro.core.fairshare import Accountant, ScheddSpec
from repro.core.jobqueue import Job, JobQueue
from repro.core.matchmaker import (
    MatchPlan, MatchProblem, NumpyMatchmaker, ScanMatchmaker,
    make_matchmaker,
)
from repro.core.worker import Collector, Worker

R = 6   # RESOURCE_KEYS width; column 0 is cpus

# the device backends' two modes: float64 is exact on any quantities,
# float32 (the TPU's) only on integer ones — fractional problems run in
# float64 alone
DTYPES = ("float32", "float64")
DTYPE_CASES = [(False, "float32"), (False, "float64"), (True, "float64")]


def pallas(dtype="float64"):
    """The Pallas backend evaluated on the host (interpret mode)."""
    return make_matchmaker("pallas", dtype=dtype, interpret=True)


def random_problem(rng, *, C=None, W=None, fractional=False,
                   sparse_compat=True, gpus=True):
    C = C if C is not None else int(rng.integers(1, 40))
    W = W if W is not None else int(rng.integers(1, 30))
    requests = np.zeros((C, R))
    requests[:, 0] = rng.integers(1, 5, size=C)            # cpus >= 1
    requests[:, 2] = rng.integers(0, 9, size=C)            # memory
    if gpus:
        requests[:, 1] = rng.integers(0, 3, size=C)
    if fractional:
        requests[:, 0] += rng.choice([0.0, 0.25, 0.5], size=C)
        requests[:, 2] *= 0.4
    demand = rng.integers(1, 60, size=C).astype(np.int64)
    free = np.zeros((W, R))
    free[:, 0] = rng.integers(1, 17, size=W)
    free[:, 2] = rng.integers(0, 65, size=W)
    if gpus:
        free[:, 1] = rng.integers(0, 9, size=W)
    if fractional:
        free[:, 2] *= 0.4
    compat = (rng.random((C, W)) < 0.8 if sparse_compat
              else np.ones((C, W), dtype=bool))
    order = rng.permutation(C).astype(np.int64)
    return MatchProblem(
        keys=[(0, i) for i in range(C)], requests=requests,
        demand=demand, order=order, free=free, capacity=free.copy(),
        compat=np.asarray(compat, dtype=bool))


def assert_plans_equal(a: MatchPlan, b: MatchPlan, label: str):
    assert a.takes.shape == b.takes.shape
    np.testing.assert_array_equal(a.takes, b.takes, err_msg=label)
    np.testing.assert_allclose(a.free_after, b.free_after, atol=1e-7,
                               err_msg=label)


# -- pure problems: numpy vs jax ---------------------------------------------

@pytest.mark.parametrize("fractional,dtype", DTYPE_CASES)
def test_jax_identical_on_random_problems(fractional, dtype):
    jaxmm = make_matchmaker("jax", dtype=dtype)
    ref = NumpyMatchmaker()
    rng = np.random.default_rng(7 + fractional)
    for trial in range(40):
        p = random_problem(rng, fractional=fractional)
        assert_plans_equal(ref.match(p), jaxmm.match(p),
                           f"trial={trial} fractional={fractional}")


@pytest.mark.parametrize("dtype", DTYPES)
def test_jax_identical_under_budget_and_active(dtype):
    jaxmm = make_matchmaker("jax", dtype=dtype)
    ref = NumpyMatchmaker()
    rng = np.random.default_rng(11)
    for trial in range(25):
        p = random_problem(rng)
        budget = int(rng.integers(1, 1 + int(p.demand.sum())))
        active = rng.random(p.n_cohorts) < 0.6
        assert_plans_equal(ref.match(p, budget=budget),
                           jaxmm.match(p, budget=budget),
                           f"budget trial={trial}")
        assert_plans_equal(ref.match(p, active=active),
                           jaxmm.match(p, active=active),
                           f"active trial={trial}")
        assert_plans_equal(ref.match(p, budget=budget, active=active),
                           jaxmm.match(p, budget=budget, active=active),
                           f"both trial={trial}")


@pytest.mark.parametrize("dtype", DTYPES)
def test_jax_padding_boundaries(dtype):
    """Cohort/worker counts straddling the chunk (256) and lane (128)
    buckets — padding rows must take nothing."""
    jaxmm = make_matchmaker("jax", dtype=dtype)
    ref = NumpyMatchmaker()
    rng = np.random.default_rng(13)
    for C in (1, 255, 256, 257):
        for W in (1, 127, 128, 129):
            p = random_problem(rng, C=C, W=W)
            assert_plans_equal(ref.match(p), jaxmm.match(p),
                               f"C={C} W={W}")


@pytest.mark.parametrize("dtype", DTYPES)
def test_jax_drain_guard_exact_when_pool_exhausts(dtype):
    """Demand >> supply: later chunks are skipped by the drain guard —
    skipping must be claim-exact, including zero-CPU-request cohorts
    (they disarm the guard)."""
    jaxmm = make_matchmaker("jax", dtype=dtype)
    ref = NumpyMatchmaker()
    rng = np.random.default_rng(17)
    p = random_problem(rng, C=600, W=4)
    assert_plans_equal(ref.match(p), jaxmm.match(p), "drain")
    p2 = random_problem(rng, C=600, W=4)
    p2.requests[300:, 0] = 0.0       # zero-cpu cohorts in late chunks
    assert_plans_equal(ref.match(p2), jaxmm.match(p2), "drain+zero-cpu")


# -- pure problems: pallas water-fill kernel (interpret mode) ----------------

@pytest.mark.parametrize("fractional,dtype", DTYPE_CASES)
def test_pallas_interpret_identical_on_random_problems(fractional, dtype):
    """The Pallas kernel in interpret mode (what CPU CI runs) must be
    bit-identical to BOTH the jax scan and the numpy reference — the
    same arithmetic in a different program shape."""
    pmm = pallas(dtype)
    jaxmm = make_matchmaker("jax", dtype=dtype)
    ref = NumpyMatchmaker()
    rng = np.random.default_rng(31 + fractional)
    for trial in range(12):
        p = random_problem(rng, fractional=fractional)
        plan_p = pmm.match(p)
        label = f"trial={trial} fractional={fractional}"
        np.testing.assert_array_equal(ref.match(p).takes, plan_p.takes,
                                      err_msg=label)
        plan_j = jaxmm.match(p)
        np.testing.assert_array_equal(plan_j.takes, plan_p.takes,
                                      err_msg=label)
        np.testing.assert_array_equal(plan_j.free_after, plan_p.free_after,
                                      err_msg=label + " free (bitwise)")


@pytest.mark.parametrize("dtype", DTYPES)
def test_pallas_interpret_budget_and_drain(dtype):
    """Claim budgets thread through the kernel's SMEM scalar, and the
    in-kernel drain guard must skip chunks claim-exactly when the pool
    exhausts (demand >> supply)."""
    pmm = pallas(dtype)
    ref = NumpyMatchmaker()
    rng = np.random.default_rng(37)
    for trial in range(8):
        p = random_problem(rng)
        budget = int(rng.integers(1, 1 + int(p.demand.sum())))
        assert_plans_equal(ref.match(p, budget=budget),
                           pmm.match(p, budget=budget),
                           f"budget trial={trial}")
    p = random_problem(rng, C=600, W=4)
    assert_plans_equal(ref.match(p), pmm.match(p), "drain")


@pytest.mark.parametrize("dtype", DTYPES)
def test_pallas_padding_boundaries(dtype):
    """Chunk/lane bucket edges — padding cohorts and workers must take
    nothing."""
    pmm = pallas(dtype)
    ref = NumpyMatchmaker()
    rng = np.random.default_rng(41)
    for C in (1, 63, 64, 65):
        for W in (1, 127, 128, 129):
            p = random_problem(rng, C=C, W=W)
            assert_plans_equal(ref.match(p), pmm.match(p), f"C={C} W={W}")


def test_collector_run_cycle_pallas_equals_numpy():
    for seed in range(3):
        ca, qa = build_pool("numpy", rng_seed=seed)
        cb, qb = build_pool(pallas(), rng_seed=seed)
        assert ca.run_cycle(qa, 0.0) == cb.run_cycle(qb, 0.0)
        assert claim_map(qa) == claim_map(qb), f"seed={seed}"


@pytest.mark.parametrize("backend", ["jax", "pallas"])
def test_float32_refuses_problems_outside_its_exact_domain(backend):
    """float32 is exact only on integer quantities below 2**24 (demand
    below 2**23): anything else must raise, never return claims that
    differ from the reference."""
    mm = (pallas("float32") if backend == "pallas"
          else make_matchmaker("jax", dtype="float32"))
    rng = np.random.default_rng(43)
    frac = random_problem(rng, fractional=True)
    frac.requests[0, 0] = 1.5
    with pytest.raises(ValueError, match="float64"):
        mm.match(frac)
    big_free = random_problem(rng)
    big_free.free[0, 2] = 2.0 ** 24
    with pytest.raises(ValueError, match="float64"):
        mm.match(big_free)
    big_demand = random_problem(rng)
    big_demand.demand[0] = 2 ** 23
    with pytest.raises(ValueError, match="float64"):
        mm.match(big_demand)
    ok = random_problem(rng)
    assert_plans_equal(NumpyMatchmaker().match(ok), mm.match(ok), "in range")


def test_exact_floor_f32_repairs_an_off_by_one_division():
    """A TPU's float32 division is not correctly rounded: an exact
    multiple can floor one short, a near-multiple one long.  From
    either, the repair must land on the exact floor of the integers."""
    import jax.numpy as jnp
    from repro.core.matchmaker.jax_backend import exact_floor_f32
    rng = np.random.default_rng(47)
    Wn = 512
    want = rng.integers(0, 4_096, size=(R, 1)).astype(np.float64)
    want[0] = np.maximum(want[0], 1)                # cpus always asked
    k = rng.integers(0, 4_096, size=(R, Wn))
    rem = (rng.random((R, Wn)) < 0.5) * rng.integers(0, 4_096, size=(R, Wn))
    free = want * k + np.minimum(rem, np.maximum(want - 1, 0))
    exact = np.min(np.where(want > 0, free // np.maximum(want, 1), np.inf),
                   axis=0, keepdims=True)
    for off in (-1.0, 0.0, 1.0):
        got = exact_floor_f32(jnp.asarray(exact + off, jnp.float32),
                              jnp.asarray(free, jnp.float32),
                              jnp.asarray(want, jnp.float32))
        np.testing.assert_array_equal(np.asarray(got), exact,
                                      err_msg=f"off={off}")


# -- pure problems: numpy vs scan oracle -------------------------------------

def test_scan_oracle_matches_reference_cohort_contiguous():
    """With jobs visited cohort-contiguously in processing order, the
    per-job oracle and the vectorized walk make identical claims
    (integer resources; the oracle never divides).

    Restricted to cpu+memory pools: the seed oracle retires a worker
    once ANY declared countable resource exhausts (a gpu slot out of
    gpus stops taking cpu-only jobs), which the cohort walk — and real
    partitionable slots — do not.  When cpus are the only exhaustible
    resource, retirement coincides with nothing-fits and the two are
    identical; that documented divergence is why the scan stays an
    oracle, not a backend for mixed pools."""
    scan = ScanMatchmaker()
    ref = NumpyMatchmaker()
    rng = np.random.default_rng(23)
    for trial in range(30):
        p = random_problem(rng, gpus=False)
        assert_plans_equal(ref.match(p), scan.match(p), f"trial={trial}")


# -- hypothesis variants (skip cleanly when not installed) -------------------

@settings(max_examples=30, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2**31 - 1),
       fractional=st.booleans())
def test_hypothesis_jax_identical(seed, fractional):
    rng = np.random.default_rng(seed)
    p = random_problem(rng, fractional=fractional)
    assert_plans_equal(NumpyMatchmaker().match(p),
                       make_matchmaker("jax").match(p),
                       f"seed={seed}")


@settings(max_examples=30, deadline=None)
@given(seed=st.integers(min_value=0, max_value=2**31 - 1))
def test_hypothesis_scan_identical(seed):
    rng = np.random.default_rng(seed)
    p = random_problem(rng, gpus=False)
    assert_plans_equal(NumpyMatchmaker().match(p),
                       ScanMatchmaker().match(p), f"seed={seed}")


# -- end-to-end collector differential ---------------------------------------

def build_pool(matchmaker, rng_seed=0, n_workers=12, n_jobs=200,
               gpus=True):
    rng = np.random.default_rng(rng_seed)
    col = Collector(matchmaker=matchmaker)
    for i in range(n_workers):
        ad = {"cpus": int(rng.integers(2, 17)),
              "memory": int(rng.integers(8, 65))}
        g = int(rng.integers(0, 5))
        if gpus and g:
            ad["gpus"] = g
        w = Worker(name=f"w{i:02d}", ad=ad,
                   start_expr=ClassAdExpr("true"))
        w.booted_at = 0.0
        col.advertise(w)
    q = JobQueue()
    for i in range(n_jobs):
        ad = {
            "request_cpus": int(rng.integers(1, 5)),
            "request_memory": int(rng.integers(1, 9)),
            "user": f"u{int(rng.integers(0, 4))}",
        }
        g = int(rng.integers(0, 2))
        if gpus and g:
            ad["request_gpus"] = g
        q.submit(Job(ad=ad, runtime_s=60), float(i))
    return col, q


def claim_map(q):
    return {j.jid: j.claimed_by for j in q.jobs() if j.claimed_by}


def test_collector_run_cycle_jax_equals_numpy():
    for seed in range(5):
        ca, qa = build_pool("numpy", rng_seed=seed)
        cb, qb = build_pool("jax", rng_seed=seed)
        na = ca.run_cycle(qa, 0.0)
        nb = cb.run_cycle(qb, 0.0)
        assert na == nb
        assert claim_map(qa) == claim_map(qb), f"seed={seed}"


def test_collector_run_cycle_scan_backend_equals_numpy():
    # cpu/memory pools only: see the scan-oracle docstring above
    for seed in range(3):
        ca, qa = build_pool("numpy", rng_seed=seed, gpus=False)
        cb, qb = build_pool("scan", rng_seed=seed, gpus=False)
        assert ca.run_cycle(qa, 0.0) == cb.run_cycle(qb, 0.0)
        assert claim_map(qa) == claim_map(qb), f"seed={seed}"


# -- flocking fair-share on both backends ------------------------------------

def build_federation(matchmaker, rng_seed=1):
    rng = np.random.default_rng(rng_seed)
    specs = [ScheddSpec(name="osg", quota=3.0,
                        priority_factors={"heavy": 4.0}),
             ScheddSpec(name="cms", quota=1.0),
             ScheddSpec(name="icecube", quota=2.0)]
    acct = Accountant()
    col = Collector(matchmaker=matchmaker)
    for i in range(16):
        w = Worker(name=f"w{i:02d}", ad={"cpus": 4, "memory": 32},
                   start_expr=ClassAdExpr("true"))
        w.booted_at = 0.0
        col.advertise(w)
    queues = []
    for spec in specs:
        q = JobQueue(name=spec.name)
        acct.set_quota(spec.name, spec.quota)
        for u, f in spec.priority_factors.items():
            acct.set_priority_factor(u, f)
        acct.attach_queue(spec.name, q)
        for i in range(40):
            q.submit(Job(ad={
                "request_cpus": int(rng.integers(1, 3)),
                "request_memory": int(rng.integers(1, 5)),
                "user": rng.choice(["alice", "bob", "heavy"]),
            }, runtime_s=300), float(i))
        queues.append(q)
    return col, queues, acct


def test_flocking_fairshare_jax_equals_numpy():
    ca, qsa, aa = build_federation("numpy")
    cb, qsb, ab = build_federation("jax")
    na = ca.run_cycle(qsa, 0.0, accountant=aa, quantum=2)
    nb = cb.run_cycle(qsb, 0.0, accountant=ab, quantum=2)
    assert na == nb and na > 0
    for qa, qb in zip(qsa, qsb):
        assert claim_map(qa) == claim_map(qb), qa.name
    # identical books: same rates, same effective priorities
    sa, sb = aa.snapshot(0.0), ab.snapshot(0.0)
    assert sa == sb


def test_flocking_fairshare_split_respects_quotas_both_backends():
    """The 3:1:2-quota pool split must come out identical (and quota-
    proportional) on both backends."""
    for mm in ("numpy", "jax"):
        col, queues, acct = build_federation(mm, rng_seed=3)
        col.run_cycle(queues, 0.0, accountant=acct, quantum=1)
        by_schedd = [sum(1 for j in q.jobs() if j.claimed_by)
                     for q in queues]
        if mm == "numpy":
            ref_split = by_schedd
        else:
            assert by_schedd == ref_split
        assert by_schedd[0] > by_schedd[1]    # quota 3 beats quota 1
