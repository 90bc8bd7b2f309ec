"""The rebuilt pass's expression evaluator agrees with the pool's own
on the expressions the mixes and deployments use, and the rebuilt pass
deals jobs oldest first."""
import pytest

from bench import passes
from bench.probe import CLAIM, IDLE_IN, IDLE_OUT
from repro.core.classad import ClassAdExpr

JOB = {"request_cpus": 2, "request_memory": 32.0, "user": "user03",
       "arch": "gpu"}
OFFERS = [{"cpus": 64, "memory": 512.0, "arch": "gpu"},
          {"cpus": 1, "memory": 16.0},
          {"cpus": 8, "memory": 32.0, "arch": "cpu"}]
EXPRS = ["", "true", "memory >= 32", "arch == 'gpu'", "TARGET.arch == 'cpu'",
         "MY.arch == 'gpu' and memory >= 32", "not (memory < 32)",
         "disk > 1 or cpus >= 8", "user == 'user03'", "gpus > 0",
         "arch != 'gpu'", "1 < cpus <= 8"]


@pytest.mark.parametrize("src", EXPRS)
def test_evaluator_agrees_with_the_pools(src):
    ours = passes.Expr(src)
    theirs = ClassAdExpr(src)
    for offer in OFFERS:
        assert ours(passes._lower(JOB), offer) == theirs.evaluate(JOB, offer)
    assert ours.reads_quantity == bool(
        theirs.refs & set(passes.QUANTITIES))


def _rec(t, user="u0", cpus=1):
    return {"arrival_s": t, "runtime_s": 10.0, "cpus": cpus, "gpus": 0,
            "memory_gb": 2.0, "disk_gb": 8.0, "requirements": "",
            "group": "g", "user": user, "attrs": {}}


def test_rebuilt_pass_serves_the_first_idle_cohort_first_and_deals_fifo():
    recs = [_rec(0.0, "a"), _rec(1.0, "b"), _rec(2.0, "a"), _rec(3.0, "a")]
    log = [(IDLE_IN, j, float(j)) for j in range(4)]
    # job 0 ran and left; cohort "a" keeps its first mark while job 2 waits
    log += [(IDLE_OUT, 0, 3.0), (CLAIM, 0, "w0", 3.0)]
    replay = passes.Replay(recs)
    replay.advance(log, len(log))
    workers = [("w0", {"cpus": 2, "memory": 8.0, "disk": 16.0}, ""),
               ("w1", {"cpus": 1, "memory": 8.0, "disk": 16.0}, "")]
    got = passes.pass_claims(replay, workers)
    # w0 has one cpu left after job 0: cohort "a" (first mark t=0) takes
    # it with its oldest idle job, then w1; cohort "b" gets nothing
    assert got == {2: "w0", 3: "w1"}
    assert passes.claims_differing(recs, log, [
        {"pos": len(log), "workers": workers,
         "claims": [(2, "w0"), (3, "w1")]}]) == ([0], 0)
    assert passes.claims_differing(recs, log, [
        {"pos": len(log), "workers": workers,
         "claims": [(1, "w0"), (2, "w1")]}]) == ([3], 0)


def test_quantity_reading_requirements_are_checked_before_every_claim():
    recs = [dict(_rec(0.0), requirements="memory >= 4") for _ in range(3)]
    log = [(IDLE_IN, j, 0.0) for j in range(3)]
    replay = passes.Replay(recs)
    replay.advance(log, len(log))
    workers = [("w0", {"cpus": 8, "memory": 7.0, "disk": 64.0}, "")]
    # two jobs of 2 GB fit by size (floor(7 / 2) = 3 with three cpus), but
    # after the second the offer holds 3 GB and 'memory >= 4' fails
    assert passes.pass_claims(replay, workers) == {0: "w0", 1: "w0"}
