"""Flocking and fair-share pools, on the CPU: the harness routes each
record to its schedd, samples every kind of pass and rebuilds it from
the deployment's statement, so that a sound run of the test-only
deployments in `data/` is `correct` and a planted fault in the
negotiator's order or its accountant is not."""
import ast
import hashlib
import json
from pathlib import Path

import numpy as np
import pytest

from bench import passes
from bench.cells import BENCH_DIR, Catalog
from bench.probe import CLAIM, DONE, IDLE_IN, IDLE_OUT
from bench.reference import ControlMatchmaker
from bench.run import run_cell

DATA = Path(__file__).resolve().parent / "data"
SMALL = {"warmup_s": 3600.0, "horizon_s": 43200.0}
CELLS = {"flocking": "flocking.communities",
         "fairshare": "fairshare.communities"}


class _WithTestDeployments(Catalog):
    """The benchmark's catalog with the deployments and the mix of
    `data/` beside its own."""

    def __init__(self):
        super().__init__()
        extra = json.loads((DATA / "deployments.json").read_text())
        self.spec = dict(
            self.spec, configs=self.spec["configs"] + extra["configs"],
            workloads=self.spec["workloads"] + extra["workloads"])

    def traffic(self, name):
        path = DATA / f"{name}.json"
        if path.is_file():
            return json.loads(path.read_text())
        return super().traffic(name)


def _run(kind, seed=11, wrap=None):
    return run_cell(_WithTestDeployments(), CELLS[kind], seed, 1.5, False,
                    overrides=SMALL, wrap_matchmaker=wrap)


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_sound_run_is_correct_with_passes_of_its_kind(kind):
    out = _run(kind)
    assert out["correct"], out["checks"]
    assert out["checks"]["checked_passes_missing"]["value"] == 0
    assert out["window"]["sampled"]["pass_kinds"].get(kind, 0) >= 1
    assert set(out["window"]["sampled"]["pass_kinds"]) == {kind}


def _wrap_method(monkeypatch, cls, name, make):
    monkeypatch.setattr(cls, name, make(getattr(cls, name)))


def _flocking_reversed(monkeypatch):
    from repro.core.worker import Collector

    def make(inner):
        def run_cycle(self, queues, now, **kw):
            if not hasattr(queues, "idle_cohorts"):
                queues = list(queues)[::-1]
            return inner(self, queues, now, **kw)
        return run_cycle
    _wrap_method(monkeypatch, Collector, "run_cycle", make)


def _most_used_schedd_first(monkeypatch):
    from repro.core.fairshare import Accountant
    _wrap_method(monkeypatch, Accountant, "group_owed",
                 lambda inner: lambda self, s, now: -inner(self, s, now))


def _worst_priority_user_first(monkeypatch):
    from repro.core.fairshare import Accountant
    _wrap_method(monkeypatch, Accountant, "effective_priority",
                 lambda inner: lambda self, u, now: -inner(self, u, now))


def _virtual_charges_dropped(monkeypatch):
    from repro.core.fairshare import Accountant
    monkeypatch.setattr(Accountant, "charge_virtual",
                        lambda self, schedd, user, cores: None)


def _half_life_of_an_hour(monkeypatch):
    from repro.core.fairshare import Accountant

    def make(inner):
        def init(self, **kw):
            inner(self, **dict(kw, half_life_s=3600.0))
        return init
    _wrap_method(monkeypatch, Accountant, "__init__", make)


FAULTS = {
    "flocking_order_reversed": ("flocking", _flocking_reversed),
    "most_used_schedd_first": ("fairshare", _most_used_schedd_first),
    "worst_priority_user_first": ("fairshare", _worst_priority_user_first),
    "virtual_charges_dropped": ("fairshare", _virtual_charges_dropped),
    "half_life_3600_against_86400": ("fairshare", _half_life_of_an_hour),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(fault, monkeypatch):
    """Each fault leaves every device answer consistent with its own
    problem, so only the pass rebuilt from the statement can tell."""
    kind, plant = FAULTS[fault]
    plant(monkeypatch)
    out = _run(kind)
    assert not out["correct"], (fault, out["checks"])
    assert out["checks"]["claims_differing"]["value"] > 0


def test_control_under_fairshare_is_not_correct():
    out = _run("fairshare", wrap=lambda _mm: ControlMatchmaker())
    assert not out["correct"], out["checks"]


def test_fairshare_deployment_without_its_terms_is_an_error():
    config = json.loads((DATA / "federation-fairshare.json").read_text())
    passes.Deployment(config)
    del config["fairshare"]
    with pytest.raises(ValueError, match="fairshare"):
        passes.Deployment(config)
    config["service"]["schedds"] = 4
    with pytest.raises(ValueError, match="flocking order"):
        passes.Deployment(config)


# -- the rebuilt fair-share pass on a hand-made history ----------------------

def _rec(user, schedd, t=0.0):
    return {"arrival_s": t, "runtime_s": 600.0, "cpus": 1, "gpus": 0,
            "memory_gb": 2.0, "disk_gb": 8.0, "requirements": "",
            "group": "g", "user": user, "attrs": {}, "schedd": schedd}


def _deployment():
    return passes.Deployment({
        "service": {"schedds": ["a", "b"], "fairshare": True},
        "fairshare": {"half_life_s": 86400.0, "base_priority": 0.5,
                      "quantum": 1, "quotas": {"a": 1.0, "b": 1.0},
                      "factors": {}, "default_factor": 1.0}})


def _history(extra_user_y: float):
    """Users x and y of schedd "a" each ran one job from t=0; y's ended
    `extra_user_y` seconds later than x's.  Then each has one job idle
    at t=1000, and one worker has room for one of them."""
    recs = [_rec("x", "a"), _rec("y", "a"), _rec("x", "a", 10.0),
            _rec("y", "a", 10.0)]
    log = [(IDLE_IN, 0, 0.0), (IDLE_IN, 1, 0.0),
           (IDLE_OUT, 0, 0.0), (CLAIM, 0, "old", 0.0),
           (IDLE_OUT, 1, 0.0), (CLAIM, 1, "old", 0.0),
           (DONE, 0, 500.0), (DONE, 1, 500.0 + extra_user_y),
           (IDLE_IN, 2, 10.0), (IDLE_IN, 3, 10.0)]
    workers = [("w", {"cpus": 1, "memory": 8.0, "disk": 16.0}, "")]
    return recs, log, workers


@pytest.mark.parametrize("extra,got,tie,differing", [
    # equal histories: either user may go first, and the pass counts
    (0.0, 2, True, 0), (0.0, 3, True, 0),
    # y ran a millisecond longer: its key lies 1.6e-8 above x's, 16
    # times the tolerance, so x goes first and a pass that served y
    # first reads as wrong
    (0.001, 2, False, 0), (0.001, 3, False, 2),
])
def test_keys_equal_to_rounding_tie_and_a_swapped_order_fails(
        extra, got, tie, differing):
    recs, log, workers = _history(extra)
    sample = {"kind": "fairshare", "pos": len(log), "now": 1000.0,
              "workers": workers, "claims": [(got, "w")]}
    diff, ties = passes.claims_differing(recs, log, [sample],
                                         deployment=_deployment())
    assert (diff, ties) == ([differing], int(tie))


def test_decayed_usage_is_the_closed_form_integral():
    recs, log, _w = _history(0.0)
    replay = passes.Replay(recs, deployment=_deployment())
    replay.advance(log, len(log))
    users, schedds = replay.usage(1000.0, 86400.0)
    # du/dt = 1 - (ln2/hl) u over [0, 500], then decay to 1000, in cores
    lam = np.log(2) / 86400.0
    one = (1 - np.exp(-lam * 500.0)) * np.exp(-lam * 500.0)
    assert users["x"] == pytest.approx(one, rel=1e-12)
    assert schedds[0] == pytest.approx(2 * one, rel=1e-12)


# -- traffic ------------------------------------------------------------------

#: sha256 of the records the generator gave before `communities` existed
PARENT_RECORDS = {
    ("blind-mix", 3):
        "50495f3d6e92927eee92e76a59b89129e74720c4c24b963ff6780daca807d227",
    ("blind-mix", 2**31 + 17):
        "89989fc8bb01c22ac8bf10d8e7bb29339d599d89e2820456edb2279a0dc709b9",
    ("osg-mix", 3):
        "044afb3923c9cb14e81b0d6f2fc0797401ac2b6f59ef58a7164ddf200f9e1631",
    ("osg-mix", 2**31 + 17):
        "2852ecc9c1fc8f665fd17a654f7caef066bc22650d599674ff8203276a74cef9",
    ("saturated", 3):
        "1a010d6bd31e78ef675ba47b4cf62b5b0431568ee97c5f4ff8be4590d49802f4",
    ("saturated", 2**31 + 17):
        "d993d6d4938a427258b162a2bdc0af605ee15d1ecaed2ae8acf032de7878727e",
}


@pytest.mark.parametrize("mix,seed", sorted(PARENT_RECORDS))
def test_mixes_without_communities_give_the_same_records(mix, seed):
    catalog = Catalog()
    recs = catalog.generator("campaign").generate(catalog.traffic(mix), seed)
    digest = hashlib.sha256(json.dumps(recs, sort_keys=True).encode())
    assert digest.hexdigest() == PARENT_RECORDS[(mix, seed)]


def test_communities_keep_each_schedds_work_and_users_across_seeds():
    catalog = _WithTestDeployments()
    mix = dict(catalog.traffic("communities"), **SMALL)
    gen = catalog.generator("campaign")
    a, b = gen.generate(mix, 1), gen.generate(mix, 2**31 + 7)

    def work(recs):
        return (sorted(r["arrival_s"] for r in recs),
                sorted((r["group"], r["runtime_s"]) for r in recs))

    def per_schedd(recs):
        out = {}
        for r in recs:
            out.setdefault(r["schedd"], []).append(r)
        return {s: work(v) for s, v in out.items()}

    def users(recs):
        out = {}
        for r in recs:
            out.setdefault(r["schedd"], set()).add(r["user"])
        return out

    assert per_schedd(a) == per_schedd(b)
    assert [r["user"] for r in a] != [r["user"] for r in b]
    ua, ub = users(a), users(b)
    assert set(ua) == set(ub) == {c["name"] for c in mix["communities"]}
    for c in mix["communities"]:
        # a seed reorders users within their community, never across
        assert len(ua[c["name"]] | ub[c["name"]]) <= c["users"]
    assert len(set().union(*ua.values(), *ub.values())) == sum(
        len(ua[s] | ub[s]) for s in ua)
    # the same arrivals, kinds and runtimes as the mix without them
    assert work(gen.generate(dict(mix, communities=None), 1)) == work(a)


# -- independence of the yardstick --------------------------------------------

def _bench_imports(path: Path, seen: set) -> set:
    """Every module a bench file imports, following bench's own."""
    out = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            names = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            names = [node.module or ""] + [
                f"{node.module}.{a.name}" for a in node.names]
        else:
            continue
        for name in names:
            out.add(name)
            sub = BENCH_DIR.joinpath(*name.split(".")[1:]).with_suffix(".py")
            if name.startswith("bench.") and sub.is_file() \
                    and sub not in seen:
                seen.add(sub)
                out |= _bench_imports(sub, seen)
    return out


@pytest.mark.parametrize("module", ["passes", "reference"])
def test_reference_imports_nothing_of_the_program(module):
    path = BENCH_DIR / f"{module}.py"
    names = _bench_imports(path, {path})
    assert not [n for n in names if n == "repro" or n.startswith("repro.")]
