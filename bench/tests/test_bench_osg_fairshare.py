"""The multi-community OSG fair-share deployment (`federation.fairshare`)
on the CPU: its INI states the terms its `fairshare` block restates for
the check, a sound run reads `correct` and the control does not, the
device program claims what the NumPy slice loop claims over 12 simulated
hours, and the cell's three readers read the program's records."""
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import passes, roofline_fairshare
from bench.cells import Catalog
from bench.reference import ControlMatchmaker
from bench.run import route, run_cell

CELL = "federation.fairshare"
SMALL = {"warmup_s": 3600.0, "horizon_s": 43200.0}
DATA = Path(__file__).resolve().parent / "data"


def _config():
    catalog = Catalog()
    return catalog.config(catalog.cell(CELL)["config"])


def test_ini_states_the_terms_of_the_fairshare_block():
    from repro.core import load_ini

    config = _config()
    block = config["fairshare"]
    fs = load_ini(config["ini"]).fairshare
    assert fs.half_life_s == block["half_life_s"]
    assert fs.base_priority == block["base_priority"]
    assert fs.default_factor == block["default_factor"]
    assert fs.quantum == block["quantum"]
    assert fs.quotas == block["quotas"]
    assert fs.factors == block["factors"]
    assert set(fs.quotas) == set(config["service"]["schedds"])
    passes.Deployment(config)                  # the check accepts it


def test_the_pool_is_the_burst_federations():
    """The INI is `k8s-burst-federation`'s with a [fairshare] section."""
    base = Catalog().config("k8s-burst-federation")
    ini = _config()["ini"]
    assert ini.startswith(base["ini"])
    assert ini[len(base["ini"]):].lstrip().startswith("[fairshare]")


def test_sound_run_is_correct_and_the_control_is_not():
    out = run_cell(Catalog(), CELL, 3717000213, 1.5, False, overrides=SMALL)
    assert out["correct"], out["checks"]
    assert out["checks"]["checked_passes_missing"]["value"] == 0
    assert out["window"]["sampled"]["pass_kinds"].get("fairshare", 0) >= 1
    assert set(out["window"]["sampled"]["pass_kinds"]) == {"fairshare"}
    ctl = run_cell(Catalog(), CELL, 3717000213, 1.5, False, overrides=SMALL,
                   wrap_matchmaker=lambda _mm: ControlMatchmaker())
    assert not ctl["correct"], ctl["checks"]


class _WithTestDeployments(Catalog):
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


def test_completions_logged_late_charge_no_usage_seed_3717000213():
    """The test deployment at full size on seed 3717000213, from the pass
    before t = 27,480: there the engine logs completions after later
    events, and an accountant that charged them until then served
    ligo's user01 before cms's user04 (claims_differing 2)."""
    out = run_cell(_WithTestDeployments(), "fairshare.communities",
                   3717000213, 1.0, False, overrides={"warmup_s": 27420.0})
    assert out["correct"], out["checks"]
    t0, t1 = out["window"]["sim_s"]
    assert t0 < 27480.0 < t1
    assert out["window"]["sampled"]["passes"] == \
        out["window"]["sampled"]["passes_seen"]


def _claims(matchmaker: str, seed: int) -> list:
    from repro.service.pool import PoolClient, PoolService

    config = _config()
    catalog = Catalog()
    traffic = dict(catalog.traffic(catalog.cell(CELL)["traffic"]), **SMALL)
    records = catalog.generator(traffic["shape"]).generate(traffic, seed)
    ini = config["ini"].replace("matchmaker=jax", f"matchmaker={matchmaker}")
    svc = PoolService(ini, seed=seed, speed=None, telemetry=False,
                      **config["service"])
    out = []
    for q in svc.sim.queues:
        q.add_claim_hook(lambda job, now, _q=q.name: out.append(
            (_q, job.jid, job.claimed_by, now)))
    client = PoolClient(svc)
    for schedd, recs in route(records).items():
        client.submit(recs, schedd=schedd, at_trace_times=True, at=0.0)
    svc.sim.run(SMALL["horizon_s"])
    return out


def test_device_and_numpy_pools_claim_alike_over_12_hours():
    jax_claims = _claims("jax", 5)
    assert len(jax_claims) > 5000
    assert jax_claims == _claims("numpy", 5)


# -- readers ------------------------------------------------------------------

def _record(**kw):
    rec = {"kind": "fairshare", "w0": 0.0, "slices": 20, "device_calls": 1,
           "entries": 18, "cohorts": 9, "workers": 620, "resources": 6,
           "nonzero_takes": 11}
    rec.update(kw)
    return rec


def _win(cycles, trace=None):
    return SimpleNamespace(cycles=cycles, trace=trace,
                           device_kind="TPU v5 lite")


def _reader(name):
    return Catalog().reader(name)


def test_slices_and_calls_per_pass_read_the_records():
    recs = [_record(slices=18, device_calls=1),
            _record(slices=22, device_calls=1),
            {"kind": "plain", "w0": 0.0}]
    assert _reader("fairshare_slices_per_pass").read(_win(recs)) == 20.0
    assert _reader("fairshare_calls_per_pass").read(_win(recs)) == 1.0
    parent = [{"kind": "fairshare", "w0": 0.0}, {"kind": "legacy", "w0": 1.}]
    for name in ("fairshare_slices_per_pass", "fairshare_calls_per_pass"):
        assert _reader(name).read(_win(parent)) is None
        assert _reader(name).read(_win([])) is None


def test_roofline_reads_traced_passes_over_device_time_outside_entries():
    trace = {"busy_s": 0.30, "window_s": 15.0,
             "entry_device_s": {"bench.preview": 0.10}}
    recs = [_record(w0=100.0), _record(w0=110.0),
            _record(w0=130.0)]          # after the trace: not counted
    moved = 2 * roofline_fairshare.pass_bytes(recs[0])
    want = 100.0 * moved / 819e9 / 0.20
    got = _reader("fairshare_roofline_pct").read(_win(recs, trace))
    assert got == pytest.approx(want)
    assert 0.0 < got < 100.0
    read = _reader("fairshare_roofline_pct").read
    assert read(_win(recs)) is None                           # untraced
    assert read(_win([{"kind": "fairshare", "w0": 0.0}], trace)) is None
    assert read(_win(recs, dict(trace, busy_s=0.10))) is None  # no time


def test_pass_bytes_follow_the_water_fill_convention():
    from bench import roofline
    import numpy as np

    takes = np.zeros((5, 620), dtype=np.int64)
    takes[1, 3] = takes[1, 4] = takes[3, 9] = 1
    compat = np.ones((5, 620), dtype=bool)
    rec = _record(cohorts=2, nonzero_takes=3, entries=4)
    assert roofline_fairshare.pass_bytes(rec) == (
        roofline.match_bytes(compat, 6, takes) + 4 * 8)
