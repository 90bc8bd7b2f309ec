"""The `[fairshare]` INI section (core/config.py) and the usage ledger's
time order (core/fairshare.py).

  * the section parses, `dump_ini` writes it back, and a snapshot/resume
    of the pool service keeps its quotas and factors;
  * a non-positive term is refused;
  * `Simulation(fairshare=True)` builds its accountant and slice quantum
    from the section, a `ScheddSpec` keeping its own quota, and without
    the section builds today's `Accountant()`;
  * a rate change logged after a later one is back-dated: the ledger
    fed the fair-share test deployment's events in the order the engine
    logged them (seed 3717000213, up to t = 27,480) reads what it reads
    fed them in time order.
"""
import gzip
import json
from pathlib import Path

import pytest

from repro.core import (
    Accountant, FairshareConfig, ScheddSpec, Simulation, UsageLedger,
    dump_ini, load_ini,
)
from repro.service import PoolService

FAIRSHARE_INI = """\
[provision]
matchmaker=numpy

[fairshare]
half_life_s=43200
base_priority=0.5
default_factor=1000
quantum=2
quotas_dict=cms:0.4,atlas:0.3,ligo:0.2,icecube:0.1
factors_dict=user00:500,user01:2000
"""

WITNESS = Path(__file__).resolve().parent / "data" / \
    "usage_witness_3717000213.json.gz"


def test_section_parses():
    fs = load_ini(FAIRSHARE_INI).fairshare
    assert fs == FairshareConfig(
        half_life_s=43200.0, base_priority=0.5, default_factor=1000.0,
        quantum=2,
        quotas={"cms": 0.4, "atlas": 0.3, "ligo": 0.2, "icecube": 0.1},
        factors={"user00": 500.0, "user01": 2000.0})
    assert isinstance(fs.quantum, int)
    assert load_ini("[provision]\nmatchmaker=jax\n").fairshare is None


def test_dump_ini_round_trips_the_section():
    cfg = load_ini(FAIRSHARE_INI)
    again = load_ini(dump_ini(cfg))
    assert again.fairshare == cfg.fairshare
    assert dump_ini(again) == dump_ini(cfg)
    assert "[fairshare]" not in dump_ini(load_ini("[provision]\n"))


@pytest.mark.parametrize("line", [
    "half_life_s=0", "base_priority=-0.5", "default_factor=0",
    "quantum=0", "quantum=1.5", "quotas_dict=cms:0",
    "factors_dict=user00:-1",
])
def test_non_positive_term_is_refused(line):
    key = line.split("=", 1)[0]
    ini = "\n".join(l if not l.startswith(key + "=") else line
                    for l in FAIRSHARE_INI.splitlines())
    with pytest.raises(ValueError, match=r"\[fairshare\]"):
        load_ini(ini)


def test_simulation_builds_its_accountant_from_the_section():
    sim = Simulation.from_config(
        load_ini(FAIRSHARE_INI),
        schedds=["cms", "atlas", ScheddSpec(name="ligo", quota=0.7),
                 "osg"],
        fairshare=True)
    acct = sim.accountant
    assert acct.users.half_life_s == acct.groups.half_life_s == 43200.0
    assert acct.base_priority == 0.5 and acct.default_factor == 1000.0
    assert acct.priority_factor("user00") == 500.0
    assert acct.priority_factor("user01") == 2000.0
    assert acct.priority_factor("user07") == 1000.0
    # a ScheddSpec keeps its quota; a name takes the INI's, else 1
    assert {s: acct.quota(s) for s in ("cms", "atlas", "ligo", "osg")} == {
        "cms": 0.4, "atlas": 0.3, "ligo": 0.7, "osg": 1.0}
    assert [s.quota for s in sim.schedd_specs] == [0.4, 0.3, 0.7, 1.0]
    assert sim.negotiate_quantum == 2
    assert Simulation.from_config(load_ini(FAIRSHARE_INI), schedds=2,
                                  fairshare=True,
                                  negotiate_quantum=5).negotiate_quantum == 5


def test_without_the_section_the_accountant_is_todays():
    sim = Simulation.from_config(load_ini("[provision]\nmatchmaker=numpy\n"),
                                 schedds=["cms", "atlas"], fairshare=True)
    fresh = Accountant()
    acct = sim.accountant
    for attr in ("base_priority", "default_factor", "factors"):
        assert getattr(acct, attr) == getattr(fresh, attr)
    assert acct.users.half_life_s == fresh.users.half_life_s
    assert acct.quotas == {"cms": 1.0, "atlas": 1.0}
    assert sim.negotiate_quantum == 1
    # the section alone turns nothing on
    plain = Simulation.from_config(load_ini(FAIRSHARE_INI))
    assert plain.accountant is None and not plain.flocking


def test_snapshot_resume_keeps_quotas_and_factors():
    svc = PoolService(FAIRSHARE_INI, schedds=["cms", "atlas", "ligo"],
                      fairshare=True, speed=None, telemetry=False)
    snap = json.loads(json.dumps(svc.snapshot()))
    again = PoolService.resume(snap)
    for s in (svc, again):
        acct = s.sim.accountant
        assert acct.quotas == {"cms": 0.4, "atlas": 0.3, "ligo": 0.2}
        assert acct.factors == {"user00": 500.0, "user01": 2000.0}
        assert acct.default_factor == 1000.0
        assert acct.users.half_life_s == 43200.0
        assert s.sim.negotiate_quantum == 2


def test_a_late_stop_is_back_dated():
    """A 2-core job claimed at 0 stops at 100, logged after another key
    change at 300: it is charged for [0, 100] only."""
    in_order, late = UsageLedger(600.0), UsageLedger(600.0)
    for led, events in ((in_order, [(2.0, 0.0), (-2.0, 100.0), (1.0, 300.0)]),
                        (late, [(2.0, 0.0), (1.0, 300.0), (-2.0, 100.0)])):
        for delta, t in events:
            led.add_rate("u", delta, t)
    for t in (300.0, 450.0, 1000.0):
        assert late.usage("u", t) == pytest.approx(in_order.usage("u", t),
                                                   rel=1e-13)
    # a one-shot charge dated before the last settle has decayed since
    a, b = UsageLedger(600.0), UsageLedger(600.0)
    a.charge("u", 60.0, 0.0)
    b.usage("u", 600.0)
    b.charge("u", 60.0, 0.0)
    assert b.usage("u", 600.0) == pytest.approx(a.usage("u", 600.0))


def test_ledger_fed_in_the_order_logged_reads_the_time_order():
    """The witness: some of the deployment's claims and completions come
    earlier than the same schedd's previous one; charged until the later
    event, cms and ligo read 129.2125 and 126.2122 group cores."""
    with gzip.open(WITNESS, "rt") as f:
        witness = json.load(f)
    events = [tuple(e) for e in witness["events"]]
    assert any(b[2] < a[2] for a, b in zip(events, events[1:])
               if a[0] == b[0])
    for order in (events, sorted(events, key=lambda e: e[2])):
        led = UsageLedger(86400.0)
        for key, delta, t in order:
            led.add_rate(key, delta, t)
        assert led.effective_cores("cms", witness["t"]) == pytest.approx(
            129.19339534973432, rel=1e-12)
        assert led.effective_cores("ligo", witness["t"]) == pytest.approx(
            126.19571722328081, rel=1e-12)
