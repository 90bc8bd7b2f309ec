"""The copied generator: one seed, one trace, byte for byte."""
import json

import pytest

from bench.cells import Catalog

CATALOG = Catalog()
GEN = CATALOG.generator("campaign")
SMALL = dict(CATALOG.traffic("blind-mix"), horizon_s=43200.0)


def _bytes(records):
    return json.dumps(records, sort_keys=True).encode()


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345, 2**33 + 5])
def test_same_seed_gives_byte_identical_trace(seed):
    assert _bytes(GEN.generate(SMALL, seed)) == _bytes(GEN.generate(SMALL, seed))


def test_seeds_change_the_draws_not_the_count():
    a, b = GEN.generate(SMALL, 1), GEN.generate(SMALL, 2)
    assert len(a) == len(b) == round(0.2 * 43200)
    assert _bytes(a) != _bytes(b)


def test_every_seed_brings_the_same_work():
    def work(records):
        return sorted((r["arrival_s"], r["runtime_s"], r["cpus"],
                       r["memory_gb"]) for r in records)

    a, b = GEN.generate(SMALL, 1), GEN.generate(SMALL, 2**31 + 7)
    assert sorted(r["arrival_s"] for r in a) == \
        sorted(r["arrival_s"] for r in b)
    assert sorted(r["runtime_s"] for r in a) == \
        sorted(r["runtime_s"] for r in b)
    assert work(a) != work(b)


def test_a_mix_without_a_structure_seed_is_refused():
    mix = dict(SMALL)
    del mix["structure_seed"]
    with pytest.raises(KeyError):
        GEN.generate(mix, 1)


def test_records_are_sorted_and_inside_the_horizon():
    recs = GEN.generate(SMALL, 5)
    t = [r["arrival_s"] for r in recs]
    assert t == sorted(t) and 0.0 <= t[0] and t[-1] < SMALL["horizon_s"]
    assert {r["group"] for r in recs} == {k["name"] for k in SMALL["kinds"]}


def test_backlog_mix_arrives_at_once_over_many_users():
    mix = dict(CATALOG.traffic("saturated"), n_jobs=20000)
    recs = GEN.generate(mix, 9)
    assert len(recs) == 20000
    assert {r["arrival_s"] for r in recs} == {0.0}
    assert len({r["user"] for r in recs}) > 500


def test_blind_mix_reads_no_offered_quantity_and_osg_mix_does():
    blind = {k["name"]: k["requirements"]
             for k in CATALOG.traffic("blind-mix")["kinds"]}
    osg = {k["name"]: k["requirements"]
           for k in CATALOG.traffic("osg-mix")["kinds"]}
    assert blind["cpu-highmem"] == "" and osg["cpu-highmem"] == "memory >= 32"
    assert {k: v for k, v in blind.items() if k != "cpu-highmem"} == \
        {k: v for k, v in osg.items() if k != "cpu-highmem"}
