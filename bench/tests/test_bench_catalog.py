"""The benchmark finds every cell's parts, and a new configuration, mix
or metric, by name alone."""
import json
import shutil
import textwrap
from pathlib import Path

import pytest

from bench.cells import BENCH_DIR, ROOT, Catalog

CATALOG = Catalog()
CELLS = [w["name"] for w in CATALOG.spec["workloads"]]
METRICS = [m["name"] for k in ("end_to_end", "per_layer")
           for m in CATALOG.spec[k]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_finds_its_config_traffic_and_generator(cell):
    w = CATALOG.cell(cell)
    config = CATALOG.config(w["config"])
    assert "ini" in config and "service" in config and "guarantees" in config
    traffic = CATALOG.traffic(w["traffic"])
    gen = CATALOG.generator(traffic["shape"])
    assert callable(gen.generate)
    assert CATALOG.metrics_of(cell, traced=False)
    assert CATALOG.metrics_of(cell, traced=True)


@pytest.mark.parametrize("metric", METRICS)
def test_metric_reader_found_by_name(metric):
    assert callable(CATALOG.reader(metric).read)


def test_config_file_keeps_reduced_and_source_in_step_with_benchmark():
    for c in CATALOG.spec["configs"]:
        data = json.loads((ROOT / c["file"]).read_text())
        assert data["name"] == c["name"]
        assert data["source"] == c["source"]
        assert data["reduced"] == c["reduced"]
        assert c["file"].startswith(CATALOG.spec["paths"][0] + "/")


def test_every_cell_reports_setup_another_end_to_end_and_a_layer_metric():
    for cell in CELLS:
        e2e = {m["name"] for m in CATALOG.metrics_of(cell, traced=False)}
        assert "setup_s" in e2e and len(e2e) >= 2
        for m in CATALOG.metrics_of(cell, traced=True):
            assert m["moves"] in e2e, (cell, m["name"])


def test_new_config_mix_and_metric_are_picked_up_from_files(tmp_path):
    bench = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics"):
        (bench / sub).mkdir(parents=True)
    shutil.copy(BENCH_DIR / "traffic" / "campaign.py", bench / "traffic")
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "dummy-pool", "source": "https://example.org/dummy",
        "file": "bench/configs/dummy-pool.json", "reduced": [],
        "why": "a dummy"})
    spec["workloads"].append({
        "name": "dummy.cell", "config": "dummy-pool", "traffic": "dummy-mix",
        "chips": 1, "why": "a dummy"})
    spec["per_layer"].append({
        "name": "dummy_count", "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "dummy", "moves": "claims_per_s",
        "workloads": ["dummy.cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    (bench / "configs" / "dummy-pool.json").write_text(json.dumps(
        {"name": "dummy-pool", "ini": "[provision]\n", "service": {},
         "guarantees": []}))
    mix = CATALOG.traffic("blind-mix")
    mix.update(rate_per_s=0.01, horizon_s=3600.0)
    (bench / "traffic" / "dummy-mix.json").write_text(json.dumps(mix))
    (bench / "metrics" / "dummy_count.py").write_text(textwrap.dedent("""
        def read(win):
            return 7.0
    """))
    cat = Catalog(root=tmp_path, bench_dir=bench)
    w = cat.cell("dummy.cell")
    assert cat.config(w["config"])["name"] == "dummy-pool"
    records = cat.generator(cat.traffic(w["traffic"])["shape"]).generate(
        cat.traffic(w["traffic"]), 3)
    assert len(records) == 36
    names = [m["name"] for m in cat.metrics_of("dummy.cell", traced=True)]
    assert "dummy_count" in names
    assert cat.reader("dummy_count").read(None) == 7.0
    # a cell's per-layer list never shows another cell's metric
    assert "dummy_count" not in [
        m["name"] for m in cat.metrics_of(CELLS[0], traced=True)]


NAME = r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$"
UNIT = r"^[A-Za-z0-9_/%.\-]{1,16}$"


def test_benchmark_json_keeps_to_its_shape():
    import re

    spec = CATALOG.spec
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    names = [c["name"] for c in spec["configs"]]
    cells = [w["name"] for w in spec["workloads"]]
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert re.match(NAME, m["name"]) and re.match(UNIT, m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= set(cells)
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            assert cell in e2e[m["moves"]].get("workloads", cells)
    for w in spec["workloads"]:
        assert re.match(NAME, w["name"]) and w["config"] in names
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert len(set(cells)) == len(cells) and len(set(names)) == len(names)
    assert all(c in {w["config"] for w in spec["workloads"]} for c in names)
