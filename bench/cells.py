"""Finds a cell's parts by the names in BENCHMARK.json.

A cell names a configuration (``configs/<name>.json``, found through
the ``file`` of its entry) and a traffic mix (``traffic/<name>.json``,
whose ``shape`` names the generator ``traffic/<shape>.py``).  A
per-layer metric ``<name>`` is read by ``metrics/<name>.py``, which
defines ``read(ctx) -> float | None``.  Adding a configuration, a mix
or a metric is adding files and entries; nothing here changes.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Catalog:
    """The benchmark's entries, and the files they name, under `root`."""

    def __init__(self, root: Path = ROOT, bench_dir: Path = BENCH_DIR):
        self.root = Path(root)
        self.dir = Path(bench_dir)
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in self.spec['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                return json.loads((self.root / c["file"]).read_text())
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return json.loads((self.dir / "traffic" / f"{name}.json").read_text())

    def generator(self, shape: str):
        return load_module(self.dir / "traffic" / f"{shape}.py",
                           f"bench_traffic_{shape}")

    def metrics_of(self, cell: str, traced: bool) -> list[dict]:
        """The metrics a run of `cell` reports: its end-to-end metrics
        untraced, its per-layer metrics traced."""
        key = "per_layer" if traced else "end_to_end"
        out = []
        for m in self.spec[key]:
            if "workloads" in m and cell not in m["workloads"]:
                continue
            if traced and "workloads" not in m:
                e2e = {e["name"]: e for e in self.spec["end_to_end"]}
                moved = e2e[m["moves"]]
                if "workloads" in moved and cell not in moved["workloads"]:
                    continue
            out.append(m)
        return out

    def reader(self, metric: str):
        return load_module(self.dir / "metrics" / f"{metric}.py",
                           "bench_metric_" + metric.replace(".", "_"))
