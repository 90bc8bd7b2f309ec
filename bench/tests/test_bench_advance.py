"""The reader of the event engine's advancement counters, on hand-made
windows: workers visited over advance calls between the window's first
and last cycle records, and nothing from a program without them."""
from types import SimpleNamespace

import pytest

from bench.cells import Catalog

CATALOG = Catalog()
NAME = "advance_touched_per_call"
CELLS = ["federation.blind-mix", "federation.osg-mix", "backlog.saturated"]


def rec(kind, run=None, touched=None, calls=None):
    out = {"kind": kind, "build_s": 0.0, "match_s": 0.0, "apply_s": 0.0}
    if run is not None:
        out["engine_s"] = {"run": run, "advance": 0.1 * run}
    if calls is not None:
        out.update(advance_touched=touched, advance_calls=calls)
    return out


def read(win):
    return CATALOG.reader(NAME).read(win)


def test_touched_over_calls_between_the_window_ends():
    win = SimpleNamespace(cycles=[
        rec("plain"),                                # an old program's
        rec("plain", 10.0, touched=5_000, calls=1_000),
        rec("legacy", 12.0, touched=5_400, calls=1_100),
        rec("fused", 20.0, touched=6_800, calls=1_500),
    ])
    assert read(win) == pytest.approx((6_800 - 5_000) / 500)


@pytest.mark.parametrize("cycles", [
    [],
    [rec("plain", 10.0), rec("plain", 12.0)],        # spans, no counters
    [rec("plain"), rec("legacy"), rec("plain")],     # neither
    [rec("plain", 10.0, 5, 100)],                    # one record
    [rec("plain", 10.0, 5, 100), rec("plain", 12.0, 5, 100)],  # no call
])
def test_a_window_without_the_counters_reports_nothing(cycles):
    assert read(SimpleNamespace(cycles=cycles)) is None


@pytest.mark.parametrize("cell", CELLS)
def test_reported_in_every_cell_of_the_layer(cell):
    traced = [m["name"] for m in CATALOG.metrics_of(cell, traced=True)]
    assert NAME in traced
    assert NAME not in [m["name"] for m in
                        CATALOG.metrics_of(cell, traced=False)]
    (m,) = [m for m in CATALOG.spec["per_layer"] if m["name"] == NAME]
    assert m["layer"] == "event engine: continuous integration"
    assert m["moves"] == "claims_per_s"
