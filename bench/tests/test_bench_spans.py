"""The readers of the program's own spans, on hand-made windows: engine
shares from the first and last cycle records' snapshots, device round
trips and bytes over device-path passes, and nothing at all from a
program whose records lack them."""
from types import SimpleNamespace

import pytest

from bench.cells import Catalog

CATALOG = Catalog()
FED = ["federation.blind-mix", "federation.osg-mix"]
ALL = FED + ["backlog.saturated"]
CELLS = {
    "engine_advance_pct": ALL,
    "engine_events_pct": ALL,
    "engine_unspanned_pct": ALL,
    "match_roundtrip_ms": FED,
    "match_roundtrip_ms.backlog": ["backlog.saturated"],
    "xfer_mb_per_pass": FED,
    "xfer_mb_per_pass.backlog": ["backlog.saturated"],
}


def rec(kind, engine=None, rt=0.0, h2d=0, d2h=0):
    out = {"kind": kind, "build_s": 0.0, "match_s": 0.0, "apply_s": 0.0}
    if engine is not None:
        out.update(engine_s=engine, roundtrip_s=rt, h2d_bytes=h2d,
                   d2h_bytes=d2h, pass_id=1)
    return out


def window():
    """Two served segments with a pause between them (a traced run's
    trace writing): the driver's `run` stops while the parts stop too."""
    first = {"run": 10.0, "advance": 2.0, "event:submit": 1.0,
             "event:backend:cloud": 0.5, "pass": 3.0, "reconcile": 1.0,
             "wait": 0.5, "inject": 0.1}
    mid = {"run": 12.0, "advance": 2.5, "event:submit": 1.2,
           "event:backend:cloud": 0.6, "pass": 3.8, "reconcile": 1.4,
           "wait": 0.6, "inject": 0.1}
    # 20 s of wall pass here with the driver stopped; the run wall
    # resumes where it stopped
    last = {"run": 20.0, "advance": 4.0, "event:submit": 1.8,
            "event:backend:cloud": 1.0, "event:metrics": 0.2, "pass": 6.0,
            "reconcile": 2.6, "wait": 1.0, "inject": 0.2}
    cycles = [
        rec("plain"),                       # an old program's record
        rec("plain", first, rt=0.004, h2d=100_000, d2h=300_000),
        rec("legacy", mid),
        rec("plain", mid, rt=0.006, h2d=110_000, d2h=340_000),
        rec("fused", last, rt=0.008, h2d=120_000, d2h=380_000),
    ]
    return SimpleNamespace(cycles=cycles)


def read(name, win):
    return CATALOG.reader(name).read(win)


def test_engine_shares_come_from_the_window_ends_over_the_run_wall():
    win = window()
    run = 10.0                    # 20 - 10: the pause is not in it
    assert read("engine_advance_pct", win) == pytest.approx(
        100 * 2.0 / run)
    # event:metrics first shows after the window's first record
    assert read("engine_events_pct", win) == pytest.approx(
        100 * (0.8 + 0.5 + 0.2) / run)
    covered = 2.0 + 0.8 + 0.5 + 0.2 + 3.0 + 1.6 + 0.5 + 0.1
    assert read("engine_unspanned_pct", win) == pytest.approx(
        100 * (run - covered) / run)


def test_device_readers_average_device_path_passes_only():
    win = window()
    for name in ("match_roundtrip_ms", "match_roundtrip_ms.backlog"):
        assert read(name, win) == pytest.approx(6.0)
    for name in ("xfer_mb_per_pass", "xfer_mb_per_pass.backlog"):
        assert read(name, win) == pytest.approx(
            (400_000 + 450_000 + 500_000) / 3 / 1e6)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_a_program_without_spans_reports_nothing(name):
    old = SimpleNamespace(cycles=[rec("plain"), rec("legacy"),
                                  rec("plain")])
    assert read(name, old) is None
    assert read(name, SimpleNamespace(cycles=[])) is None


def test_a_single_record_gives_no_engine_share():
    win = window()
    win.cycles = win.cycles[:2]
    assert read("engine_advance_pct", win) is None
    assert read("xfer_mb_per_pass", win) == pytest.approx(0.4)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_each_span_metric_is_listed_in_exactly_its_cells(name):
    for cell in ALL:
        names = [m["name"] for m in CATALOG.metrics_of(cell, traced=True)]
        assert (name in names) == (cell in CELLS[name]), (name, cell)
        untraced = CATALOG.metrics_of(cell, traced=False)
        assert name not in [m["name"] for m in untraced]
