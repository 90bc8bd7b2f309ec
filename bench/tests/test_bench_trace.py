"""The trace reduction, on hand-made events and on a trace recorded on a
TPU v5e (a 0.3 s window of federation.blind-mix)."""
from pathlib import Path

import pytest

from bench import trace_reduce

DATA = Path(__file__).resolve().parent / "data"


def _events():
    ms = 1_000_000
    return {
        "annotations": [
            ["bench.window", 0, 100 * ms],
            ["bench.pass", 10 * ms, 30 * ms],
            ["bench.match", 20 * ms, 10 * ms],
            ["bench.reconcile", 50 * ms, 20 * ms],
            ["bench.preview", 55 * ms, 10 * ms],
        ],
        "modules": {"0": [["jit_fn", 21 * ms, 8 * ms],
                          ["jit_one", 56 * ms, 4 * ms]]},
        "ops": {"0": [["while", 21 * ms, 5 * ms], ["fusion", 22 * ms, 2 * ms],
                      ["copy", 25 * ms, 4 * ms],
                      ["while", 56 * ms, 4 * ms],
                      ["convert", 95 * ms, 10 * ms]]},   # runs past the end
    }


def test_reduce_hand_made_events():
    r = trace_reduce.reduce(_events())
    assert r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx(0.008 + 0.004 + 0.005)
    assert r["entry_device_s"] == pytest.approx(
        {"bench.match": 0.008, "bench.preview": 0.004})
    ops = dict(r["device_ops"])
    assert ops["jit_fn:while"] == pytest.approx(0.003)      # less its body
    assert ops["jit_fn:fusion"] == pytest.approx(0.002)
    assert ops["jit_one:while"] == pytest.approx(0.004)
    gaps = {k.split(" (")[0]: v for k, v in r["idle_gaps"]}
    assert gaps["bench.match"] == pytest.approx(0.001 + 0.001)
    assert gaps["bench.pass"] == pytest.approx(0.010 + 0.010)
    assert gaps["bench.preview"] == pytest.approx(0.001 + 0.005)
    assert gaps["bench.reconcile"] == pytest.approx(0.005 + 0.005)
    assert gaps["host: other"] == pytest.approx(0.010 + 0.010 + 0.025)
    assert sum(v for _k, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"])


def test_reduce_needs_the_window():
    ev = _events()
    ev["annotations"] = ev["annotations"][1:]
    with pytest.raises(ValueError):
        trace_reduce.reduce(ev)


def test_short_names():
    assert trace_reduce._short("%while.3 = f32[8] while(f32[8] %x)") == "while"
    assert trace_reduce._short("jit_fn(123456)") == "jit_fn"
    assert trace_reduce._short("%reduce-window.29 = f32[8,128]") == \
        "reduce-window"


def test_reduce_recorded_chip_trace(tmp_path):
    import gzip

    path = tmp_path / "window.xplane.pb"
    path.write_bytes(gzip.decompress(
        (DATA / "blind-mix-0.3s.xplane.pb.gz").read_bytes()))
    events = trace_reduce.extract(str(path), n_devices=1)
    assert set(events["ops"]) == {"0"} and events["modules"]["0"]
    r = trace_reduce.reduce(events)
    assert r["window_s"] == pytest.approx(0.322465579)
    assert r["busy_s"] == pytest.approx(0.006020854)
    assert set(r["entry_device_s"]) == {"bench.match", "bench.preview"}
    assert sum(r["entry_device_s"].values()) <= r["busy_s"] * 1.01
    names = [n for n, _s in r["device_ops"]]
    assert any(n.startswith("jit_fn:") for n in names)      # match
    assert any(n.startswith("jit_one:") for n in names)     # preview
    assert sum(s for _n, s in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
