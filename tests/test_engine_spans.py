"""Program spans: the engine's self-time parts partition the service
driver's running wall, events are counted by a bounded kind, each
matchmaker call counts the bytes of the padded arrays it moves, the
device programs compile under stable names, and the spans land in a JAX
profiler trace as `repro.*` TraceMes — and nowhere with telemetry off."""
import glob
import time

import numpy as np
import pytest

from repro.core import (
    NodeTemplate, ProvisionerConfig, Simulation, gpu_job, onprem_nodes,
)
from repro.core.matchmaker import MatchProblem
from repro.core.matchmaker.base import RESOURCE_KEYS, CycleDelta
from repro.core.matchmaker.jax_backend import (
    JaxMatchmaker, _build_cycles_scan, _build_preview_scan, _build_scan,
)
from repro.service import PoolClient, PoolService
from repro.workload.trace import TraceRecord

CAP = {"cpu": 16, "gpu": 4, "memory": 64, "disk": 256}

# the benchmark federation's node shape at a third of its node count:
# a couple of hundred one-job pods, so that an event carries the work
# it does in a served pool (the driver loop's own bookkeeping between
# spans is a few microseconds an event whatever the pool's size)
SERVICE_INI = """\
[provision]
submit_interval_s=30
idle_timeout_s=240
startup_delay_s=15

[backend:onprem]
kind=static
nodes=4
capacity_dict=cpu:64,gpu:4,memory:512,disk:1024

[backend:cloud]
kind=autoscale
capacity_dict=cpu:64,gpu:4,memory:512,disk:1024
max_nodes=8
node_hourly_cost=1.0
provision_delay_s=30
scale_down_delay_s=120
"""


def build(telemetry=True, **kw):
    cfg = ProvisionerConfig(submit_interval_s=30, idle_timeout_s=120,
                            startup_delay_s=30)
    sim = Simulation(cfg, nodes=onprem_nodes(2, gpus=4, cpus=16),
                     node_template=NodeTemplate(capacity=dict(CAP)),
                     max_nodes=8, tick_s=5.0, negotiate_interval_s=15.0,
                     seed=3, telemetry=telemetry, **kw)
    for i in range(30):
        sim.submit_jobs(10.0 * i, [gpu_job(200.0 + 15.0 * (i % 5),
                                           gpus=1 + (i % 2))])
    return sim


def served(seconds: float):
    """A sprinting pool service run for `seconds` of wall time."""
    svc = PoolService(SERVICE_INI, tick_s=5.0, negotiate_interval_s=15.0,
                      metrics_interval_s=60.0, speed=None)
    recs = [TraceRecord(arrival_s=0.5 * i, runtime_s=60.0 + 7 * (i % 5),
                        cpus=1 + i % 3) for i in range(3000)]
    PoolClient(svc).submit(recs, at_trace_times=True, at=0.0)
    svc.start()
    time.sleep(seconds)
    svc.stop()
    return svc


# -- the engine's parts -------------------------------------------------------

def test_engine_parts_cover_the_drivers_running_wall():
    svc = served(1.0)
    prof = svc.sim.telemetry.profiler
    parts = prof.engine_seconds()
    run = parts.pop("run")
    assert 0.9 < run < 1.5
    assert {"advance", "pass", "reconcile"} <= set(parts)
    assert any(p.startswith("event:") for p in parts)
    assert all(v >= 0.0 for v in parts.values())
    # passes, reconciles and the engine's parts cover the running wall
    assert 0.95 * run <= sum(parts.values()) <= run
    # the counter family holds the same numbers once every span closed
    reg = svc.sim.telemetry.registry
    fam = reg.family("repro_engine_seconds_total").children
    assert fam[("run",)].value == pytest.approx(run)
    assert fam[("pass",)].value == pytest.approx(parts["pass"])


def test_cycle_records_carry_engine_snapshots_and_pass_ids():
    svc = served(0.5)
    prof = svc.sim.telemetry.profiler
    recs = list(prof.cycles)
    assert len(recs) >= 2
    ids = [r["pass_id"] for r in recs]
    assert all(i is not None for i in ids) and ids == sorted(ids)
    first, last = recs[0]["engine_s"], recs[-1]["engine_s"]
    assert last["run"] > first["run"]
    # cumulative: no part runs backwards between records
    assert all(last[k] >= v for k, v in first.items())
    # every pass, the no-op ones too, is one repro_pass_seconds sample
    passes = svc.sim.telemetry.registry.family(
        "repro_pass_seconds").children[()]
    assert passes.count >= len(set(ids))
    assert prof.pass_id is None          # no pass is open


def test_events_are_counted_by_bounded_kind():
    sim = build()
    sim.run_until_drained(1e6)
    fam = sim.telemetry.registry.family("repro_events_total").children
    assert sum(c.value for c in fam.values()) == sim.loop.fired
    kinds = {k[0] for k in fam}
    assert "submit" in kinds and "negotiate" in kinds
    assert not any(" " in k for k in kinds)       # no "submit x1" series
    parts = sim.telemetry.profiler.engine_seconds()
    assert {"event:" + k for k in kinds} <= set(parts)
    assert "run" not in parts           # no service driver ran


# -- bytes between host and device --------------------------------------------

R = len(RESOURCE_KEYS)


def problem(C, W, seed=0):
    rng = np.random.default_rng(seed)
    req = rng.integers(1, 4, (C, R)).astype(np.float64)
    free = rng.integers(0, 16, (W, R)).astype(np.float64)
    return MatchProblem(
        keys=[(0, c) for c in range(C)], requests=req,
        demand=rng.integers(0, 5, C), order=rng.permutation(C),
        free=free, capacity=free.copy(),
        compat=rng.random((C, W)) < 0.7)


#: the matchmaker's float dtype and its width in bytes; a float64 run
#: has 64-bit JAX types on, so the preview's summed takes are 8 bytes too
DTYPES = pytest.mark.parametrize("dtype,it", [("float64", 8),
                                              ("float32", 4)])


@DTYPES
def test_match_bytes_equal_the_padded_shapes(dtype, it):
    mm = JaxMatchmaker(dtype=dtype, chunk=64)
    mm.match(problem(70, 130))
    Cp, Wp, nch = 128, 256, 2
    lc = mm.last_call
    # free, budget, request/safe/big, demand, compat (uint8), chunk min
    assert lc["h2d_bytes"] == it * (R * Wp + 1 + 3 * Cp * R + Cp
                                     + nch * R) + Cp * Wp
    # takes (int32), free after, which chunks ran (bool)
    assert lc["d2h_bytes"] == 4 * Cp * Wp + it * R * Wp + nch
    assert lc["roundtrip_s"] > 0.0


@DTYPES
def test_match_cycles_bytes_equal_the_padded_shapes(dtype, it):
    mm = JaxMatchmaker(dtype=dtype, chunk=64)
    p = problem(70, 130)
    K = 3
    deltas = [CycleDelta(arrivals=np.ones(70, dtype=np.int64))
              for _ in range(K)]
    mm.match_cycles(p, deltas)
    Cp, Wp, nch = 128, 256, 2
    lc = mm.last_call
    # free, demand, arrivals, free deltas, budgets, request/safe/big,
    # compat
    assert lc["h2d_bytes"] == it * (R * Wp + Cp + K * Cp + K * R * Wp + K
                                     + 3 * Cp * R) + Cp * Wp
    # takes, chunks ran, free after each cycle
    assert lc["d2h_bytes"] == (4 * K * Cp * Wp + K * nch
                               + it * K * R * Wp)


@DTYPES
def test_preview_bytes_ship_cohort_constants_once_per_session(dtype, it):
    mm = JaxMatchmaker(dtype=dtype, chunk=64)
    p = problem(70, 130)
    N = 2
    Cp, Wp = 128, 512                             # the 512-lane floor
    mm.preview_many(p, [p.free] * N, session="s")
    lc = mm.last_call
    frees_and_demand = it * (N * R * Wp + N * Cp)
    consts = it * 3 * Cp * R + Cp * Wp
    assert lc["h2d_bytes"] == frees_and_demand + consts
    assert lc["d2h_bytes"] == it * N * Cp           # absorbed per cohort
    mm.preview_many(p, [p.free] * N, session="s")
    assert mm.last_call["h2d_bytes"] == frees_and_demand


def test_transfer_counter_sums_the_calls_of_a_pool():
    sim = build(matchmaker="jax")
    sim.run_until_drained(1e6)
    prof = sim.telemetry.profiler
    fam = sim.telemetry.registry.family(
        "repro_device_transfer_bytes_total").children
    device = [r for r in prof.cycles if r["kind"] != "legacy"]
    assert device and all(r["h2d_bytes"] > 0 < r["d2h_bytes"]
                          for r in device)
    assert fam[("cycle", "h2d")].value == sum(r["h2d_bytes"]
                                              for r in prof.cycles)
    assert fam[("cycle", "d2h")].value == sum(r["d2h_bytes"]
                                              for r in prof.cycles)
    assert fam[("preview", "d2h")].value > 0


# -- stable program names -----------------------------------------------------

def test_device_programs_compile_under_stable_names():
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    nch, chunk, Wp, K, N = 1, 64, 128, 2, 2

    def s(*shape, dtype=f32):
        return jax.ShapeDtypeStruct(shape, dtype)

    per_chunk = [s(nch, chunk, R)] * 3
    match = _build_scan(chunk, 4).lower(
        s(R, Wp), s(), *per_chunk, s(nch, chunk),
        s(nch, chunk, Wp, dtype=jnp.uint8), s(nch, R))
    cycles = _build_cycles_scan(chunk, 4).lower(
        s(R, Wp), s(nch, chunk), s(K, nch, chunk), s(K, R, Wp), s(K),
        *per_chunk, s(nch, chunk, Wp, dtype=jnp.uint8))
    preview = _build_preview_scan(chunk, 1).lower(
        s(N, R, Wp), s(N, nch, chunk), *per_chunk,
        s(nch, chunk, Wp, dtype=jnp.uint8))
    for lowered, name in ((match, "jit_waterfill_match"),
                          (cycles, "jit_waterfill_cycles"),
                          (preview, "jit_waterfill_preview")):
        assert f"module @{name}" in lowered.as_text()


# -- the spans in a profiler trace --------------------------------------------

def traced_events(tmp_path, sim_fn):
    """Run `sim_fn()` inside a JAX profiler session; the `repro.*` host
    events of the trace as (name, start_ns, end_ns, stats)."""
    import jax
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        sim_fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("repro."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return out


def test_trace_holds_passes_with_phases_sharing_a_pass_id(tmp_path):
    sim = build(matchmaker="jax")
    evs = traced_events(tmp_path, lambda: sim.run_until_drained(1e6))
    names = {n for n, *_ in evs}
    assert {"repro.event", "repro.advance", "repro.pass",
            "repro.reconcile", "repro.reconcile.preview",
            "repro.device.roundtrip"} <= names
    passes = {st["pass_id"]: (a, b, st) for n, a, b, st in evs
              if n == "repro.pass"}
    phases = [(n, a, b, st) for n, a, b, st in evs
              if n in ("repro.pass.build", "repro.pass.match",
                       "repro.pass.apply")]
    assert phases
    for n, a, b, st in phases:
        pa, pb, _ = passes[st["pass_id"]]     # the phase's own pass
        assert pa <= a and b <= pb
    # a pass that matched on the device shows build, match and apply
    # under one id, and the round trip inside its match phase
    full = {}
    for n, a, b, st in phases:
        full.setdefault(st["pass_id"], {})[n] = (a, b)
    pid, ph = next((k, v) for k, v in full.items() if len(v) == 3)
    ma, mb = ph["repro.pass.match"]
    assert any(ma <= a and b <= mb for n, a, b, _st in evs
               if n == "repro.device.roundtrip")
    # each pass names what caused it, and says its kind
    assert passes[pid][2]["cause"] == "event:negotiate"
    assert passes[pid][2]["kind"] == "plain"
    kinds = {st["kind"] for n, _a, _b, st in evs if n == "repro.event"}
    assert {"submit", "negotiate", "reconcile"} <= kinds


def test_no_tracemes_with_telemetry_off(tmp_path):
    sim = build(telemetry=False, matchmaker="jax")
    evs = traced_events(tmp_path, lambda: sim.run_until_drained(1e6))
    assert evs == []
    assert sim.collector.matchmaker.spans is False
    assert sim.loop.profiler is None
