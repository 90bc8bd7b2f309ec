"""Chip smoke: the device matchmaker on one TPU, through the entry points
a user calls, at the size of a real pool.

    python chip_smoke.py [--seed 0] [--jobs 100000]

Phases, all in this one process (it holds the chip; nothing it starts
touches JAX):

  1. device — the first JAX device must be a TPU; anything else exits
     nonzero before any result is printed.
  2. matchmaker — the seeded 1m-job problem of
     `benchmarks/bench_matchmaking.py` (16,384 cohorts x 1,024 workers)
     through `make_matchmaker("numpy" | "jax" | "pallas")`: each device
     plan must equal the numpy plan (takes and free capacity after), the
     Pallas program must hold the compiled kernel (`tpu_custom_call`),
     and `match_cycles` (8 fused cycles with arrivals and returned
     capacity) and `preview_many` (8 candidates) must equal their
     sequential references.
  3. served — a `PoolService` on the standard 3-backend federation with
     ``matchmaker=jax`` streams a seeded OSG-shaped diurnal day as fast
     as it goes and drains; the same day with ``matchmaker=numpy`` must
     give the same claims and completion statistics, and every job must
     complete.

Each phase prints its timings on a line of its own.  The last line is
one JSON object naming the device; any failure raises before it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from benchmarks.bench_matchmaking import build_problem  # noqa: E402
from repro.compile_cache import use_compile_cache  # noqa: E402
from repro.core.matchmaker import MatchProblem, make_matchmaker  # noqa: E402
from repro.core.matchmaker.base import (  # noqa: E402
    CycleDelta, sequential_match_cycles, sequential_preview_many,
)
from repro.kernels.waterfill.kernel import waterfill_pallas  # noqa: E402
from repro.service.__main__ import STANDARD_INI  # noqa: E402
from repro.service.pool import PoolClient, PoolService  # noqa: E402
from repro.workload.generators import generate_preset  # noqa: E402

TIER = dict(jobs=1_000_000, C=16_384, W=1_024)   # bench_matchmaking "1m"
K_CYCLES = 8
N_CANDIDATES = 8


class CompileClock:
    """Seconds XLA spends compiling, from JAX's own monitoring events."""

    def __init__(self):
        self.s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.s += duration

    def lap(self) -> float:
        s, self.s = self.s, 0.0
        return s


def report(phase: str, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


def timed(clock: CompileClock, fn):
    """(result, wall seconds, compile seconds inside that wall)."""
    clock.lap()
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0, clock.lap()


def require(cond: bool, what: str):
    if not cond:
        raise AssertionError(what)


def plans_equal(a, b) -> bool:
    return (np.array_equal(a.takes, b.takes)
            and np.array_equal(a.free_after, b.free_after))


def exact_multiples_problem(seed: int, n: int = 2_048) -> MatchProblem:
    """Cohort c fits only worker c, whose free cores are an exact
    multiple of its request: the case where a float32 division that is
    not correctly rounded floors one short."""
    rng = np.random.default_rng(seed)
    requests = np.zeros((n, 6))
    requests[:, 0] = rng.integers(1, 4_096, size=n)
    free = np.zeros((n, 6))
    free[:, 0] = requests[:, 0] * rng.integers(1, 4_096, size=n)
    return MatchProblem(
        keys=[(0, c) for c in range(n)], requests=requests,
        demand=np.full(n, 5_000, dtype=np.int64),
        order=np.arange(n, dtype=np.int64), free=free,
        capacity=free.copy(), compat=np.eye(n, dtype=bool))


def device_phase() -> jax.Device:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{dev.platform!r} ({dev.device_kind})")
    report("device", platform=dev.platform, kind=dev.device_kind,
           count=len(jax.devices()))
    return dev


def matchmaker_phase(seed: int, clock: CompileClock):
    p = build_problem(seed=seed, **TIER)
    ref = make_matchmaker("numpy")
    want, ref_s, _ = timed(clock, lambda: ref.match(p))
    report("match", backend="numpy", wall_s=ref_s, claimed=want.claimed)
    require(want.claimed > 0, "the 1m problem must claim something")

    device = {name: make_matchmaker(name) for name in ("jax", "pallas")}
    for name, mm in device.items():
        require(mm.dtype == "float32", f"{name} must run float32 on a TPU")
        got, first_s, compile_s = timed(clock, lambda: mm.match(p))
        again, exec_s, recompile_s = timed(clock, lambda: mm.match(p))
        require(plans_equal(want, got) and plans_equal(want, again),
                f"{name} plan differs from numpy at the 1m tier")
        require(recompile_s == 0.0, f"{name} recompiled a warm bucket")
        report("match", backend=name, dtype=mm.dtype, compile_s=compile_s,
               first_call_s=first_s, execute_s=exec_s, identical=True)

    edge = exact_multiples_problem(seed)
    want_edge = ref.match(edge)
    for name, mm in device.items():
        require(plans_equal(want_edge, mm.match(edge)),
                f"{name} differs from numpy on exact multiples")
    report("exact_multiples", cohorts=edge.n_cohorts,
           claimed=want_edge.claimed, identical=True)

    # the compiled Pallas program at the shapes the backend just ran
    C, W, R = p.compat.shape + (p.requests.shape[1],)
    chunk = device["pallas"].chunk
    nch = C // chunk
    f32 = jax.numpy.float32
    hlo = waterfill_pallas.lower(
        jax.ShapeDtypeStruct((R, W), f32), jax.ShapeDtypeStruct((1,), f32),
        jax.ShapeDtypeStruct((nch, 1, chunk * R), f32),
        jax.ShapeDtypeStruct((nch, 1, chunk), f32),
        jax.ShapeDtypeStruct((nch, 1, R), f32),
        jax.ShapeDtypeStruct((nch, chunk, W), np.uint8),
    ).compile().as_text()
    require("tpu_custom_call" in hlo, "pallas program holds no TPU kernel")
    report("pallas_kernel", tpu_custom_call=True)

    rng = np.random.default_rng(seed + 1)
    deltas = []
    for k in range(K_CYCLES):
        add = np.zeros_like(p.free)
        add[:, 0] = rng.integers(0, 9, size=W)          # cores back
        add[:, 2] = rng.integers(0, 33, size=W)         # GB back
        deltas.append(CycleDelta(
            arrivals=rng.integers(0, 4, size=C).astype(np.int64),
            free_add=add, budget=None if k % 2 else 5_000))
    jaxmm = device["jax"]
    want_k, seq_s, _ = timed(
        clock, lambda: sequential_match_cycles(ref, p, deltas))
    got_k, first_s, compile_s = timed(
        clock, lambda: jaxmm.match_cycles(p, deltas))
    _, exec_s, _ = timed(clock, lambda: jaxmm.match_cycles(p, deltas))
    require(len(got_k) == K_CYCLES
            and all(plans_equal(a, b) for a, b in zip(want_k, got_k)),
            "match_cycles differs from the sequential numpy reference")
    report("match_cycles", k=K_CYCLES, numpy_sequential_s=seq_s,
           compile_s=compile_s, first_call_s=first_s, execute_s=exec_s,
           claimed=[plan.claimed for plan in got_k], identical=True)

    frees = [np.maximum(p.free - rng.integers(0, 5, size=p.free.shape), 0)
             .astype(np.float64) for _ in range(N_CANDIDATES)]
    demands = [np.maximum(p.demand - rng.integers(0, 40, size=C), 0)
               for _ in range(N_CANDIDATES)]
    want_n, seq_s, _ = timed(
        clock, lambda: sequential_preview_many(ref, p, frees, demands))
    got_n, first_s, compile_s = timed(
        clock, lambda: jaxmm.preview_many(p, frees, demands))
    _, exec_s, _ = timed(clock, lambda: jaxmm.preview_many(p, frees, demands))
    require(all(np.array_equal(a, b) for a, b in zip(want_n, got_n)),
            "preview_many differs from the sequential numpy reference")
    report("preview_many", n=N_CANDIDATES, numpy_sequential_s=seq_s,
           compile_s=compile_s, first_call_s=first_s, execute_s=exec_s,
           identical=True)


def serve_day(matchmaker: str, records, clock: CompileClock) -> dict:
    """Stream the day into a fresh service as fast as it goes, drain it,
    and return what the comparison needs."""
    ini = STANDARD_INI.replace(
        "[provision]\n", f"[provision]\nmatchmaker={matchmaker}\n", 1)
    t0 = time.perf_counter()
    clock.lap()
    svc = PoolService(ini, tick_s=30.0, negotiate_interval_s=60.0,
                      metrics_interval_s=300.0, seed=0, speed=None)
    require(svc.sim.collector.matchmaker.name == matchmaker,
            f"service did not select matchmaker={matchmaker}")
    claims = []
    for q in svc.sim.queues:
        q.add_claim_hook(
            lambda job, now: claims.append((job.jid, job.claimed_by, now)))
    PoolClient(svc).submit(records, at_trace_times=True, at=0.0)
    svc.run_until_drained(max_t=5e6)
    wall = time.perf_counter() - t0
    col = svc.sim.collector
    totals = col.profiler.phase_totals()
    return {
        "wall_s": wall, "compile_s": clock.lap(),
        "claims": sorted(claims, key=lambda c: (c[2], c[0])),
        "stats": svc.completed_stats().state_dict(),
        "sim_t": svc.sim.now,
        "jit_compiles": totals["jit_compiles_by_path"],
        "fused_batches": col.fused_batches,
        "fallbacks": {k[0]: int(c.value)
                      for k, c in col._c_fallbacks.children.items()},
        "cycles": totals["cycles"],
        "phase_s": {k: totals[k] for k in (
            "build_s", "match_s", "apply_s", "reconcile_s", "preview_s")},
    }


def served_phase(seed: int, jobs: int, clock: CompileClock):
    trace = generate_preset("diurnal", jobs, seed=seed)
    records = trace.records
    runs = {mm: serve_day(mm, records, clock) for mm in ("jax", "numpy")}
    for mm, r in runs.items():
        report("served", matchmaker=mm, jobs=jobs, wall_s=r["wall_s"],
               compile_s=r["compile_s"], sim_t=r["sim_t"],
               claims=len(r["claims"]), completed=r["stats"]["n"],
               jit_compiles=r["jit_compiles"],
               fused_batches=r["fused_batches"], fallbacks=r["fallbacks"],
               cycles=r["cycles"], phase_s=r["phase_s"])
    dev, ref = runs["jax"], runs["numpy"]
    require(ref["stats"]["n"] == jobs, "numpy run left jobs uncompleted")
    require(dev["stats"]["n"] == jobs, "jax run left jobs uncompleted")
    require(dev["claims"] == ref["claims"], "claim maps differ")
    require(dev["stats"] == ref["stats"], "completion statistics differ")
    report("served_identity", claims_equal=True, stats_equal=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--jobs", type=int, default=100_000,
                    help="jobs in the served diurnal day")
    args = ap.parse_args(argv)

    dev = device_phase()
    report("compile_cache", dir=use_compile_cache())
    clock = CompileClock()
    t0 = time.perf_counter()
    matchmaker_phase(args.seed, clock)
    report("matchmaker_phase", wall_s=time.perf_counter() - t0)
    t0 = time.perf_counter()
    served_phase(args.seed, args.jobs, clock)
    report("served_phase", wall_s=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
