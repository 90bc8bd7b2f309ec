"""Matchmaker backends head-to-head: one negotiation step at scale.

ISSUE 6 acceptance: the jitted JAX water-fill must be >= 5x faster than
the NumPy reference on the 100k-job tier, claim-for-claim identical.

What is timed is ONE `Matchmaker.match` call — the pure negotiation
step both backends expose behind the protocol — on the paper's
demand >> supply shape: a large idle backlog (cohort-compressed, the
job queue's cohort index does that for free) against a Kubernetes pool
of a few hundred partitionable slots (bench_event_engine provisions 600
pods for its 100k-job campaign).  Tiers scale the backlog:

    tier    jobs      cohorts  workers
    10k     10_000      512      128
    100k    100_000    4_096      512
    1m      1_000_000  16_384    1_024

The JAX timing EXCLUDES the one-off jit trace (warmup) and INCLUDES
host->device transfer of the cycle's arrays — it is the steady-state
per-cycle cost a simulation pays.  `identical` is a hard gate: a fast
wrong matchmaker fails the bench before any ratio is read.

The END-TO-END tier (ISSUE 8) times the whole Collector pipeline —
problem build from live cohorts, match, claim apply-back — over a
K-wave submission campaign, three series on identical pools:

    numpy      K × run_cycle against the NumPy reference
    jax        K × run_cycle against the jitted water-fill (per-cycle
               dispatch: K problem builds, K device round-trips)
    fused      K × stage_cycle + one flush through the fused K-cycle
               jit (ONE problem build, ONE device dispatch)

`e2e_identical` gates all three claim maps (jid, worker, timestamp)
bitwise; `--e2e-min-ratio` gates jax_s / fused_s at the first tier.

The PREVIEW REPLAY tier (ISSUE 10) streams the 2k-job diurnal day
through the standard federation with the profiler on, once per backend,
and splits the provisioner's reconcile wall into preview vs the rest —
`--preview-max-ratio` gates the jax preview wall against numpy's (the
batched vmapped preview dispatch must not pay per-call jit overhead).

Usage:
    python benchmarks/bench_matchmaking.py [--tiers 10k,100k,1m]
        [--budget-s SECONDS] [--min-ratio 5] [--repeats 3]
        [--e2e-min-ratio 1.5] [--preview-jobs 2000]
        [--preview-max-ratio 2]
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from benchmarks.common import Timer, emit
from repro.core.matchmaker import (
    MatchProblem, NumpyMatchmaker, make_matchmaker,
)

TIERS = {
    "10k": dict(jobs=10_000, C=512, W=128),
    "100k": dict(jobs=100_000, C=4_096, W=512),
    "1m": dict(jobs=1_000_000, C=16_384, W=1_024),
}
R = 6


def build_problem(jobs: int, C: int, W: int, seed: int = 0) -> MatchProblem:
    """The paper regime: heterogeneous 1-4 cpu / 0-1 gpu requests,
    cohort-compressed backlog, a pool that drains mid-cycle."""
    rng = np.random.default_rng(seed)
    requests = np.zeros((C, R))
    requests[:, 0] = rng.integers(1, 5, size=C)           # cpus
    requests[:, 1] = rng.integers(0, 2, size=C)           # gpus
    requests[:, 2] = rng.integers(1, 9, size=C)           # memory GB
    demand = np.full(C, jobs // C, dtype=np.int64)
    demand[: jobs % C] += 1
    free = np.zeros((W, R))
    free[:, 0] = rng.integers(8, 65, size=W)
    free[:, 1] = rng.integers(0, 9, size=W)
    free[:, 2] = rng.integers(32, 257, size=W)
    compat = rng.random((C, W)) < 0.9
    return MatchProblem(
        keys=[(0, c) for c in range(C)], requests=requests,
        demand=demand, order=rng.permutation(C).astype(np.int64),
        free=free, capacity=free.copy(),
        compat=np.asarray(compat, dtype=bool))


def best_of(fn, repeats: int) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


# -- replay tier: provisioner preview wall over the 2k diurnal day -----------

def run_preview_replay(n_jobs: int = 2_000, duration_s: float = 14_400.0,
                       seed: int = 3, batch: int = 8) -> dict:
    """ISSUE 10 acceptance surface: stream the diurnal trace through
    the standard federation with the profiler on, once per backend, and
    report where the provisioner's reconcile wall goes.  The jax
    backend's batched preview dispatch (device-resident constants, no
    per-call problem rebuild) must keep its preview wall within the
    same order as numpy's — the `--preview-max-ratio` CI guard."""
    from repro.workload.compare import standard_policy
    from repro.workload.generators import diurnal_day
    from repro.workload.replay import replay_trace

    out: dict = {"jobs": n_jobs, "duration_s": duration_s, "seed": seed,
                 "negotiation_batch": batch}
    for mm in ("numpy", "jax"):
        trace = diurnal_day(n_jobs, seed=seed, duration_s=duration_s)
        # fusion-friendly cadence: negotiations fire every 20s INSIDE a
        # 60s tick/reconcile/metrics grid, so the [20,40] windows carry
        # no observer events and the backlog-driven deferral can stage
        # 2+ cycles per flush (the default 30s tick grid puts a
        # reconcile on every negotiation instant, vetoing every window)
        spec = standard_policy("fill-first", tick_s=60.0,
                               negotiate_interval_s=20.0,
                               metrics_interval_s=60.0)
        spec.ini = spec.ini.replace(
            "[provision]\n",
            f"[provision]\nmatchmaker={mm}\nnegotiation_batch={batch}\n", 1)
        sim = spec.build(telemetry=True)
        replay_trace(sim, trace, coalesce_s=0.0)
        t0 = time.perf_counter()
        sim.run_until_drained(max_t=5e6)
        wall = time.perf_counter() - t0
        assert sim.queue.drained(), f"{mm} replay failed to drain"
        totals = sim.collector.profiler.phase_totals()
        col = sim.collector
        fallbacks = {k[0]: int(c.value)
                     for k, c in col._c_fallbacks.children.items()}
        flushes = col.fused_batches + col.staged_fallbacks
        out[mm] = {
            "wall_s": round(wall, 3),
            "reconcile_s": round(totals["reconcile_s"], 3),
            "preview_s": round(totals["preview_s"], 3),
            "preview_legacy": col.preview_legacy,
            "jit_compiles_by_path": totals["jit_compiles_by_path"],
            "fused_batches": col.fused_batches,
            "fused_cycles": col.fused_cycles,
            "fallbacks": fallbacks,
            "single_cycle_fraction": (
                round(fallbacks.get("single_cycle", 0) / flushes, 3)
                if flushes else None),
        }
    if out["numpy"]["preview_s"] > 0:
        out["preview_ratio"] = round(
            out["jax"]["preview_s"] / out["numpy"]["preview_s"], 3)
    return out


# -- end-to-end tier: Collector build -> match -> apply over K waves ---------

E2E = {
    # waves of NEW cohort shapes (memory varies per wave) so early full
    # drains never re-arrive — the fused batch stays on the jit path
    "10k": dict(jobs=10_000, waves=16, W=128, cpus=64),
    "100k": dict(jobs=100_000, waves=16, W=512, cpus=64),
}


def _e2e_pool(matchmaker, spec, batch: int):
    """A fresh pool + pre-loaded K-wave queue (setup is NOT timed).
    Workers pre-boot at t=0 and absorb roughly a third of the campaign;
    wave k's jobs carry submit time t_k - 1, so the staged flush and the
    `max_submit` replay see identical per-cycle visibility."""
    from repro.core.classad import ClassAdExpr
    from repro.core.jobqueue import Job, JobQueue
    from repro.core.worker import Collector, Worker

    col = Collector(matchmaker=matchmaker, negotiation_batch=batch)
    for i in range(spec["W"]):
        w = Worker(name=f"w{i}", ad={"cpus": spec["cpus"], "memory": 8192},
                   start_expr=ClassAdExpr("True"))
        w.booted_at = 0.0
        col.advertise(w)
    q = JobQueue()
    waves = spec["waves"]
    per_wave = spec["jobs"] // waves
    times = [60.0 * (k + 1) for k in range(waves)]
    for k, t in enumerate(times):
        for i in range(per_wave):
            q.submit(Job(ad={"request_cpus": 1 + (i % 4),
                             "request_memory": 4 + 8 * k,   # new shapes/wave
                             "owner": f"u{i % 4}",
                             "runtime_s": 1e6}), now=t - 1.0)
    return col, q, times


def _claim_map(q):
    return sorted((j.jid, j.claimed_by, j.attempt_started_at)
                  for j in q.jobs() if j.claimed_by is not None)


def run_e2e(tier: str, repeats: int, jax_mm, numpy_mm) -> dict:
    spec = E2E[tier]
    row = dict(spec)

    def percycle(mm):
        col, q, times = _e2e_pool(mm, spec, batch=1)
        t0 = time.perf_counter()
        claimed = sum(col.run_cycle(q, t, max_submit=t) for t in times)
        return time.perf_counter() - t0, claimed, _claim_map(q)

    def fused(mm):
        col, q, times = _e2e_pool(mm, spec, batch=spec["waves"])
        t0 = time.perf_counter()
        claimed = sum(col.stage_cycle(q, t) for t in times)
        claimed += col.quiesce()
        return (time.perf_counter() - t0, claimed, _claim_map(q),
                col.fused_batches, col.staged_fallbacks)

    np_s, np_claimed, np_map = min(
        (percycle(numpy_mm) for _ in range(repeats)), key=lambda r: r[0])
    row["numpy_s"] = round(np_s, 4)
    row["claimed"] = np_claimed
    percycle(jax_mm)                                  # warmup: jit trace
    fused(jax_mm)
    jx_s, jx_claimed, jx_map = min(
        (percycle(jax_mm) for _ in range(repeats)), key=lambda r: r[0])
    fu_s, fu_claimed, fu_map, fb, ffb = min(
        (fused(jax_mm) for _ in range(repeats)), key=lambda r: r[0])
    row["jax_s"] = round(jx_s, 4)
    row["fused_s"] = round(fu_s, 4)
    row["fused_ratio"] = round(jx_s / fu_s, 2)
    row["fused_batches"] = fb
    row["staged_fallbacks"] = ffb
    row["e2e_identical"] = bool(np_map == jx_map == fu_map
                                and np_claimed == jx_claimed == fu_claimed)
    return row


def run(echo: bool = True, tiers=("10k", "100k"), repeats: int = 5,
        e2e_tiers=("10k",), e2e_repeats: int = 3,
        preview_jobs: int | None = 2_000):
    ref = NumpyMatchmaker()
    jaxmm = make_matchmaker("jax")
    out = {"tiers": {}, "e2e": {}}
    with Timer() as total:
        for tier in tiers:
            spec = TIERS[tier]
            p = build_problem(**spec)
            row = dict(spec)
            plan_ref = ref.match(p)
            row["claimed"] = plan_ref.claimed
            row["numpy_s"] = best_of(lambda: ref.match(p), repeats)
            plan_jax = jaxmm.match(p)              # warmup: jit trace
            row["identical"] = bool(
                np.array_equal(plan_ref.takes, plan_jax.takes)
                and np.allclose(plan_ref.free_after, plan_jax.free_after))
            row["jax_s"] = best_of(lambda: jaxmm.match(p), repeats)
            row["ratio"] = round(row["numpy_s"] / row["jax_s"], 2)
            out["tiers"][tier] = row
        for tier in e2e_tiers:
            out["e2e"][tier] = run_e2e(tier, e2e_repeats, jaxmm, ref)
        if preview_jobs:
            out["preview_replay"] = run_preview_replay(preview_jobs)
    out["wall_s"] = round(total.s, 2)
    meta = None
    pr = out.get("preview_replay")
    if pr:
        meta = {"reconcile_preview_split": {
            mm: {"reconcile_s": pr[mm]["reconcile_s"],
                 "preview_s": pr[mm]["preview_s"]}
            for mm in ("numpy", "jax") if mm in pr}}
    emit("matchmaking", out, echo=echo, meta=meta)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--tiers", default="10k,100k",
                    help="comma list from 10k,100k,1m")
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--budget-s", type=float, default=None,
                    help="fail if the whole bench exceeds this wall time")
    ap.add_argument("--min-ratio", type=float, default=None,
                    help="fail if the jax/numpy speedup at the largest "
                         "requested tier is below this")
    ap.add_argument("--e2e-tiers", default="10k",
                    help="comma list from 10k,100k (empty disables e2e)")
    ap.add_argument("--e2e-min-ratio", type=float, default=None,
                    help="fail if the fused-batch speedup over per-cycle "
                         "jax at the first e2e tier is below this")
    ap.add_argument("--preview-jobs", type=int, default=2_000,
                    help="diurnal replay size for the preview tier "
                         "(0 disables it)")
    ap.add_argument("--preview-max-ratio", type=float, default=None,
                    help="fail if the jax preview wall exceeds this "
                         "multiple of the numpy preview wall on the "
                         "diurnal replay tier")
    args = ap.parse_args(argv)
    tiers = [t.strip() for t in args.tiers.split(",") if t.strip()]
    e2e_tiers = [t.strip() for t in args.e2e_tiers.split(",") if t.strip()]
    unknown = ([t for t in tiers if t not in TIERS]
               + [t for t in e2e_tiers if t not in E2E])
    if unknown:
        print(f"[bench] unknown tiers {unknown}; known: {sorted(TIERS)} "
              f"(e2e: {sorted(E2E)})", file=sys.stderr)
        return 2
    out = run(echo=True, tiers=tiers, repeats=args.repeats,
              e2e_tiers=e2e_tiers, preview_jobs=args.preview_jobs or None)
    rc = 0
    if args.preview_max_ratio is not None:
        pr = out.get("preview_replay") or {}
        ratio = pr.get("preview_ratio")
        if ratio is None:
            print("[bench] FAIL: --preview-max-ratio given but the "
                  "preview replay tier did not run", file=sys.stderr)
            rc = 1
        elif ratio > args.preview_max_ratio:
            print(f"[bench] FAIL: jax preview wall {pr['jax']['preview_s']}s"
                  f" is {ratio}x numpy's {pr['numpy']['preview_s']}s "
                  f"(max {args.preview_max_ratio}x)", file=sys.stderr)
            rc = 1
        # backlog-driven live fusion must engage on the replay: with
        # negotiation_batch > 1 the quiet windows between the 60s
        # reconcile instants must defer flushes, so single-cycle
        # fallbacks can no longer be 100% of flushes (the pre-deferral
        # live engine quiesced every cycle in place).  Completion-heavy
        # stretches still veto deferral cycle-by-cycle — exactness over
        # batching — so the guard is on the fraction, not on a count of
        # non-empty fused batches (tests/test_live_fusion.py pins those
        # on a saturated pool).
        for mm in ("numpy", "jax"):
            row = pr.get(mm)
            if (row is not None and pr.get("negotiation_batch", 1) > 1
                    and not (row["single_cycle_fraction"] is not None
                             and row["single_cycle_fraction"] < 1.0)):
                print(f"[bench] FAIL: live fusion never engaged on the "
                      f"{mm} preview replay (single-cycle fallbacks were "
                      f"100% of flushes)", file=sys.stderr)
                rc = 1
    for tier in tiers:
        row = out["tiers"][tier]
        if row["identical"] is False:
            print(f"[bench] FAIL: jax plan diverges from the reference "
                  f"at tier {tier}", file=sys.stderr)
            rc = 1
    for tier in e2e_tiers:
        row = out["e2e"][tier]
        if row["e2e_identical"] is False:
            print(f"[bench] FAIL: e2e claim maps diverge across series "
                  f"at tier {tier}", file=sys.stderr)
            rc = 1
    if args.e2e_min_ratio is not None and e2e_tiers:
        top = out["e2e"][e2e_tiers[0]]
        if top["fused_batches"] < 1:
            print("[bench] FAIL: fused path never engaged "
                  f"(fallbacks={top['staged_fallbacks']})", file=sys.stderr)
            rc = 1
        elif top["fused_ratio"] < args.e2e_min_ratio:
            print(f"[bench] FAIL: fused speedup {top['fused_ratio']}x < "
                  f"{args.e2e_min_ratio}x at e2e tier {e2e_tiers[0]}",
                  file=sys.stderr)
            rc = 1
    top = out["tiers"][tiers[-1]]
    if args.min_ratio is not None and top["ratio"] < args.min_ratio:
        print(f"[bench] FAIL: jax speedup {top['ratio']}x < "
              f"{args.min_ratio}x at tier {tiers[-1]}", file=sys.stderr)
        rc = 1
    if args.budget_s is not None and out["wall_s"] > args.budget_s:
        print(f"[bench] FAIL: wall {out['wall_s']}s > budget "
              f"{args.budget_s}s", file=sys.stderr)
        rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
