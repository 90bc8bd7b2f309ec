"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (BENCHMARK.json ``workloads``) names a deployment
(``bench/configs/<config>.json``: the pool's INI and its service
settings) and a traffic mix (``bench/traffic/<traffic>.json``).  A run:

  1. needs a TPU and as many chips as the cell asks for; anything else
     exits nonzero before any result;
  2. set-up: builds a `PoolService` from the deployment (``speed=None``:
     the served path, sprinting), schedules the seeded trace through
     `PoolClient.submit(..., at_trace_times=True)`, each record to the
     schedd it names (or the default one), runs the warm-up
     span of simulated time, then calls the matchmaker once on every
     padding bucket that the warm-up's second half touched, with its
     calls or with the sizes of its passes (and one cohort chunk either
     side), so that nothing compiles in the window;
  3. window: runs the service's event thread for ``--seconds`` of wall
     time; every pass, reconcile and claim inside is recorded by
     `bench.probe`; with ``--trace 1`` the JAX profiler traces the
     window's first `TRACE_SECONDS`, and the service is stopped while
     the trace is written, so that the window still holds ``--seconds``
     of service and none of the writing;
  4. reads the peak device memory, computes the cell's metrics (the
     end-to-end ones untraced, the per-layer ones traced) with the
     readers in ``bench/metrics/``, then draws the trace again from the
     seed and checks the sampled passes' claims and device calls
     against the plain references (`bench.check`).

The last line of standard output is one JSON object; the last lines of
standard error are the numbers compared, each with its limit.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.cells import Catalog  # noqa: E402

#: cohort-chunk and worker-lane neighbours warmed around the shapes the
#: warm-up saw
WARM_CHUNKS_AROUND = 1
#: the program's worker-axis padding granularity (lanes)
WORKER_LANES = 128
#: a traced run traces the window's first this many seconds: enough
#: passes for the device metrics, and a trace that reduces in seconds
TRACE_SECONDS = 15.0


def device_or_exit(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench: this cell needs {chips} TPU chip(s); JAX found "
              f"{len(devs)} {devs[0].platform} device(s) "
              f"({devs[0].device_kind})", file=sys.stderr)
        raise SystemExit(2)


def counters(sim) -> dict:
    reg = sim.telemetry.registry

    def children(name):
        try:
            return reg.family(name).children
        except KeyError:
            return {}

    prev = children("repro_reconcile_preview_seconds").get(())
    return {
        "cycles": {k[0]: c.value
                   for k, c in children("repro_cycles_total").items()},
        "jit_compiles": sum(
            c.value for c in
            children("repro_matchmaker_jit_compiles_total").values()),
        "preview_sum": 0.0 if prev is None else prev.sum,
        "preview_count": 0 if prev is None else prev.count,
    }


class CompileCount:
    """XLA compilations, from JAX's monitoring events."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


def warm_buckets(mm, shapes: dict):
    """Call the matchmaker once per padding bucket that `shapes` (the
    (C, W) sizes of the calls and plain passes of the warm-up's second
    half) touches, plus one cohort chunk and one worker bucket either
    side, with empty problems."""
    import numpy as np

    from repro.core.matchmaker import MatchProblem
    from repro.core.matchmaker.base import RESOURCE_KEYS

    chunk = int(getattr(mm, "chunk", 64))
    R = len(RESOURCE_KEYS)

    def problem(C, W):
        return MatchProblem(
            keys=[(0, c) for c in range(C)], requests=np.ones((C, R)),
            demand=np.ones(C, dtype=np.int64),
            order=np.arange(C, dtype=np.int64), free=np.zeros((W, R)),
            capacity=np.zeros((W, R)), compat=np.zeros((C, W), dtype=bool))

    def grid(sizes, step, around):
        lo = (min(sizes) - 1) // step - around
        hi = (max(sizes) - 1) // step + around
        return [max(1, (k + 1) * step) for k in range(max(lo, 0), hi + 1)]

    for kind in ("match", "preview"):
        seen = shapes[kind] | shapes["pass"]
        if not seen:
            continue
        cs = grid([c for c, _w in seen], chunk, WARM_CHUNKS_AROUND)
        ws = grid([w for _c, w in seen], WORKER_LANES, WARM_CHUNKS_AROUND)
        for C in cs:
            for W in ws:
                p = problem(C, W)
                if kind == "match":
                    mm.match(p)
                else:
                    mm.preview_many(p, [p.free])


def route(records: list[dict]) -> dict:
    """The records by the schedd each names (None: the default one), in
    trace order within each."""
    out: dict = {}
    for r in records:
        out.setdefault(r.get("schedd"), []).append(r)
    return out


def window_cycles(prof, before):
    """Profiler cycle records appended after `before` (the last record
    at the window's start)."""
    recs = list(prof.cycles)
    if before is None:
        return recs
    for i in range(len(recs) - 1, -1, -1):
        if recs[i] is before:
            return recs[i + 1:]
    return recs


def run_cell(catalog: Catalog, workload: str, seed: int, seconds: float,
             traced: bool, *, t_start: float | None = None,
             overrides: dict | None = None, wrap_matchmaker=None,
             save_trace: str | None = None) -> dict:
    """Set up, measure and check one cell; returns the result object.
    `overrides` replaces traffic parameters and `wrap_matchmaker`
    replaces the pool's matchmaker (both for tests and the control)."""
    import jax

    from repro.compile_cache import use_compile_cache
    from repro.service.pool import PoolClient, PoolService

    from bench import check, passes
    from bench.probe import Probe

    t_start = time.perf_counter() if t_start is None else t_start
    if jax.default_backend() == "tpu":
        # every program the window uses, however quick to compile, is
        # found in the checkout's cache by the next run
        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = CompileCount()

    marks = {"start": t_start, "imported": time.perf_counter()}
    cell = catalog.cell(workload)
    config = catalog.config(cell["config"])
    deployment = passes.Deployment(config)
    traffic = dict(catalog.traffic(cell["traffic"]), **(overrides or {}))
    generator = catalog.generator(traffic["shape"])
    records = generator.generate(traffic, seed)
    marks["generated"] = time.perf_counter()

    svc = PoolService(config["ini"], seed=seed, speed=None,
                      **config["service"])
    col = svc.sim.collector
    if wrap_matchmaker is not None:
        col.matchmaker = wrap_matchmaker(col.matchmaker)
    probe = Probe(svc, seed=seed, traced=traced)
    probe.expect(records, svc.sim.queue.name)
    client = PoolClient(svc)
    for schedd, recs in route(records).items():
        client.submit(recs, schedd=schedd, at_trace_times=True, at=0.0)
    del records
    marks["submitted"] = time.perf_counter()

    warm_s = float(traffic["warmup_s"])
    svc.sim.run(warm_s / 2)
    probe.shapes = {"match": set(), "preview": set(), "pass": set()}
    svc.sim.run(warm_s)
    marks["warmed_up"] = time.perf_counter()
    warm_buckets(col.matchmaker, probe.shapes)
    probe.shapes = None
    horizon = float(traffic["horizon_s"])

    prof = col.profiler
    before = prof.cycles[-1] if prof.cycles else None
    c0 = counters(svc.sim)
    n_compiles0 = compiles.n
    sim_t0 = svc.sim.now
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    gc.collect()
    if traced:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0      # no per-call Python events
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        note = jax.profiler.TraceAnnotation("bench.window")
        note.__enter__()
    t0 = marks["window"] = time.perf_counter()
    spans = []

    def serve(seconds):
        t = time.perf_counter()
        probe.start()
        svc.start()
        time.sleep(seconds)
        svc.stop()
        probe.stop()
        spans.append((t, time.perf_counter()))

    if traced:
        serve(min(seconds, TRACE_SECONDS))
        note.__exit__(None, None, None)
        probe.traced = False
        jax.profiler.stop_trace()
        marks["trace_written"] = time.perf_counter()
    left = seconds - sum(b - a for a, b in spans)
    if left > 0 or not spans:
        serve(max(left, 0.0))
    marks["closed"] = time.perf_counter()
    window_s = sum(b - a for a, b in spans)
    sim_t1 = svc.sim.now
    c1 = counters(svc.sim)
    n_compiles = compiles.n - n_compiles0
    cycles = window_cycles(prof, before)

    devs = jax.devices()
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs[:cell["chips"]])
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell["chips"], "memory_peak_bytes": peak}

    reduced = None
    if traced:
        from bench import trace_reduce

        path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
        if save_trace:
            shutil.copy(path, save_trace)
        reduced = trace_reduce.reduce(
            trace_reduce.extract(path, cell["chips"]))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]

    # what the metric readers (bench/metrics/<name>.py) see of the run
    win = SimpleNamespace(
        probe=probe, window_s=window_s, setup_s=t0 - t_start,
        bytes_moved=probe.bytes_moved() if traced else None,
        claims=len(probe.claims), counters_before=c0, counters_after=c1,
        cycles=cycles, compiles=n_compiles, trace=reduced,
        device_kind=devs[0].device_kind)
    metrics = {}
    for m in catalog.metrics_of(workload, traced):
        value = catalog.reader(m["name"]).read(win)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    # the program's state goes before the reference runs
    probe.sim = None
    del svc, col, prof
    gc.collect()
    probe.device_calls = []
    records = generator.generate(traffic, seed)
    marks["measured"] = time.perf_counter()
    numbers, failed, ties = check.compare(probe, records, deployment)
    marks["checked"] = time.perf_counter()
    if horizon > 0:
        # a window that ran past the trace's end measured an emptying pool
        numbers["sim_end_past_horizon"] = (int(sim_t1 > horizon), 0)
    correct = all(v <= limit for v, limit in numbers.values())
    out = {
        "correct": correct,
        "attempted": len(probe.passes) + len(probe.reconciles),
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if reduced is not None:
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["window"] = {"sim_s": [sim_t0, sim_t1], "passes": len(probe.passes),
                     "reconciles": len(probe.reconciles),
                     "claims": len(probe.claims), "sampled":
                     check.sampled(probe, ties)}
    stamps = list(marks.items())
    out["timing_s"] = {b: tb - ta for (_a, ta), (b, tb) in
                       zip(stamps[:-1], stamps[1:])}
    out["checks"] = {k: {"value": v, "limit": limit}
                     for k, (v, limit) in numbers.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true",
                    help="put the control (bench.reference's parallel "
                         "water-fill) in the matchmaker's place; the check "
                         "must then come out false")
    ap.add_argument("--save-trace", default=None,
                    help="copy the traced window's .xplane.pb here")
    args = ap.parse_args(argv)

    catalog = Catalog()
    cell = catalog.cell(args.workload)
    device_or_exit(cell["chips"])
    wrap = None
    if args.control:
        from bench.reference import ControlMatchmaker
        wrap = lambda _mm: ControlMatchmaker()  # noqa: E731
    out = run_cell(catalog, args.workload, args.seed, args.seconds,
                   bool(args.trace), t_start=T_START, wrap_matchmaker=wrap,
                   save_trace=args.save_trace)
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
