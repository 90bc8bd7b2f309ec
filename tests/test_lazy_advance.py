"""The event engine's lazy worker advancement (core/calendar.py) against
the eager walk it replaces (`advance_workers`, every worker before every
event): the same claims, completions and terminations in the same hook
order, the same clocks; and the calendar's edge cases — a finish on an
event time, a boot mid-segment, a C2 verdict flip, a snapshot mid-run."""
import json

import numpy as np
import pytest

from repro.core import (
    KubeBackend, KubeCluster, NodeAutoscaler, NodeTemplate, ProvisionerConfig,
    Simulation, gpu_job, onprem_nodes,
)
from repro.core.classad import ClassAdExpr
from repro.core.jobqueue import Job
from repro.core.stragglers import StragglerPolicy
from repro.core.worker import Worker, advance_workers


class EagerSim(Simulation):
    """The same engine with the calendar taken out: every advertised
    worker is walked before every event, as before the calendar."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.collector.calendar = None

    def _advance_unchecked(self, t):
        if t <= self._advanced_until:
            return
        advance_workers(self.collector, self.pool_queue, self.cluster_view,
                        self._advanced_until, t - self._advanced_until)
        self._advanced_until = t


def elastic(name, *, spot=False, max_nodes=3):
    cluster = KubeCluster([], name=name)
    tmpl = NodeTemplate(
        capacity={"cpu": 16, "gpu": 4, "memory": 64, "disk": 256},
        provision_delay_s=40, scale_down_delay_s=60, hourly_cost=1.0)
    return KubeBackend(name, cluster,
                       NodeAutoscaler(cluster, tmpl, max_nodes=max_nodes,
                                      prefix=f"{name}-np"), spot=spot)


#: scenario -> (idle timeout, backend tick, negotiation interval); the
#: coarse one has segments longer than the idle timeout
SCENARIOS = {"federation": (45.0, 5.0, 15.0), "flocked": (45.0, 5.0, 15.0),
             "coarse": (30.0, 50.0, 100.0)}


def federation(cls, scenario):
    """Three backends, short idle timeouts, stragglers retired, and
    fractional runtimes so that finishes fall inside segments."""
    idle_timeout, tick, negotiate = SCENARIOS[scenario]
    cfg = ProvisionerConfig(submit_interval_s=30, idle_timeout_s=idle_timeout,
                            startup_delay_s=20)
    onprem = KubeBackend("onprem", KubeCluster(
        onprem_nodes(2, gpus=4, cpus=16), name="onprem"))
    flocked = scenario == "flocked"
    sim = cls(cfg, backends=[onprem, elastic("cloud"),
                             elastic("spot", spot=True)],
              tick_s=tick, negotiate_interval_s=negotiate,
              metrics_interval_s=60.0, seed=11,
              straggler_policy=StragglerPolicy(factor=1.5,
                                               min_runtime_s=60.0),
              schedds=2 if flocked else None,
              fairshare=True if flocked else None)
    rng = np.random.default_rng(5)
    for i in range(70):
        t = float(np.round(rng.uniform(0, 1500), 1))
        runtime = float(rng.choice([37.3, 90.0, 161.7, 240.25, 400.0]))
        job = gpu_job(runtime, gpus=int(rng.integers(1, 3)),
                      cpus=int(rng.integers(1, 4)))
        job.ad["user"] = f"u{i % 3}"
        sim.submit_jobs(t, [job], schedd=(i % 2) if flocked else None)
    # one opaque job: a payload that reports its own progress
    left = {"s": 75.5}

    def work(job, dt):
        left["s"] -= dt
        return left["s"] <= 0

    sim.submit_jobs(100.0, [Job(ad={"request_cpus": 1, "request_gpus": 1,
                                    "request_memory": 4, "request_disk": 8},
                                runtime_s=1e9, work_fn=work)])
    sim.inject_slow_workers(250.0, frac=0.3, rate=0.2)
    sim.inject_pod_preemption(1100.0, frac=0.5, backend="spot")
    sim.at(1250.0, lambda s, now: s.drain_backend("cloud"), name="drain")
    return sim


def record(sim):
    log = {"claims": [], "done": [], "gone": []}
    for q in sim.queues:
        q.add_claim_hook(lambda job, now: log["claims"].append(
            (now, job.jid, job.claimed_by)))
        q.add_complete_hook(lambda job: log["done"].append(
            (job.jid, job.completed_at)))
    col = sim.collector
    inner = col.invalidate

    def invalidate(name):
        log["gone"].append((name, sim.loop.now))
        inner(name)

    col.invalidate = invalidate
    return log


def running(sim):
    """{jid: (worker, remaining work)}, brought up to now."""
    out = {}
    for w in sim.collector.workers.values():
        if w.cal is not None:
            w.cal.settle(w)
        for jid, job in w.claimed.items():
            if job.work_fn is None:
                out[jid] = (w.name, job.remaining_s)
    return out


@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_lazy_advance_matches_the_eager_walk(scenario):
    lazy, eager = federation(Simulation, scenario), federation(EagerSim,
                                                               scenario)
    assert lazy.collector.calendar is not None
    assert eager.collector.calendar is None
    logs = record(lazy), record(eager)
    for until in (240.0, 530.0, 1120.0, 1800.0, 6000.0):
        lazy.run(until)
        eager.run(until)
        a, b = running(lazy), running(eager)
        assert a.keys() == b.keys()
        for jid in a:
            assert a[jid][0] == b[jid][0]
            assert a[jid][1] == pytest.approx(b[jid][1], rel=1e-9,
                                              abs=1e-9)
    la, ea = logs
    assert [(t, j, w) for t, j, w in la["claims"]] == ea["claims"]
    assert [j for j, _t in la["done"]] == [j for j, _t in ea["done"]]
    for (_j, ta), (_k, tb) in zip(la["done"], ea["done"]):
        assert ta == pytest.approx(tb, abs=1e-9)
    assert [n for n, _t in la["gone"]] == [n for n, _t in ea["gone"]]
    assert [t for _n, t in la["gone"]] == [t for _n, t in ea["gone"]]
    assert len(lazy.all_workers) == len(eager.all_workers)
    for wa, wb in zip(lazy.all_workers, eager.all_workers):
        assert wa.name == wb.name and wa.terminated == wb.terminated
        assert wa.alive_s == pytest.approx(wb.alive_s, rel=1e-9)
        assert wa.busy_s == pytest.approx(wb.busy_s, rel=1e-9)
        assert wa.idle_since == pytest.approx(wb.idle_since, rel=1e-9)
    # the scenario reached every path it is meant to
    assert lazy.straggler_policy.rescheduled >= 1
    assert lazy.backend("spot").stats.pods_reclaimed >= 1
    assert [b.name for b in lazy.detached_backends] == ["cloud"]
    assert any(w.work_rate < 1.0 for w in lazy.all_workers)
    assert sum(1 for w in lazy.all_workers if w.terminated) >= 5
    assert lazy.drained() and eager.drained()
    reg = lazy.telemetry.registry
    touched = reg.get_value("repro_advance_workers_touched_total")
    calls = reg.get_value("repro_advance_calls_total")
    assert calls > 0 and touched / calls < 5


# -- the calendar's edge cases --------------------------------------------------

def one_worker_sim(**cfg):
    """A pool of one advertised worker and nothing else that claims it."""
    cfg = ProvisionerConfig(**{"submit_interval_s": 1e6,
                               "idle_timeout_s": 300.0, **cfg})
    sim = Simulation(cfg, nodes=[], tick_s=5.0, negotiate_interval_s=15.0)
    return sim


def advertise(sim, name, *, booted_at=0.0, idle_timeout=300.0, start=None,
              ad=None):
    w = Worker(name=name, ad=ad or {"cpus": 8, "gpus": 4, "memory": 64,
                                    "disk": 64},
               start_expr=ClassAdExpr(start), idle_timeout=idle_timeout,
               startup_delay=0.0)
    w.booted_at = booted_at
    sim.collector.advertise(w)
    return w


@pytest.mark.parametrize("runtime,done_at", [
    (45.0, 60.0),                   # exactly on the event time
    (45.0 + 5e-10, 60.0),           # within the tolerance after it
    (45.0 - 2e-9, 60.0 - 2e-9),     # just before it
])
def test_a_finish_on_an_event_time_completes_before_the_event(runtime,
                                                               done_at):
    sim = one_worker_sim()
    advertise(sim, "w0")
    sim.submit_jobs(15.0, [gpu_job(runtime)])
    seen = []
    sim.at(60.0, lambda s, now: seen.append(s.queue.n_running()))
    sim.run(61.0)
    (job,) = sim.queue.completed_log
    assert job.started_at == 15.0
    assert job.completed_at == pytest.approx(done_at, abs=1e-12)
    assert seen == [0]


def test_a_worker_booting_mid_segment_gets_a_full_idle_timeout():
    """test_idle_clock_never_predates_worker_boot, through the engine:
    boundaries every 5 s, a boot at 17.5, a 10 s idle timeout."""
    sim = one_worker_sim()
    w = advertise(sim, "w0", booted_at=17.5, idle_timeout=10.0)
    sim.run(20.0)
    assert w.idle_since == 17.5          # boot time, not segment start
    sim.run(27.0)
    assert not w.terminated              # 9.5 s idle so far
    sim.run(30.0)
    assert w.terminated                  # 17.5 + 10 <= 30
    assert w.alive_s == 12.5


@pytest.mark.parametrize("cls", [Simulation, EagerSim])
def test_an_idle_deadline_waits_for_the_eager_expression(cls):
    """A boundary a hair before the deadline pops it early; the worker
    lives on to the next boundary, as the eager walk's test says."""
    sim = one_worker_sim()
    if cls is EagerSim:
        sim.__class__, sim.collector.calendar = EagerSim, None
    w = advertise(sim, "w0", idle_timeout=10.0)
    sim.at(10.0 - 5e-7, lambda s, now: None)
    sim.run(9.9999999)
    assert w.idle_since == 0.0 and not w.terminated
    sim.run(10.0)
    assert w.terminated


def test_a_verdict_flip_moves_only_that_shapes_idle_clocks():
    sim = one_worker_sim()
    gpu = [advertise(sim, f"g{i}", idle_timeout=1e5) for i in range(3)]
    cpu = [advertise(sim, f"c{i}", idle_timeout=1e5,
                     start="arch == 'cpu'") for i in range(3)]
    # no negotiation claims anything
    sim.loop.cancel(sim._timers["negotiate"])
    sim.run(20.0)
    assert all(w.idle_since == 0.0 for w in gpu + cpu)
    job = Job(ad={"request_gpus": 1, "arch": "gpu"}, runtime_s=50.0)
    sim.at(32.5, lambda s, now: s.queue.submit(job, now))
    sim.run(40.0)
    assert all(w.idle_since == -1.0 for w in gpu)      # a job waits
    assert all(w.idle_since == 0.0 for w in cpu)       # untouched
    sim.at(52.5, lambda s, now: s.queue.remove(job.jid, now))
    sim.run(60.0)
    assert all(w.idle_since == 52.5 for w in gpu)      # from the flip
    assert all(w.idle_since == 0.0 for w in cpu)


def test_idle_workers_are_not_visited_between_transitions():
    sim = one_worker_sim()
    for i in range(50):
        advertise(sim, f"w{i}", idle_timeout=1e5)
    reg = sim.telemetry.registry
    sim.run(10.0)                        # the boot visits
    t0 = reg.get_value("repro_advance_workers_touched_total")
    c0 = reg.get_value("repro_advance_calls_total")
    sim.run(1000.0)
    touched = reg.get_value("repro_advance_workers_touched_total") - t0
    assert reg.get_value("repro_advance_calls_total") - c0 >= 190
    assert touched == 0


def test_the_tick_engine_keeps_the_eager_walk():
    sim = Simulation(ProvisionerConfig(), nodes=onprem_nodes(1),
                     engine="tick")
    sim.submit_jobs(0, [gpu_job(100.0)])
    sim.run(200.0)
    assert sim.collector.calendar is None
    assert all(w.cal is None for w in sim.all_workers)
    assert sim.queue.completed_log


def snap_sim():
    """Fractional runtimes, boots and rates, so that nothing in the
    clocks is exact."""
    cfg = ProvisionerConfig(submit_interval_s=30, idle_timeout_s=60,
                            startup_delay_s=17.3)
    return Simulation(cfg, nodes=onprem_nodes(2, gpus=4, cpus=16),
                      node_template=NodeTemplate(capacity={
                          "cpu": 16, "gpu": 4, "memory": 64, "disk": 256}),
                      max_nodes=4, tick_s=5.0, negotiate_interval_s=15.0,
                      seed=2)


def schedule(sim, after=-1.0):
    """The arrivals and the slowdown after `after` (a restored run has
    had the rest)."""
    for i in range(30):
        if 13.1 * i > after:
            sim.submit_jobs(13.1 * i, [gpu_job(101.7 + 31.3 * (i % 5),
                                               gpus=1 + i % 2)])
    if 130.0 > after:
        sim.inject_slow_workers(130.0, frac=0.5, rate=0.3)
    return sim


def test_a_snapshot_mid_run_continues_bit_for_bit():
    ref = schedule(snap_sim())
    ref.run(200.0)
    ref.run_until_drained(1e5)
    cut = schedule(snap_sim())
    cut.run(200.0)
    assert any(w.claimed and w.work_rate < 1.0
               for w in cut.collector.workers.values())
    state = json.loads(json.dumps(
        cut.state_dict(allow_pending_external=True)))
    assert any(ws["anchors"] for ws in state["workers"])
    resumed = snap_sim()
    resumed.restore(state)
    schedule(resumed, after=200.0)
    resumed.run_until_drained(1e5)
    assert resumed.summary() == ref.summary()
    assert ([(j.jid, j.completed_at) for j in resumed.queue.completed_log]
            == [(j.jid, j.completed_at) for j in ref.queue.completed_log])
    assert ([(w.name, w.alive_s, w.busy_s) for w in resumed.all_workers]
            == [(w.name, w.alive_s, w.busy_s) for w in ref.all_workers])


def test_an_old_snapshot_loads_its_fields_as_accrued_values():
    """A snapshot from before the calendar held clocks and remaining
    work as of its own time, and no anchors."""
    sim = schedule(snap_sim())
    sim.run(200.0)
    live = {w.name: w for w in sim.collector.workers.values()}
    for w in live.values():
        w.cal.settle(w)      # remaining work as of the snapshot
    state = json.loads(json.dumps(
        sim.state_dict(allow_pending_external=True)))
    for ws in state["workers"]:
        for key in ("alive_t", "busy_t", "anchors"):
            ws.pop(key)
        w = live.get(ws["name"])
        if w is not None:
            ws["alive_s"], ws["busy_s"] = w.alive_s, w.busy_s
    old = snap_sim()
    old.restore(state)
    schedule(old, after=200.0)
    assert any(w.claimed for w in old.collector.workers.values())
    for w in old.collector.workers.values():
        assert w.alive_s == live[w.name].alive_s
        assert w.busy_s == live[w.name].busy_s
        for job in w.claimed.values():
            assert job.run_t0 == 200.0
            assert job.t_finish == 200.0 + job.remaining_s / w.work_rate
    old.run_until_drained(1e5)
    assert old.drained()


def test_the_cached_c2_poll_equals_a_scan_of_every_idle_cohort():
    """Across random births and drains the poll the calendar's idle
    groups take their verdict from (one rescan per slot shape per
    `idle_version`) equals a scan of every idle cohort."""
    from repro.core import Collector, JobQueue
    rng = np.random.default_rng(3)
    col, q = Collector(), JobQueue()
    shapes = [Worker(name=f"w{i}", ad={"cpus": 8, "gpus": 4,
                                       "arch": arch},
                     start_expr=ClassAdExpr(start))
              for i, (arch, start) in enumerate([
                  ("x86", None), ("x86", "user == 'u1'"),
                  ("arm", "arch == 'arm'"), ("x86", "user == 'u3'")])]
    running = []
    for _ in range(400):
        r = rng.random()
        idle = q.idle_jobs()
        if r < 0.45 or not idle:
            user = f"u{int(rng.integers(0, 4))}"
            req = ClassAdExpr(["", "arch == 'arm'"][int(rng.integers(0, 2))]
                              or None)
            q.submit(Job(ad={"request_cpus": 1, "user": user},
                         requirements=req), now=0.0)
        elif r < 0.8:
            job = idle[int(rng.integers(0, len(idle)))]
            q.claim(job.jid, "w", 0.0)
            running.append(job)
        else:
            job = idle[int(rng.integers(0, len(idle)))]
            q.remove(job.jid, 0.0)
        if running and rng.random() < 0.2:
            q.release(running.pop().jid, 0.0, preempted=False)
        for w in shapes:
            want = any(col._shape_match(next(iter(jobs.values())), w)
                       for _k, jobs in q.idle_cohorts())
            assert col.any_cohort_matches(w, q) == want


@pytest.mark.parametrize("cls", [Simulation, EagerSim])
def test_dropped_verdicts_are_recomputed_at_the_next_boundary(cls):
    """`Collector.invalidate_cohort` (an ad mutated in place) can flip a
    C2 verdict with the idle-cohort set unchanged: the idle group takes
    the new verdict at the next boundary, as the eager walk's poll does."""
    sim = one_worker_sim()
    if cls is EagerSim:
        sim.__class__, sim.collector.calendar = EagerSim, None
    w = advertise(sim, "w0", idle_timeout=100.0)
    sim.loop.cancel(sim._timers["negotiate"])
    job = Job(ad={"request_gpus": 1}, runtime_s=50.0,
              requirements=ClassAdExpr("arch == 'arm'"))
    sim.submit_jobs(10.0, [job])
    sim.run(30.0)
    assert w.idle_since == 0.0           # nothing idle matches it
    job.requirements = ClassAdExpr(None)
    sim.collector.invalidate_cohort()
    sim.at(40.0, lambda s, now: None)
    sim.run(41.0)
    assert w.idle_since == -1.0          # the job now matches it
    sim.run(150.0)
    assert not w.terminated
