"""Fused multi-cycle negotiation (ISSUE 8 tentpole): a staged K-cycle
batch flushed through the fused jit is bit-identical — claim maps,
timestamps, free matrices — to K sequential single-cycle negotiations.

Three layers:
  * backend — `match_cycles` (one device dispatch) vs
    `sequential_match_cycles` (the K-loop reference) on random deltas;
  * collector — `stage_cycle`/`quiesce` pools vs `run_cycle` pools fed
    the identical interleaved submission stream, including the
    mid-batch quiesce, worker-churn (fingerprint) fallback, and the
    cohort reseed-hazard fallback;
  * simulation — `negotiation_batch=K` engines drain to the same claim
    map as `negotiation_batch=1`.
"""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.core.classad import ClassAdExpr
from repro.core.config import load_ini, dump_ini
from repro.core.jobqueue import Job, JobQueue
from repro.core.matchmaker import make_matchmaker
from repro.core.matchmaker.base import (
    CycleDelta, match_cycles, sequential_match_cycles,
)
from repro.core.worker import Collector, Worker

from test_matchmaker_differential import random_problem


# -- backend: fused K-cycle dispatch vs K-loop reference ---------------------

def random_deltas(rng, p, K):
    C, W = p.compat.shape
    deltas = []
    for _ in range(K):
        arrivals = rng.integers(0, 6, size=C).astype(np.int64)
        free_add = None
        if rng.random() < 0.5:
            free_add = np.zeros((W, p.requests.shape[1]))
            free_add[:, 0] = rng.integers(0, 5, size=W)
            free_add[:, 2] = rng.integers(0, 9, size=W)
        budget = (None if rng.random() < 0.7
                  else int(rng.integers(1, 40)))
        deltas.append(CycleDelta(arrivals=arrivals, free_add=free_add,
                                 budget=budget))
    return deltas


@pytest.mark.parametrize("K", [1, 2, 8])
def test_match_cycles_bit_identical_to_sequential(K):
    jaxmm = make_matchmaker("jax")
    ref = make_matchmaker("numpy")
    rng = np.random.default_rng(100 + K)
    for trial in range(8):
        p = random_problem(rng)
        p.demand = np.zeros_like(p.demand)     # arrivals carry the demand
        deltas = random_deltas(rng, p, K)
        fused = jaxmm.match_cycles(p, deltas)
        seq_jax = sequential_match_cycles(jaxmm, p, deltas)
        seq_np = match_cycles(ref, p, deltas)  # dispatcher -> sequential
        assert len(fused) == len(seq_jax) == len(seq_np) == K
        for k in range(K):
            np.testing.assert_array_equal(
                fused[k].takes, seq_jax[k].takes,
                err_msg=f"trial={trial} cycle={k} (vs sequential jax)")
            np.testing.assert_array_equal(
                fused[k].free_after, seq_jax[k].free_after,
                err_msg=f"trial={trial} cycle={k} free")
            np.testing.assert_array_equal(
                fused[k].takes, seq_np[k].takes,
                err_msg=f"trial={trial} cycle={k} (vs numpy)")


# -- collector: staged batches vs interleaved sequential cycles --------------

def mk_pool(batch, n_workers=10, cpus=8, matchmaker="jax"):
    col = Collector(matchmaker=matchmaker, negotiation_batch=batch)
    for i in range(n_workers):
        w = Worker(name=f"w{i}", ad={"cpus": cpus, "memory": 64},
                   start_expr=ClassAdExpr("True"))
        w.booted_at = 0.0
        col.advertise(w)
    return col, JobQueue()


def submit_wave(q, t, n, cpus=1, mem=4, user="alice"):
    for _ in range(n):
        q.submit(Job(ad={"request_cpus": cpus, "request_memory": mem,
                         "owner": user, "runtime_s": 1e5}), now=t)


def full_claim_map(q):
    return sorted((j.jid, j.claimed_by, j.attempt_started_at)
                  for j in q.jobs() if j.claimed_by is not None)


@pytest.mark.parametrize("K", [1, 2, 8])
def test_staged_flush_identical_to_sequential(K):
    """Random interleaved waves: whatever mix of fused batches and
    fallbacks the guards pick, the claim map (including the per-claim
    timestamps) must equal the cycle-by-cycle reference."""
    rng = np.random.default_rng(7 + K)
    for trial in range(6):
        col_s, q_s = mk_pool(batch=K)
        col_r, q_r = mk_pool(batch=1)
        times = [10.0 * (k + 1) for k in range(K)]
        waves = [(int(rng.integers(0, 20)), int(rng.integers(1, 4)),
                  ["alice", "bob"][int(rng.integers(0, 2))])
                 for _ in times]
        claims_s = 0
        for t, (n, c, u) in zip(times, waves):
            submit_wave(q_s, t - 1, n, cpus=c, user=u)
            claims_s += col_s.stage_cycle(q_s, t)
        claims_s += col_s.quiesce()
        claims_r = 0
        for t, (n, c, u) in zip(times, waves):
            submit_wave(q_r, t - 1, n, cpus=c, user=u)
            claims_r += col_r.run_cycle(q_r, t)
        assert claims_s == claims_r, f"K={K} trial={trial}"
        assert full_claim_map(q_s) == full_claim_map(q_r), \
            f"K={K} trial={trial}"


def test_staged_batch_takes_fused_path_on_disjoint_waves():
    """Waves of fresh cohort shapes never re-seed a drained cohort, so
    the batch must go through the fused jit (not the fallback) and
    still match the sequential reference exactly."""
    K = 4
    col_s, q_s = mk_pool(batch=K, n_workers=4, cpus=4)
    col_r, q_r = mk_pool(batch=1, n_workers=4, cpus=4)
    times = [10.0 * (k + 1) for k in range(K)]
    for q, col, stage in ((q_s, col_s, True), (q_r, col_r, False)):
        for k, t in enumerate(times):
            submit_wave(q, t - 1, 8, cpus=2, mem=4 + 8 * k)  # new shape/wave
            if stage:
                col.stage_cycle(q, t)
            else:
                col.run_cycle(q, t)
    col_s.quiesce()
    assert col_s.fused_batches == 1 and col_s.staged_fallbacks == 0
    assert col_s.fused_cycles == K
    assert full_claim_map(q_s) == full_claim_map(q_r)


def test_mid_batch_quiesce_flushes_and_matches():
    """An external op mid-batch (snapshot, reconfig, ...) quiesces a
    half-full staging buffer; the partial flush plus the follow-on
    cycles still replay the sequential reference bit-for-bit."""
    K = 8
    col_s, q_s = mk_pool(batch=K, n_workers=4, cpus=4)
    col_r, q_r = mk_pool(batch=1, n_workers=4, cpus=4)
    times = [10.0 * (k + 1) for k in range(5)]
    for k, t in enumerate(times[:3]):
        submit_wave(q_s, t - 1, 5, cpus=2, mem=4 + 8 * k)
        col_s.stage_cycle(q_s, t)
    col_s.quiesce()                      # external op: flush 3 of 8
    assert not col_s._staged_times
    for k, t in enumerate(times[3:], start=3):
        submit_wave(q_s, t - 1, 5, cpus=2, mem=4 + 8 * k)
        col_s.stage_cycle(q_s, t)
    col_s.quiesce()
    for k, t in enumerate(times):
        submit_wave(q_r, t - 1, 5, cpus=2, mem=4 + 8 * k)
        col_r.run_cycle(q_r, t)
    assert full_claim_map(q_s) == full_claim_map(q_r)


def test_worker_churn_mid_batch_forces_fallback():
    """A worker booting between staged cycles changes the pool
    fingerprint — the batch must replay sequentially (the fused problem
    would give the newcomer to cycles that predate it) and match the
    reference, which sees the worker only from its boot time."""
    col_s, q_s = mk_pool(batch=4, n_workers=2, cpus=4)
    col_r, q_r = mk_pool(batch=1, n_workers=2, cpus=4)
    times = [10.0, 20.0, 30.0, 40.0]

    def boot_extra(col):
        w = Worker(name="late", ad={"cpus": 4, "memory": 64},
                   start_expr=ClassAdExpr("True"))
        w.booted_at = 15.0
        col.advertise(w)

    for k, t in enumerate(times):
        submit_wave(q_s, t - 1, 6, cpus=2, mem=4 + 8 * k)
        col_s.stage_cycle(q_s, t)
        if t == 10.0:
            boot_extra(col_s)
    col_s.quiesce()
    for k, t in enumerate(times):
        submit_wave(q_r, t - 1, 6, cpus=2, mem=4 + 8 * k)
        col_r.run_cycle(q_r, t)
        if t == 10.0:
            boot_extra(col_r)
    assert col_s.staged_fallbacks == 1 and col_s.fused_batches == 0
    assert full_claim_map(q_s) == full_claim_map(q_r)


def test_reseed_hazard_forces_fallback():
    """A cohort that fully drains mid-batch and then receives new
    arrivals would re-seed its FIFO sort key in the sequential path —
    the guard must detect it from the fused plans and replay
    sequentially, exactly."""
    col_s, q_s = mk_pool(batch=3, n_workers=10, cpus=8)
    col_r, q_r = mk_pool(batch=1, n_workers=10, cpus=8)
    times = [10.0, 20.0, 30.0]
    waves = [(4, 3, "alice"), (1, 1, "bob"), (13, 3, "alice")]
    for (t, (n, c, u)) in zip(times, waves):
        submit_wave(q_s, t - 1, n, cpus=c, user=u)
        col_s.stage_cycle(q_s, t)
    col_s.quiesce()
    for (t, (n, c, u)) in zip(times, waves):
        submit_wave(q_r, t - 1, n, cpus=c, user=u)
        col_r.run_cycle(q_r, t)
    assert col_s.staged_fallbacks == 1
    assert full_claim_map(q_s) == full_claim_map(q_r)


def test_noop_memo_skips_unchanged_cycles():
    """Idle cycles with no queue or pool change hit the no-op memo; any
    idle-set or claim change invalidates it."""
    col, q = mk_pool(batch=1, matchmaker="numpy")
    submit_wave(q, 0.0, 80, cpus=2)      # exceeds the 10x8-cpu pool
    col.run_cycle(q, 1.0)                # claims 40, pool exhausts
    col.run_cycle(q, 2.0)                # claims 0 -> memo armed
    base = col.noop_hits
    col.run_cycle(q, 3.0)
    col.run_cycle(q, 4.0)
    assert col.noop_hits == base + 2
    submit_wave(q, 4.5, 1, cpus=2)       # idle set changed -> memo stale
    col.run_cycle(q, 5.0)
    assert col.noop_hits == base + 2


# -- simulation: negotiation_batch=K engines match batch=1 -------------------

def test_simulation_batch_knob_preserves_claim_map():
    from repro.core import (
        ProvisionerConfig, Simulation, gpu_job, onprem_nodes,
    )

    def drive(batch):
        cfg = ProvisionerConfig(submit_interval_s=30, idle_timeout_s=120,
                                startup_delay_s=30, matchmaker="jax",
                                negotiation_batch=batch)
        sim = Simulation(cfg, nodes=onprem_nodes(4, gpus=8), tick_s=5)
        sim.submit_jobs(0, [gpu_job(300) for _ in range(12)])
        sim.run(3000)
        return sim, full_claim_map(sim.queue)

    sim1, cm1 = drive(1)
    sim4, cm4 = drive(4)
    assert sim1.queue.drained() and sim4.queue.drained()
    assert cm1 == cm4


# -- config plumbing ---------------------------------------------------------

def test_negotiation_batch_ini_roundtrip():
    cfg = load_ini("[provision]\nnegotiation_batch=8\n")
    assert cfg.negotiation_batch == 8
    assert "negotiation_batch=8" in dump_ini(cfg)
    cfg2 = load_ini(dump_ini(cfg))
    assert cfg2.negotiation_batch == 8


def test_negotiation_batch_default_is_one():
    cfg = load_ini("[provision]\n")
    assert cfg.negotiation_batch == 1
