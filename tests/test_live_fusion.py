"""Backlog-driven live fusion (ISSUE 10 tentpole part 2).

Before this change the event engine quiesced every staged cycle in the
same instant, so `negotiation_batch=K` degenerated to K=1 flushes in
live mode — `repro_fused_fallbacks_total{reason="single_cycle"}`
was 100% of flushes.  Now `_negotiate_cb` defers the flush across
provably-unobservable windows (no event, no completion, no idle-timeout
expiry before the next firing), so backlogs of 2+ cycles reach the
fused multi-cycle jit.

Pinned here:
  * engagement — a fusion-friendly cadence (negotiate 20s inside a 60s
    tick/reconcile grid) on a saturated pool accumulates real fused
    batches, and single-cycle fallbacks drop below 100% of flushes;
  * safety — deferral parks worker advancement; the flush replays it
    segment-by-segment at the staged timestamps, so claim maps, the
    recorder's Fig 2-3 gauge series, and completion logs stay
    bit-identical to `negotiation_batch=1` across K in {1,2,8}, on a
    streaming diurnal trace replay (numpy and jax backends);
  * boundaries — `run()` returns quiescent (no staged residue for
    observers), and a completion landing inside a candidate window
    vetoes deferral (the claim that would go stale is negotiated on
    time).
"""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.core import ProvisionerConfig, Simulation, gpu_job, onprem_nodes
from repro.workload.generators import diurnal_day
from repro.workload.replay import replay_trace


def fusion_sim(batch, *, matchmaker="numpy", nodes=2):
    """negotiate every 20s inside a 60s tick/reconcile/metrics grid:
    the [20,40] windows carry no events, so deferral can engage there;
    every grid instant (reconcile, straggler, metrics) vetoes."""
    cfg = ProvisionerConfig(submit_interval_s=60, idle_timeout_s=900,
                            startup_delay_s=30, matchmaker=matchmaker,
                            negotiation_batch=batch)
    return Simulation(cfg, nodes=onprem_nodes(nodes, gpus=8), tick_s=60,
                      negotiate_interval_s=20, metrics_interval_s=60)


def fallback_counts(sim):
    fam = sim.collector._c_fallbacks
    return {k[0]: int(c.value) for k, c in fam.children.items()}


def claim_map(q):
    return sorted((j.jid, j.claimed_by, j.attempt_started_at)
                  for j in q.jobs() if j.claimed_by is not None)


def completion_signature(sim):
    return sorted((j.jid, j.submitted_at, j.runtime_s, j.completed_at)
                  for j in sim.queue.completed_log)


# -- engagement ---------------------------------------------------------------

def test_live_fusion_engages_on_saturated_pool():
    sim = fusion_sim(batch=4)
    # runtimes far beyond the horizon: no completion ever vetoes
    sim.submit_jobs(0, [gpu_job(50000.0) for _ in range(40)])
    sim.run(600)
    col = sim.collector
    assert col.fused_batches > 0, fallback_counts(sim)
    flushes = col.fused_batches + col.staged_fallbacks
    single = fallback_counts(sim).get("single_cycle", 0)
    # the pre-deferral live engine was 100% single_cycle
    assert single < flushes
    # run() hands back a quiescent simulation
    assert not col._staged_times


def test_deferral_respects_completions():
    """A claim completing inside a candidate window must veto deferral:
    the freed capacity is negotiated at the very next firing, exactly
    as in batch=1, and the completion time itself stays exact."""
    def drive(batch):
        sim = fusion_sim(batch=batch)
        # completes at boot+startup+runtime, deliberately off-grid and
        # inside a [20,40] deferral window
        sim.submit_jobs(0, [gpu_job(93.0)] + [gpu_job(50000.0)
                                              for _ in range(20)])
        sim.run(900)
        return completion_signature(sim), claim_map(sim.queue)

    sig1, cm1 = drive(1)
    sig8, cm8 = drive(8)
    assert sig1 and sig1 == sig8
    assert cm1 == cm8


# -- differential: streaming diurnal replay across K --------------------------

def _replay(batch, matchmaker):
    trace = diurnal_day(150, seed=3, duration_s=3600.0)
    cfg = ProvisionerConfig(submit_interval_s=60, idle_timeout_s=300,
                            startup_delay_s=30, matchmaker=matchmaker,
                            negotiation_batch=batch)
    sim = Simulation(cfg, nodes=onprem_nodes(2, gpus=8), tick_s=60,
                     negotiate_interval_s=20, metrics_interval_s=60)
    replay_trace(sim, trace, coalesce_s=0.0)
    sim.run_until_drained(max_t=1e6)
    return sim


@pytest.mark.parametrize("matchmaker", ["numpy", "jax"])
def test_diurnal_replay_bit_identical_across_batch(matchmaker):
    ref = _replay(1, matchmaker)
    ref_sig = completion_signature(ref)
    ref_series = ref.recorder.series
    assert ref_sig, "trace must complete jobs"
    for K in (2, 8):
        sim = _replay(K, matchmaker)
        assert completion_signature(sim) == ref_sig, f"K={K}"
        # Fig 2-3 gauge series: same sample instants, same values
        assert sim.recorder.series == ref_series, f"K={K}"
        assert not sim.collector._staged_times


def test_diurnal_replay_live_fusion_fraction():
    """On the streaming trace the quiet windows must actually fuse —
    single-cycle fallbacks are no longer 100% of flushes."""
    sim = _replay(8, "numpy")
    col = sim.collector
    assert col.fused_batches > 0, fallback_counts(sim)
    flushes = col.fused_batches + col.staged_fallbacks
    assert fallback_counts(sim).get("single_cycle", 0) < flushes
