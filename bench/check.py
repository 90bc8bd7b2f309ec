"""Decides `correct`: the claims of sampled passes against a pass
rebuilt from the seeded trace (`bench.passes`), and the sampled device
calls against the plain water-fill (`bench.reference`).

Each number compared is a count of disagreements, so every limit is 0:
  claims_differing         over the sampled passes of every kind
                           (plain, flocking, fair-share), jobs whose
                           claim (worker, or none) differs from the
                           pass `bench.passes` rebuilds from the trace,
                           the job events and the deployment's
                           statement: cohorts, their order, demand,
                           requests, compatibility, free capacity and
                           the fair-share slices all come from there
  plan_cells_differing     takes and free-after cells of the sampled
                           ``match``/``match_cycles`` answers that
                           differ from `reference.waterfill` on the
                           same problem (the device layer's answer)
  preview_cells_differing  per-cohort absorbed counts of the sampled
                           previews that differ from the reference on
                           the same problem
  checked_passes_missing   1 when the window held no pass to check
"""
from __future__ import annotations

import numpy as np

from bench import passes, reference


def _plan_diff(takes, free_after, ref_takes, ref_free) -> int:
    takes = np.asarray(takes)
    if takes.shape != ref_takes.shape:
        return int(ref_takes.size)
    return (int(np.count_nonzero(takes != ref_takes))
            + int(np.count_nonzero(np.asarray(free_after) != ref_free)))


def compare(probe, records: list[dict],
            deployment: passes.Deployment | None = None
            ) -> tuple[dict, int, int]:
    """({name: (value, limit)} for every number compared, the number of
    sampled passes and calls whose answer disagreed, the number of
    sampled passes with a choice that tied to rounding).  `records` is
    the run's trace, drawn again from the seed, and `deployment` what
    the cell's configuration states of its passes."""
    plan = preview = bad = 0
    per_pass, ties = passes.claims_differing(
        records, probe.log, probe.pass_samples.items,
        record_of=probe.record_of, deployment=deployment)
    claims = sum(per_pass)
    bad += sum(c > 0 for c in per_pass)
    for item in probe.match_samples.items:
        p = item["problem"]
        ref_takes, ref_free = reference.waterfill(
            p.requests, item["demand"], p.order, p.compat, item["free"],
            budget=item["budget"], active=item["active"])
        d = _plan_diff(item["takes"], item["free_after"], ref_takes, ref_free)
        plan += d
        bad += d > 0
    for item in probe.cycle_samples.items:
        p = item["problem"]
        deltas = [(d.arrivals, d.free_add, d.budget) for d in item["deltas"]]
        refs = reference.waterfill_cycles(p.requests, p.demand, p.order,
                                          p.compat, p.free, deltas)
        d = abs(len(refs) - len(item["plans"])) * p.compat.size
        for (takes, free_after), (rt, rf) in zip(item["plans"], refs):
            d += _plan_diff(takes, free_after, rt, rf)
        plan += d
        bad += d > 0
    for item in probe.preview_samples.items:
        p = item["problem"]
        d = 0
        for i, free in enumerate(item["frees"]):
            demand = p.demand if item["demands"] is None \
                else item["demands"][i]
            rt, _ = reference.waterfill(p.requests, demand, p.order,
                                        p.compat, free)
            got = np.asarray(item["absorbed"][i])
            want = rt.sum(axis=1)
            d += (int(np.count_nonzero(got != want))
                  if got.shape == want.shape else len(want))
        preview += d
        bad += d > 0
    return {
        "claims_differing": (claims, 0),
        "plan_cells_differing": (plan, 0),
        "preview_cells_differing": (preview, 0),
        "checked_passes_missing": (int(not probe.pass_samples.items), 0),
    }, int(bad), ties


def sampled(probe, near_ties: int) -> dict:
    kinds: dict[str, int] = {}
    for item in probe.pass_samples.items:
        kinds[item["kind"]] = kinds.get(item["kind"], 0) + 1
    return {"passes": len(probe.pass_samples.items),
            "passes_seen": probe.pass_samples.seen,
            "pass_kinds": kinds, "near_tie_passes": near_ties,
            "match": len(probe.match_samples.items),
            "match_seen": probe.match_samples.seen,
            "match_cycles": len(probe.cycle_samples.items),
            "preview": len(probe.preview_samples.items),
            "preview_seen": probe.preview_samples.seen}
