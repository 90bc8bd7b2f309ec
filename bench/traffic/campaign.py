"""The campaign generator: OSG-shaped job traces from a parameter file.

Copied from the pool's own workload generator (uniform base arrivals
conditioned on their count, a sampled kind and Zipf user per job, a
share of jobs in single-user bursts, per-kind log-normal or Pareto
runtimes) so that the yardstick stays put when the program changes.  Every size
and arrival time is drawn from the file's ``structure_seed``; the run's
seed only reorders them, so a seed gives the same records, byte for
byte, and every seed brings the same work.

Parameters (a traffic file's keys):

  rate_per_s, horizon_s   base arrivals over [0, horizon_s); or n_jobs
                          with horizon_s 0 for a backlog at t=0
  burst_frac, bursts_per_day, burst_width_s
  n_users, zipf_s         Zipf-ish user popularity
  structure_seed          every size and arrival time (base arrivals,
                          kinds, users, burst layout, runtimes) is drawn
                          from this seed
  seed_reorders           what the run's seed reorders: "kind" (which
                          kind each base arrival gets), "user" (which
                          user each base arrival and each burst gets),
                          "runtime" (which runtime each job of a kind
                          gets)
  kinds                   [{name, weight, cpus, gpus, memory_gb,
                          disk_gb, requirements, attrs, runtime}], with
                          runtime {dist: lognormal, median_s, sigma,
                          min_s} or {dist: pareto, min_s, alpha, cap_s}
  communities             optional [{name, users}]: the users split
                          among schedds, ``users`` each, summing to
                          n_users; which users a community holds, and
                          which community each burst belongs to, are
                          drawn from structure_seed, and the seed then
                          reorders kinds, users and runtimes only within
                          a community, so every seed gives each schedd
                          the same work

Records are dicts in the pool's trace-record form; with communities
each also names its schedd (``schedd``), and without them the records
are those the generator gave before communities existed, byte for byte.
"""
from __future__ import annotations

import numpy as np

DAY_S = 86400.0


def n_jobs(params: dict) -> int:
    if "n_jobs" in params:
        return int(params["n_jobs"])
    return int(round(params["rate_per_s"] * params["horizon_s"]))


def _arrivals(rng, n: int, horizon_s: float) -> np.ndarray:
    if n <= 0:
        return np.empty(0)
    return np.sort(rng.random(n)) * horizon_s


def _kinds(rng, kinds: list, n: int) -> np.ndarray:
    w = np.asarray([max(float(k["weight"]), 0.0) for k in kinds])
    return rng.choice(len(kinds), size=n, p=w / w.sum())


def _users(rng, n: int, n_users: int, s: float) -> np.ndarray:
    p = np.arange(1, n_users + 1, dtype=np.float64) ** (-s)
    return rng.choice(n_users, size=n, p=p / p.sum())


def _runtimes(rng, runtime: dict, n: int) -> np.ndarray:
    if runtime["dist"] == "lognormal":
        return np.maximum(runtime["min_s"], runtime["median_s"] * np.exp(
            runtime["sigma"] * rng.standard_normal(n)))
    if runtime["dist"] == "pareto":
        out = runtime["min_s"] * (1.0 + rng.pareto(runtime["alpha"], size=n))
        return np.minimum(out, runtime["cap_s"])
    raise ValueError(f"unknown runtime dist {runtime['dist']!r}")


def _communities(params: dict, n_users: int):
    """(community of each user, the users of each community) or None.
    Drawn from their own stream of ``structure_seed``, so that the other
    draws stay those of the same mix without communities."""
    comms = params.get("communities")
    if not comms:
        return None
    sizes = [int(c["users"]) for c in comms]
    if min(sizes) < 1 or sum(sizes) != n_users:
        raise ValueError(f"communities hold {sizes} users; they must "
                         f"partition all {n_users}, each at least one")
    perm = np.random.default_rng([params["structure_seed"], 1]).permutation(
        n_users)
    members = np.split(perm, np.cumsum(sizes)[:-1])
    of = np.empty(n_users, dtype=np.int64)
    for ci, users in enumerate(members):
        of[users] = ci
    return of, members


def _reorder(rng, values: np.ndarray, groups) -> np.ndarray:
    """`values` permuted by the run's seed, within each group where
    `groups` (one label per value) is given."""
    if groups is None:
        return rng.permutation(values)
    out = values.copy()
    for g in np.unique(groups):
        idx = np.nonzero(groups == g)[0]
        out[idx] = rng.permutation(values[idx])
    return out


def generate(params: dict, seed: int) -> list[dict]:
    rng = np.random.default_rng(seed)
    src = np.random.default_rng(params["structure_seed"])
    kinds = params["kinds"]
    horizon = float(params["horizon_s"])
    n_users = int(params["n_users"])
    total = n_jobs(params)
    n_bursts = int(round(params["bursts_per_day"] * horizon / DAY_S))
    n_burst_total = int(total * params["burst_frac"]) if n_bursts > 0 else 0
    n_base = total - n_burst_total
    width = len(str(n_users - 1))
    comms = _communities(params, n_users)
    # a burst's community, when there are communities to belong to
    csrc = np.random.default_rng([params["structure_seed"], 2])

    rows: list[tuple[float, int, int]] = []   # (arrival, kind, user)
    base_t = _arrivals(src, n_base, horizon)
    base_kind = _kinds(src, kinds, n_base)
    base_user = _users(src, n_base, n_users, params["zipf_s"])
    reorder = set(params.get("seed_reorders", ()))
    base_comm = None if comms is None else comms[0][base_user]
    if "kind" in reorder:
        base_kind = _reorder(rng, base_kind, base_comm)
    if "user" in reorder:
        base_user = _reorder(rng, base_user, base_comm)
    rows.extend(zip(base_t.tolist(), base_kind.tolist(), base_user.tolist()))
    if n_burst_total > 0:
        sizes = src.multinomial(n_burst_total,
                                np.full(n_bursts, 1.0 / n_bursts))
        centers = _arrivals(src, n_bursts, horizon)
        for size, center in zip(sizes, centers):
            if size <= 0:
                continue
            kind = int(_kinds(src, kinds, 1)[0])
            if comms is None or "user" not in reorder:
                user = int((rng if "user" in reorder else src)
                           .integers(0, n_users))
            else:
                users = comms[1][comms[0][csrc.integers(0, n_users)]]
                user = int(users[rng.integers(0, len(users))])
            ts = np.clip(center + params["burst_width_s"]
                         * src.standard_normal(size),
                         0.0, max(horizon - 1e-3, 0.0))
            rows.extend((t, kind, user) for t in ts.tolist())
    rows.sort(key=lambda r: r[0])

    order_kinds = np.asarray([r[1] for r in rows], dtype=np.int64)
    row_comm = None if comms is None else comms[0][
        np.asarray([r[2] for r in rows], dtype=np.int64)]
    runtimes = np.empty(len(rows))
    for ki, kind in enumerate(kinds):
        idx = np.nonzero(order_kinds == ki)[0]
        if row_comm is not None:
            # each community's jobs of a kind take their own run of draws
            idx = idx[np.argsort(row_comm[idx], kind="stable")]
        if len(idx):
            draws = _runtimes(src, kind["runtime"], len(idx))
            if "runtime" in reorder:
                draws = _reorder(rng, draws, None if row_comm is None
                                 else row_comm[idx])
            runtimes[idx] = draws

    out = []
    for (t, ki, u), rt in zip(rows, runtimes.tolist()):
        k = kinds[ki]
        rec = {
            "arrival_s": round(float(t), 3),
            "runtime_s": round(float(rt), 3),
            "cpus": int(k["cpus"]),
            "gpus": int(k["gpus"]),
            "memory_gb": float(k["memory_gb"]),
            "disk_gb": float(k["disk_gb"]),
            "requirements": k["requirements"],
            "group": k["name"],
            "user": f"user{u:0{width}d}",
            "attrs": dict(sorted(k["attrs"].items())),
        }
        if comms is not None:
            rec["schedd"] = params["communities"][comms[0][u]]["name"]
        out.append(rec)
    return out
