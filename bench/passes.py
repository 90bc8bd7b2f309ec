"""Plain reference of whole negotiation passes, rebuilt from the seeded
trace and the job events the probe logged; it imports nothing of the
program and reads none of its problems.

What a deployment states of one plain pass, and what this follows:

  * jobs: record ``i`` of the trace (in arrival order) is job ``i``;
    its ad is its requests (``request_cpus``, ``request_gpus``,
    ``request_memory``, ``request_disk``), ``accounting_group``,
    ``user`` and its attrs;
  * idle cohorts: the idle jobs with the same ad and Requirements;
  * order: cohorts by the earliest (submit time, job) that the cohort
    has held idle since it was last empty, earliest first;
  * workers: the live workers in the order given, each with its ad's
    quantities less the requests of the jobs running on it;
  * matching: a cohort goes on a worker where its Requirements hold
    with the job ad as MY and the worker's offer as TARGET, and the
    worker's START holds the other way round; an expression that reads
    an offered quantity is evaluated again before every claim;
  * claims: each cohort in order takes from the workers in index order
    as many of its jobs as fit (the floor of the smallest free/request
    ratio over what it requests), up to its idle count, and its jobs go
    out oldest first (submit time, then job).

Expressions use the pool's syntax (Python's), with names looked up in
MY and then in TARGET, letter case ignored; a missing name compares
false, and an expression that cannot be evaluated is false.
"""
from __future__ import annotations

import ast
import heapq
import operator

import numpy as np

from bench.probe import CLAIM, DONE, IDLE_IN, IDLE_OUT
from bench.reference import fits_row

#: offered quantities, in the order of a request vector
QUANTITIES = ("cpus", "gpus", "memory", "disk", "chips", "hbm_gb")
_MISSING = object()
_CMP = {ast.Eq: operator.eq, ast.NotEq: operator.ne, ast.Lt: operator.lt,
        ast.LtE: operator.le, ast.Gt: operator.gt, ast.GtE: operator.ge}


class Expr:
    """One Requirements or START expression."""

    def __init__(self, src: str):
        self.src = (src or "").strip()
        self.tree = (None if self.src.lower() in ("", "true")
                     else ast.parse(self.src, mode="eval").body)
        self.names = set() if self.tree is None else {
            (n.attr if isinstance(n, ast.Attribute) else n.id).lower()
            for n in ast.walk(self.tree)
            if isinstance(n, (ast.Name, ast.Attribute))} - {"my", "target"}
        self.reads_quantity = bool(self.names & set(QUANTITIES))

    def __call__(self, my: dict, target: dict) -> bool:
        if self.tree is None:
            return True
        try:
            return bool(self._eval(self.tree, my, target))
        except TypeError:
            return False

    def _name(self, name, my, target):
        name = name.lower()
        if name in ("true", "false"):
            return name == "true"
        for ad in (my, target):
            if name in ad:
                return ad[name]
        return _MISSING

    def _eval(self, node, my, target):
        if isinstance(node, ast.Constant):
            return node.value
        if isinstance(node, ast.Name):
            return self._name(node.id, my, target)
        if isinstance(node, ast.Attribute):
            scope = node.value.id.lower()
            ad = my if scope == "my" else target if scope == "target" else None
            if ad is None:
                raise ValueError(f"unknown scope in {self.src!r}")
            return ad.get(node.attr.lower(), _MISSING)
        if isinstance(node, ast.BoolOp):
            vals = (self._eval(v, my, target) for v in node.values)
            if isinstance(node.op, ast.And):
                return all(v is not _MISSING and v for v in vals)
            return any(v is not _MISSING and v for v in vals)
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.Not):
            v = self._eval(node.operand, my, target)
            return not (v is not _MISSING and v)
        if isinstance(node, ast.Compare):
            left = self._eval(node.left, my, target)
            for op, right_node in zip(node.ops, node.comparators):
                right = self._eval(right_node, my, target)
                if left is _MISSING or right is _MISSING:
                    return False
                if not _CMP[type(op)](left, right):
                    return False
                left = right
            return True
        raise ValueError(f"no plain evaluation of {ast.dump(node)} in "
                         f"{self.src!r}")


def _lower(ad: dict) -> dict:
    return {str(k).lower(): v for k, v in ad.items()}


def job_ad(rec: dict) -> dict:
    ad = {"request_cpus": rec["cpus"], "request_gpus": rec["gpus"],
          "request_memory": rec["memory_gb"], "request_disk": rec["disk_gb"],
          "accounting_group": rec["group"], "user": rec["user"]}
    ad.update(rec["attrs"])
    return ad


def _request(rec: dict) -> np.ndarray:
    return np.array([rec["cpus"], rec["gpus"], rec["memory_gb"],
                     rec["disk_gb"], 0, 0], dtype=np.float64)


def _cohort(rec: dict) -> tuple:
    return ((rec["requirements"] or "").strip(),
            tuple(sorted((k, repr(v)) for k, v in job_ad(rec).items())))


class Replay:
    """Walks the job events in order, keeping what the reference needs
    at any point: the idle jobs of each cohort, each cohort's ordering
    key, and the jobs running on each worker."""

    def __init__(self, records: list[dict]):
        self.records = records
        self.keys = [None] * len(records)
        self.idle: dict[tuple, set] = {}
        self.first: dict[tuple, tuple] = {}
        self.running: dict[int, str] = {}
        self.on: dict[str, set] = {}
        self.pos = 0

    def key(self, jid: int) -> tuple:
        k = self.keys[jid]
        if k is None:
            k = self.keys[jid] = _cohort(self.records[jid])
        return k

    def advance(self, log: list, pos: int):
        for ev in log[self.pos:pos]:
            kind, jid = ev[0], ev[1]
            if kind == IDLE_IN:
                self._stop_running(jid)
                k = self.key(jid)
                members = self.idle.setdefault(k, set())
                members.add(jid)
                mark = (self.records[jid]["arrival_s"], jid)
                if k not in self.first or mark < self.first[k]:
                    self.first[k] = mark
            elif kind == IDLE_OUT:
                k = self.key(jid)
                members = self.idle[k]
                members.discard(jid)
                if not members:
                    del self.idle[k]
                    del self.first[k]
            elif kind == CLAIM:
                self.running[jid] = ev[2]
                self.on.setdefault(ev[2], set()).add(jid)
            elif kind == DONE:
                self._stop_running(jid)
        self.pos = pos

    def _stop_running(self, jid: int):
        w = self.running.pop(jid, None)
        if w is not None:
            self.on[w].discard(jid)


def pass_claims(replay: Replay, workers: list[tuple]) -> dict[int, str]:
    """The claims {job: worker} of one plain pass over the replay's
    current state; `workers` are (name, ad, START source) in the pass's
    order."""
    recs = replay.records
    cohorts = sorted(replay.idle, key=replay.first.__getitem__)
    W = len(workers)
    cap = np.array([[float(ad.get(q, 0) or 0) for q in QUANTITIES]
                    for _n, ad, _s in workers]).reshape(W, len(QUANTITIES))
    free = cap.copy()
    for wi, (name, _ad, _s) in enumerate(workers):
        for jid in replay.on.get(name, ()):
            free[wi] -= _request(recs[jid])
    wads = [_lower(ad) for _n, ad, _s in workers]
    starts = {}
    for _n, _ad, src in workers:
        if src not in starts:
            starts[src] = Expr(src)
    start_of = [starts[src] for _n, _ad, src in workers]
    shape_of, shapes = [], {}
    for (_n, ad, src) in workers:
        shape_of.append(shapes.setdefault(
            (src, tuple(sorted((k, repr(v)) for k, v in ad.items()))),
            len(shapes)))
    shape_of = np.asarray(shape_of, dtype=np.int64)
    rep_w = {}
    for wi, s in enumerate(shape_of.tolist()):
        rep_w.setdefault(s, wi)
    exprs: dict[str, Expr] = {}

    def offer(wi):
        ad = dict(wads[wi])
        for q, v in zip(QUANTITIES, free[wi]):
            if q in ad:
                ad[q] = v
        return ad

    out: dict[int, str] = {}
    for key in cohorts:
        members = replay.idle[key]
        rec = recs[next(iter(members))]
        want = _request(rec)
        src = rec["requirements"] or ""
        req = exprs.get(src)
        if req is None:
            req = exprs[src] = Expr(src)
        jad = _lower(job_ad(rec))
        left = len(members)
        take = np.zeros(W, dtype=np.int64)
        live = req.reads_quantity or any(
            e.reads_quantity for e in starts.values())
        if live:
            for wi in range(W):
                while left > 0 and fits_row(free[wi:wi + 1], want,
                                            left)[0] >= 1:
                    o = offer(wi)
                    if not (req(jad, o) and start_of[wi](o, jad)):
                        break
                    take[wi] += 1
                    free[wi] -= want
                    left -= 1
                if left == 0:
                    break
        else:
            ok_shape = np.array([
                req(jad, wads[rep_w[s]]) and start_of[rep_w[s]](
                    wads[rep_w[s]], jad) for s in range(len(shapes))])
            fits = np.where(ok_shape[shape_of], fits_row(free, want, left), 0)
            before = np.cumsum(fits) - fits
            take = np.clip(left - before, 0, fits)
            free -= take[:, None] * want[None, :]
        total = int(take.sum())
        if total == 0:
            continue
        jobs = heapq.nsmallest(
            total, members, key=lambda j: (recs[j]["arrival_s"], j))
        ji = 0
        for wi in np.nonzero(take)[0]:
            for jid in jobs[ji:ji + int(take[wi])]:
                out[jid] = workers[wi][0]
            ji += int(take[wi])
    return out


def claims_differing(records: list[dict], log: list,
                     samples: list) -> list[int]:
    """For each sampled pass, the jobs whose claim (or absence of one)
    differs from the reference's."""
    replay = Replay(records)
    out = [0] * len(samples)
    for i in sorted(range(len(samples)), key=lambda i: samples[i]["pos"]):
        item = samples[i]
        replay.advance(log, item["pos"])
        want = pass_claims(replay, item["workers"])
        got = dict(item["claims"])
        out[i] = sum(want.get(j) != got.get(j) for j in set(want) | set(got))
    return out
