"""Plain reference of whole negotiation passes, rebuilt from the seeded
trace and the job events the probe logged; it imports nothing of the
program and reads none of its problems.

What a deployment states of one pass, and what this follows:

  * jobs: the probe maps each job to the trace record it was submitted
    from; its ad is the record's requests (``request_cpus``,
    ``request_gpus``, ``request_memory``, ``request_disk``),
    ``accounting_group``, ``user`` and its attrs, and its schedd is the
    record's ``schedd`` (the first schedd where it names none);
  * idle cohorts: the idle jobs of one schedd with the same ad and
    Requirements;
  * order (plain and flocking passes): schedds in flocking order (the
    deployment's ``service.schedds``); within a schedd, cohorts by the
    earliest (submit time, job) that the cohort has held idle since it
    was last empty, earliest first;
  * workers: the live workers in the order given, each with its ad's
    quantities less the requests of the jobs running on it, one free
    matrix shared by every schedd;
  * matching: a cohort goes on a worker where its Requirements hold
    with the job ad as MY and the worker's offer as TARGET, and the
    worker's START holds the other way round; an expression that reads
    an offered quantity is evaluated again before every claim;
  * claims: each cohort in order takes from the workers in index order
    as many of its jobs as fit (the floor of the smallest free/request
    ratio over what it requests), up to its idle count, and its jobs go
    out oldest first (submit time, then job);
  * fair-share passes (the deployment's ``fairshare`` block states the
    half-life, base priority, quantum, quotas and priority factors):
    the (schedd, user) entries with idle jobs are served in slices
    until none is left.  A slice goes to the schedd with the smallest
    decayed group cores / quota, and there to the user with the
    smallest factor x (base + decayed cores + the virtual cores charged
    to it this pass); ties go to the schedd's flocking index, then the
    user's name.  The slice serves that user's cohorts of that schedd
    in the order above, at most ``quantum`` claims in all; claims
    charge their cores, max(1, cpus) + gpus each, to the schedd and the
    user as virtual cores, and a slice that claims fewer than
    ``quantum`` retires its entry;
  * decayed usage: each claimed job adds its cores to its user's and
    its schedd's rate from its claim to its completion or release, and
    ``du/dt = rate - (ln 2 / half_life) u`` from the first submission
    on; in cores, ``u ln 2 / half_life``, which in closed form is the
    sum over runs of cores x (2^-(t - end)/hl - 2^-(t - start)/hl).

Keys that agree to `REL_TOL` are equal to rounding: the program settles
each ledger in floating point at every observation, so two submitters
with the same history (each claimed the same cores at the same pass,
both still running) can read a last-digit apart, either way round.
Where such a tie holds a decayed usage, the reference takes the first
candidate of the stated order whose slice agrees with the claims the
pass made, and counts the pass (`near_ties`); keys further apart than
that are served in the stated order, whatever the pass did.

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


def job_cores(rec: dict) -> float:
    """The cores a job is charged at: max(1, cpus) + gpus."""
    return max(1.0, float(rec["cpus"])) + float(rec["gpus"])


def _request(rec: dict) -> np.ndarray:
    return np.array([rec["cpus"], rec["gpus"], rec["memory_gb"],
                     rec["disk_gb"], 0, 0], dtype=np.float64)


def _cohort(rec: dict) -> tuple:
    return ((rec["requirements"] or "").strip(),
            tuple(sorted((k, repr(v)) for k, v in job_ad(rec).items())))


class FairShare:
    """The fair-share terms a deployment states (its ``fairshare``
    block); every key is required."""

    def __init__(self, block: dict, schedds: list[str]):
        self.half_life_s = float(block["half_life_s"])
        self.base_priority = float(block["base_priority"])
        self.quantum = int(block["quantum"])
        self.default_factor = float(block["default_factor"])
        self.factors = {str(u): float(f)
                        for u, f in block["factors"].items()}
        self.quotas = [float(block["quotas"][name]) for name in schedds]
        if not (self.half_life_s > 0 and self.quantum >= 1
                and min(self.quotas) > 0):
            raise ValueError(f"fair-share terms out of range: {block}")

    def factor(self, user: str) -> float:
        return self.factors.get(user, self.default_factor)


class Deployment:
    """What a deployment's configuration states of its passes: its
    schedds in flocking order (``service.schedds``; one unnamed schedd
    where it names none) and its fair-share terms, or None."""

    def __init__(self, config: dict | None = None):
        service = (config or {}).get("service", {})
        schedds = service.get("schedds")
        if schedds is not None and (isinstance(schedds, (int, str))
                                    or not schedds):
            raise ValueError(f"service.schedds must name the schedds in "
                             f"flocking order, not {schedds!r}")
        self.schedds = list(schedds) if schedds else [None]
        self.fairshare = None
        if service.get("fairshare"):
            if schedds is None or "fairshare" not in config:
                raise ValueError(
                    "a fair-share deployment names its schedds and states "
                    "its terms in a `fairshare` block")
            self.fairshare = FairShare(config["fairshare"], self.schedds)


class Replay:
    """Walks the job events in order, keeping what the reference needs
    at any point: the idle jobs of each cohort, each cohort's ordering
    key, the jobs running on each worker, and every run of a claimed job
    (its start, its end once it completed or was released, its cores,
    user and schedd) for the decayed usage."""

    def __init__(self, records: list[dict], record_of: dict | None = None,
                 deployment: Deployment | None = None):
        self.records = records
        self.record_of = record_of
        self.dep = deployment or Deployment()
        self.schedd_index = {n: i for i, n in enumerate(self.dep.schedds)}
        self.keys: dict[int, tuple] = {}
        self.idle: dict[tuple, set] = {}
        self.first: dict[tuple, tuple] = {}
        self.running: dict[int, str] = {}
        self.on: dict[str, set] = {}
        self.users: dict[str, int] = {}
        self.run_of: dict[int, int] = {}
        # runs, as parallel columns: start, end (nan while running),
        # cores, user index, schedd index
        self.runs = ([], [], [], [], [])
        self.pos = 0

    def record(self, jid: int) -> dict:
        if self.record_of is None:
            return self.records[jid]
        i = self.record_of.get(jid)
        if i is None:
            raise ValueError(f"job {jid} entered a queue with no trace "
                             f"record to match it")
        return self.records[i]

    def key(self, jid: int) -> tuple:
        """(schedd index, cohort) of a job."""
        k = self.keys.get(jid)
        if k is None:
            rec = self.record(jid)
            k = self.keys[jid] = (
                self.schedd_index[rec.get("schedd") or self.dep.schedds[0]],
                _cohort(rec))
        return k

    def cohorts(self) -> list[tuple]:
        """The idle cohorts: schedds in flocking order, each schedd's by
        their first mark."""
        return sorted(self.idle, key=lambda k: (k[0], self.first[k]))

    def fifo(self, jid: int) -> tuple:
        return (self.record(jid)["arrival_s"], jid)

    def advance(self, log: list, pos: int):
        for ev in log[self.pos:pos]:
            kind, jid = ev[0], ev[1]
            if kind == IDLE_IN:
                self._stop_running(jid, ev[2])
                k = self.key(jid)
                members = self.idle.setdefault(k, set())
                members.add(jid)
                mark = self.fifo(jid)
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
                rec = self.record(jid)
                self.run_of[jid] = len(self.runs[0])
                for col, v in zip(self.runs, (
                        ev[3], np.nan, job_cores(rec),
                        self.users.setdefault(rec["user"], len(self.users)),
                        self.key(jid)[0])):
                    col.append(v)
            elif kind == DONE:
                self._stop_running(jid, ev[2])
        self.pos = pos

    def _stop_running(self, jid: int, t):
        w = self.running.pop(jid, None)
        if w is not None:
            self.on[w].discard(jid)
            self.runs[1][self.run_of.pop(jid)] = t

    def usage(self, now: float, half_life_s: float):
        """Decayed cores at `now`, ({user: cores}, {schedd index: cores}),
        from the closed form over every run so far."""
        if not self.runs[0]:
            return {}, {}
        start, end, cores, uid, sid = (np.asarray(c) for c in self.runs)
        end = np.where(np.isnan(end), now, np.minimum(end, now))
        w = cores * (np.exp2(-(now - end) / half_life_s)
                     - np.exp2(-(now - start) / half_life_s))
        by_user = np.bincount(uid, weights=w, minlength=len(self.users))
        by_schedd = np.bincount(sid, weights=w)
        return ({u: float(by_user[i]) for u, i in self.users.items()},
                dict(enumerate(by_schedd.tolist())))


class Pool:
    """One pass's workers: their offers and the capacity left free."""

    def __init__(self, replay: Replay, workers: list[tuple]):
        self.workers = workers
        W = len(workers)
        cap = np.array([[float(ad.get(q, 0) or 0) for q in QUANTITIES]
                        for _n, ad, _s in workers]).reshape(W, len(QUANTITIES))
        self.free = cap.copy()
        for wi, (name, _ad, _s) in enumerate(workers):
            for jid in replay.on.get(name, ()):
                self.free[wi] -= _request(replay.record(jid))
        self.wads = [_lower(ad) for _n, ad, _s in workers]
        self.starts: dict[str, Expr] = {}
        for _n, _ad, src in workers:
            if src not in self.starts:
                self.starts[src] = Expr(src)
        self.start_of = [self.starts[src] for _n, _ad, src in workers]
        shapes: dict = {}
        shape_of = [shapes.setdefault(
            (src, tuple(sorted((k, repr(v)) for k, v in ad.items()))),
            len(shapes)) for _n, ad, src in workers]
        self.n_shapes = len(shapes)
        self.shape_of = np.asarray(shape_of, dtype=np.int64)
        self.rep_w: dict[int, int] = {}
        for wi, s in enumerate(shape_of):
            self.rep_w.setdefault(s, wi)
        self.starts_read = any(e.reads_quantity for e in self.starts.values())
        self.exprs: dict[str, Expr] = {}
        self.compat: dict[tuple, np.ndarray] = {}

    def _offer(self, wi: int) -> dict:
        ad = dict(self.wads[wi])
        for q, v in zip(QUANTITIES, self.free[wi]):
            if q in ad:
                ad[q] = v
        return ad

    def fill(self, rec: dict, left: int) -> np.ndarray:
        """A cohort's first fit: how many of up to `left` jobs like
        `rec` each worker takes; the free capacity shrinks by them."""
        W = len(self.workers)
        want = _request(rec)
        src = rec["requirements"] or ""
        req = self.exprs.get(src)
        if req is None:
            req = self.exprs[src] = Expr(src)
        jad = _lower(job_ad(rec))
        take = np.zeros(W, dtype=np.int64)
        if req.reads_quantity or self.starts_read:
            for wi in range(W):
                while left > 0 and fits_row(self.free[wi:wi + 1], want,
                                            left)[0] >= 1:
                    o = self._offer(wi)
                    if not (req(jad, o) and self.start_of[wi](o, jad)):
                        break
                    take[wi] += 1
                    self.free[wi] -= want
                    left -= 1
                if left == 0:
                    break
            return take
        key = _cohort(rec)
        ok = self.compat.get(key)
        if ok is None:
            reps = [self.rep_w[s] for s in range(self.n_shapes)]
            ok = self.compat[key] = np.array(
                [req(jad, self.wads[w]) and self.start_of[w](self.wads[w], jad)
                 for w in reps], dtype=bool)[self.shape_of]
        fits = np.where(ok, fits_row(self.free, want, left), 0)
        before = np.cumsum(fits) - fits
        take = np.clip(left - before, 0, fits)
        self.free -= take[:, None] * want[None, :]
        return take

    def deal(self, jobs: list[int], take: np.ndarray) -> list[tuple]:
        """(job, worker) for `jobs`, oldest first, dealt to the workers
        in index order by `take`."""
        out, ji = [], 0
        for wi in np.nonzero(take)[0]:
            for jid in jobs[ji:ji + int(take[wi])]:
                out.append((jid, self.workers[wi][0]))
            ji += int(take[wi])
        return out


def pass_claims(replay: Replay, workers: list[tuple]) -> dict[int, str]:
    """The claims {job: worker} of one plain or flocking pass over the
    replay's current state; `workers` are (name, ad, START source) in
    the pass's order."""
    pool = Pool(replay, workers)
    out: dict[int, str] = {}
    for key in replay.cohorts():
        members = replay.idle[key]
        take = pool.fill(replay.record(next(iter(members))), len(members))
        total = int(take.sum())
        if total:
            out.update(pool.deal(
                heapq.nsmallest(total, members, key=replay.fifo), take))
    return out


#: keys this close, relative, are equal to rounding (module docstring);
#: ABS_TOL (cores) stands in where the smallest key is 0
REL_TOL, ABS_TOL = 1e-9, 1e-12


def _best(cands: list, key) -> tuple[list, bool]:
    """The candidates whose (key, decayed) is smallest to rounding, in
    the stated order (key, then `cands`' own order), and whether they
    tie to rounding: more than one, and a decayed usage among them (keys
    of base and virtual cores alone are exact, so equal ones tie
    exactly and go by the stated order)."""
    keys = [key(c) for c in cands]
    low = min(k for k, _d in keys)
    near = sorted((k, i) for i, (k, _d) in enumerate(keys)
                  if k <= low + REL_TOL * abs(low) + ABS_TOL)
    return ([cands[i] for _k, i in near],
            len(near) > 1 and any(keys[i][1] for _k, i in near))


def fairshare_claims(replay: Replay, workers: list[tuple], now: float,
                     made: dict | None = None) -> tuple[dict, bool]:
    """The claims {job: worker} of one fair-share pass, and whether a
    choice in it tied to rounding; `made` (the claims the pass made) is
    read only to settle such a tie."""
    fs = replay.dep.fairshare
    pool = Pool(replay, workers)
    jobs = {k: sorted(replay.idle[k], key=replay.fifo)
            for k in replay.cohorts()}
    entries: dict[tuple, list] = {}
    for k, js in jobs.items():
        entries.setdefault((k[0], replay.record(js[0])["user"]),
                           []).append(k)
    by_user, by_schedd = replay.usage(now, fs.half_life_s)
    v_user: dict[str, float] = {}
    v_schedd: dict[int, float] = {}

    def group_key(si):
        u = by_schedd.get(si, 0.0)
        return (u + v_schedd.get(si, 0.0)) / fs.quotas[si], u != 0.0

    def user_key(user):
        u = by_user.get(user, 0.0)
        return (fs.factor(user) * (fs.base_priority + u
                                   + v_user.get(user, 0.0)), u != 0.0)

    def serve(entry, commit):
        free = pool.free.copy()
        left, got = fs.quantum, []
        for k in entries[entry]:
            if left == 0:
                break
            if not jobs[k]:
                continue
            take = pool.fill(replay.record(jobs[k][0]),
                             min(len(jobs[k]), left))
            n = int(take.sum())
            got += pool.deal(jobs[k][:n], take)
            if commit:
                jobs[k] = jobs[k][n:]
            left -= n
        if not commit:
            pool.free = free
        return got

    out: dict[int, str] = {}
    tied = False
    while entries:
        schedds, s_tie = _best(sorted({si for si, _u in entries}), group_key)
        options = []
        for si in schedds[:len(schedds) if s_tie else 1]:
            users, u_tie = _best(sorted(u for s, u in entries if s == si),
                                 user_key)
            options += [(si, u) for u in users[:len(users) if u_tie else 1]]
        pick = options[0]
        if len(options) > 1:
            tied = True
            for entry in options if made is not None else ():
                if all(made.get(j) == w for j, w in serve(entry, False)):
                    pick = entry
                    break
        got = serve(pick, True)
        out.update(got)
        if got:
            cores = sum(job_cores(replay.record(j)) for j, _w in got)
            v_schedd[pick[0]] = v_schedd.get(pick[0], 0.0) + cores
            v_user[pick[1]] = v_user.get(pick[1], 0.0) + cores
        if len(got) < fs.quantum:
            del entries[pick]
    return out, tied


def claims_differing(records: list[dict], log: list, samples: list, *,
                     record_of: dict | None = None,
                     deployment: Deployment | None = None
                     ) -> tuple[list[int], int]:
    """For each sampled pass, the jobs whose claim (or absence of one)
    differs from the reference's; and how many of the passes held a
    choice that tied to rounding."""
    replay = Replay(records, record_of, deployment)
    out = [0] * len(samples)
    ties = 0
    for i in sorted(range(len(samples)), key=lambda i: samples[i]["pos"]):
        item = samples[i]
        replay.advance(log, item["pos"])
        got = dict(item["claims"])
        if item.get("kind") == "fairshare":
            want, tied = fairshare_claims(replay, item["workers"],
                                          item["now"], got)
            ties += tied
        else:
            want = pass_claims(replay, item["workers"])
        out[i] = sum(want.get(j) != got.get(j) for j in set(want) | set(got))
    return out, ties
