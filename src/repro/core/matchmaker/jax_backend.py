"""Jitted JAX matchmaker: the whole negotiation water-fill as XLA ops.

The per-cohort claiming loop is a `lax.scan` over cohort positions in
processing order: the carry is the transposed free-resource matrix
(R, W) plus the remaining claim budget, and each step converts one
cohort's request row into per-worker takes with the exact legacy
arithmetic — ``fits = floor(free/want + FIT_EPS)`` (true division, so
float64 runs are bitwise-identical to the NumPy reference), a
compat-mask multiply, and the greedy prefix allocation
``take = clip(d - exclusive_cumsum(fits), 0, fits)`` which reproduces
the seed's first-match worker walk in closed form.

Scale tricks (the ROADMAP's array-compiled matchmaking item):

  * **chunked scan + drain guard** — cohorts are processed in chunks of
    ``chunk`` positions; a chunk is skipped (``lax.cond``) once every
    worker falls below the chunk's componentwise-minimum request vector
    in some resource — provably nothing in it can fit, so skipping is
    claim-exact.  In the paper's demand >> supply regime (a 100k-job
    backlog against a ~600-pod Kubernetes pool) the pool drains early
    and most chunks cost one (R, W) comparison.
  * **padded/bucketed tensors** — cohort count pads to the chunk size
    and workers pad to lanes of 128, so XLA re-traces only when the
    bucket changes, not every cycle.
  * **donated free buffer** — the (R, W) carry is donated to the jit,
    avoiding a defensive copy per cycle.

The four device programs compile as ``jit_waterfill_match``,
``jit_waterfill_cycles``, ``jit_waterfill_preview`` and
``jit_waterfill_fairshare`` (a whole fair-share pass, every slice in one
``lax.while_loop``): stable names in a profiler trace.  Each call's
``last_call`` says its padding bucket,
whether the bucket was fresh, its round trip (``repro.device.roundtrip``:
first host-to-device copy to the answer on the host) and the bytes of
the padded arrays sent and fetched.

dtype: when the caller names none, ``float32`` on a TPU (which has no
native float64) and ``float64`` elsewhere.  ``float64`` matches the
NumPy reference bit-for-bit on any quantities (run under
``jax.enable_x64``).  ``float32`` is exact only on integer quantities
below 2**24 (and demand below 2**23) — whole cores, GPUs and GB —
and `_prep` refuses any other problem rather than return different
claims.
"""
from __future__ import annotations

import math
import time
from functools import lru_cache, partial

import numpy as np

from repro.core.matchmaker.base import (
    FIT_EPS, KEY_RTOL, RESOURCE_KEYS, CycleDelta, FairshareEntries,
    FairsharePlan, MatchPlan, MatchProblem,
)
from repro.observability import trace_me

import jax
import jax.numpy as jnp
from jax import lax

_ZERO_WANT_BIG = 1e15     # ratio offset for zero-request resource lanes
_W_LANES = 128            # worker-axis padding bucket
_PREVIEW_LANES = 512      # preview lane floor (one trace per replay)
_FS_ENTRY_LANES = 32      # fair-share entry-axis floor (power-of-two buckets)

# float32's exactness domain.  Quantities below 2**24 are exact
# integers, and so is every free/want floor and free - want*take built
# from them.  Demand is held to half that: the inclusive prefix sum over
# a cohort's fits reaches up to twice its demand before the greedy
# allocation saturates.
_F32_QUANTITY_LIMIT = 2 ** 24
_F32_DEMAND_LIMIT = 2 ** 23


def exact_floor_f32(fits, free, want):
    """Repair ``fits = floor(min_r free_r/want_r)`` computed in float32 on
    a TPU, whose float32 division is not correctly rounded: an exact
    multiple can come out just below its quotient and floor one short
    (or a near-multiple just above and floor one long).  On the integer
    quantities float32 admits, each quotient is off by at most one, and
    one step each way restores the exact floor: ``fits*want`` is exact
    below 2**24 and otherwise rounds to a value still above ``free``.
    Shapes: fits (1, W), free (R, W), want (R, 1); zero-request rows
    never veto.  A no-op where division is exact (the host)."""
    need = fits * want
    up = jnp.min(jnp.where(need + want <= free, 1.0, 0.0),
                 axis=0, keepdims=True)
    down = jnp.max(jnp.where(need > free, 1.0, 0.0), axis=0, keepdims=True)
    return fits + up - down


def _make_steps(unroll: int):
    """The shared inner/chunk scan bodies — the single-cycle jit and the
    fused multi-cycle jit run EXACTLY these ops, so their plans agree
    bit-for-bit."""

    def inner_step(carry, x):
        freeT, left = carry
        want, safe, big, d, crow = x
        d = jnp.minimum(d, left)
        ratio = freeT / safe[:, None] + big[:, None]
        fits = jnp.maximum(jnp.floor(jnp.min(ratio, axis=0) + FIT_EPS), 0.0)
        if freeT.dtype == jnp.float32:
            fits = exact_floor_f32(fits[None, :], freeT, want[:, None])[0]
        # capping fits at d leaves the greedy prefix allocation exact
        # (prefix sums below d are uncapped; above d both saturate) and
        # bounds the zero-request sentinel lanes; crow is uint8 (the
        # compat mask ships to the device at 1 byte/cell — at C=4096,
        # W=512 the f64 version alone was 16MB of PCIe per cycle)
        fits = jnp.minimum(fits, d) * crow
        cum = jnp.cumsum(fits)
        take = jnp.clip(d - (cum - fits), 0.0, fits)
        freeT = freeT - want[:, None] * take[None, :]
        left = left - jnp.sum(take)
        # emit int32 rows: takes are whole job counts, and stacking the
        # (C, W) output as f64 would cost 134MB of write traffic at the
        # 1M tier before a round+cast pass doubled it
        return (freeT, left), jnp.round(take).astype(jnp.int32)

    def chunk_step(carry, x):
        freeT, left = carry
        want_c, safe_c, big_c, d_c, crow_c, minreq = x
        # drain guard: `minreq` is the componentwise minimum request
        # vector over the chunk's still-demanding cohorts (inf when the
        # chunk has none).  A worker below it in ANY resource fits NO
        # cohort of the chunk — minreq[r] <= want[r] for every cohort —
        # so when every worker fails somewhere the whole chunk is
        # provably empty and the inner scan is skipped, claim-exactly.
        # On the paper's demand >> supply shape the pool drains a few
        # chunks in (memory/GPUs exhaust even while CPUs linger, which a
        # CPU-only guard would miss) and later chunks cost one (R, W)
        # comparison.  The (1 - 2eps) slack keeps the guard conservative
        # against the fits eps.
        ok = freeT >= (minreq * (1.0 - 2 * FIT_EPS))[:, None]
        alive = jnp.any(jnp.all(ok, axis=0)) & (left > 0)

        def run(c):
            c2, takes = lax.scan(inner_step, c,
                                 (want_c, safe_c, big_c, d_c, crow_c),
                                 unroll=unroll)
            return c2, (takes, True)

        def skip(c):
            return c, (jnp.zeros(crow_c.shape, jnp.int32), False)

        return lax.cond(alive, run, skip, (freeT, left))

    return inner_step, chunk_step


@lru_cache(maxsize=None)
def _build_scan(chunk: int, unroll: int):
    """The jitted chunked water-fill (built once per config, shape-
    polymorphic thereafter — XLA caches one executable per bucket).
    lru_cache shares the jitted callable — and therefore its per-bucket
    executable cache — across backend instances, so a process that
    builds many pools (test suites, benchmark sweeps) traces each
    (config, bucket) pair once."""
    _inner, chunk_step = _make_steps(unroll)

    def waterfill_match(freeT, left, want_s, safe_s, big_s, d_s, crow_s,
                        chunk_min):
        (freeT, left), (takes, ran) = lax.scan(
            chunk_step, (freeT, left),
            (want_s, safe_s, big_s, d_s, crow_s, chunk_min))
        # `ran` flags which chunks executed — the host scatters only
        # those rows, so a drained 1M-cohort backlog does not pay for
        # converting a matrix of zeros
        return takes, freeT, ran

    return jax.jit(waterfill_match, donate_argnums=(0,))


@lru_cache(maxsize=None)
def _build_preview_scan(chunk: int, unroll: int):
    """The batched-preview jit: a `vmap` over N independent candidate
    (free, demand) pairs of the SAME chunked water-fill inner scan the
    match path runs, emitting only per-cohort absorbed counts.

    Differences from `_build_scan`, neither of which changes claims:

      * no drain guard — the guard's skip branch emits the exact zeros
        the inner scan would compute, so omitting it is claim-exact; a
        preview is one dispatch per reconcile (not per cycle), so the
        guard's saving does not pay for its per-chunk `lax.cond`
        under `vmap` (which lowers to running both branches anyway);
      * no (C, W) takes output — only the (nch, chunk) per-cohort sums
        ship back, so an N=8 candidate batch returns 8*Cp ints instead
        of 8 full matrices.

    All N candidates share the device-resident cohort constants
    (requests/compat, cached across calls by `JaxMatchmaker`'s preview
    session); only the stacked free matrices and demand vectors ship
    down per call."""
    inner_step, _chunk_step = _make_steps(unroll)

    def one(freeT, d_s, want_s, safe_s, big_s, crow_s):
        left0 = jnp.asarray(jnp.inf, dtype=freeT.dtype)

        def chunk_step(carry, x):
            want_c, safe_c, big_c, d_c, crow_c = x
            c2, takes = lax.scan(inner_step, carry,
                                 (want_c, safe_c, big_c, d_c, crow_c),
                                 unroll=unroll)
            # takes: (chunk, Wp) int32 rows from the SHARED inner_step —
            # summing them per cohort is exactly plan.per_cohort()
            return c2, jnp.sum(takes, axis=1)

        (_f, _l), absorbed = lax.scan(
            chunk_step, (freeT, left0),
            (want_s, safe_s, big_s, d_s, crow_s))
        return absorbed                       # (nch, chunk) int32

    many = jax.vmap(one, in_axes=(0, 0, None, None, None, None))

    def waterfill_preview(freeT, d_s, want_s, safe_s, big_s, crow_s):
        return many(freeT, d_s, want_s, safe_s, big_s, crow_s)

    return jax.jit(waterfill_preview)


@lru_cache(maxsize=None)
def _build_cycles_scan(chunk: int, unroll: int):
    """The fused multi-cycle jit: an outer `lax.scan` over K negotiation
    cycles wrapping the same chunked water-fill, so the free matrix and
    the carried demand stay DEVICE-RESIDENT across cycles — one dispatch
    and one host round-trip per K-cycle batch instead of per cycle.

    Per cycle the carry applies the staged deltas on device (``demand +=
    arrivals``, ``freeT += free_add``), re-derives the drain guard's
    per-chunk componentwise-minimum request from the LIVE demand (the
    single-cycle path computes it on the host; here demand changes
    across cycles, so the guard must be recomputed per cycle with the
    identical arithmetic to stay claim-exact), resets the claim budget,
    and runs the inner chunk scan unchanged — the emitted takes are
    bit-identical to K sequential single-cycle matches."""
    _inner, chunk_step = _make_steps(unroll)

    def cycle_step(carry, x):
        freeT, d_s = carry              # d_s: (nch, chunk) live demand
        arr, fadd, left, want_s, safe_s, big_s, crow_s = x
        d_s = d_s + arr
        freeT = freeT + fadd
        # drain-guard lower bound over the cycle's still-demanding
        # cohorts — same where/min arithmetic as the host precompute
        minreq = jnp.min(
            jnp.where((d_s > 0)[..., None], want_s, jnp.inf), axis=1)
        (freeT, _left), (takes, ran) = lax.scan(
            chunk_step, (freeT, left),
            (want_s, safe_s, big_s, d_s, crow_s, minreq))
        d_s = d_s - jnp.sum(takes, axis=2).astype(d_s.dtype)
        return (freeT, d_s), (takes, ran, freeT)

    def waterfill_cycles(freeT, d_s, arrivals, free_addT, budgets,
                         want_s, safe_s, big_s, crow_s):
        # deltas scan over cycles; the per-chunk tensors are loop
        # constants (closed over via broadcast in xs would copy K-fold)
        def step(carry, x):
            arr, fadd, left = x
            return cycle_step(carry, (arr, fadd, left,
                                      want_s, safe_s, big_s, crow_s))

        (freeT, d_s), ys = lax.scan(
            step, (freeT, d_s), (arrivals, free_addT, budgets))
        takes, ran, free_per = ys
        return takes, ran, free_per

    # no buffer donation here: the per-cycle freeT snapshots are emitted
    # as scan ys, so the input buffers stay live for the whole dispatch
    return jax.jit(waterfill_cycles)


# -- fair-share keys as double-float pairs -----------------------------------
#
# A fair-share key is affine in the cores charged this pass, a + b*v
# (base.FairshareEntries), and keys a last digit apart must order as the
# host's float64 orders them up to base.KEY_RTOL.  float32 alone resolves
# 6e-8, so on a TPU each key is carried as an unevaluated sum hi + lo of
# two float32s.  The host hands the device a as (a_hi, a_lo), within
# 2**-48 |a|, and b as b1 + b2 + b3, where b1 and b2 hold 12 significant
# bits each and b3 the rest rounded to float32 (within 2**-47 |b|).  The
# device splits v (a whole number below 2**24 in float32) as
# vh + vl with vh = 4096 floor(v / 4096) and vl < 4096, so vh and vl hold
# 12 significant bits: the four products of vh, vl with b1, b2 are exact
# (24 bits), vh*b3 and vl*b3 are within 2**-24 of terms below 2**-23 |b v|,
# and each is added in by an error-free sum (`_two_sum`, adds and
# subtracts only, so a fused multiply-add cannot change it) into (hi, lo)
# renormalised after each.  b3 is below 2**-22 |b|, so the two inexact
# products lose 2**-45 |b v| together, and each of the six additions at
# most 2**-47 of the running sum: for a >= 0, b v >= 0 (every key of a
# sound accountant) the key is within 2**-43 (1.2e-13) relative, and the
# pairwise difference of two keys adds 2**-24 of itself: far below
# KEY_RTOL (1e-10), so the device ties and orders keys as float64 does.
# In float64 the host hands (a, 0) and (b, 0, 0), and the same code
# computes a + b v to float64's rounding.

def _two_sum(a, b):
    """Knuth's error-free sum: s + e == a + b exactly."""
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


def _dd_add(hi, lo, x):
    """Add `x` to the double-float hi + lo, renormalised."""
    s, e = _two_sum(hi, x)
    e = e + lo
    hi = s + e
    return hi, e - (hi - s)


def _dd_affine(k, v):
    """a + b*v as a double-float, from k = (a_hi, a_lo, b1, b2, b3)
    (each (E,)) and v (E,) (see above)."""
    a_hi, a_lo, b1, b2, b3 = k
    vh = jnp.floor(v * (1.0 / 4096.0)) * 4096.0
    vl = v - vh
    hi, lo = a_hi, a_lo
    for t in (vh * b1, vl * b1, vh * b2, vl * b2, vh * b3, vl * b3):
        hi, lo = _dd_add(hi, lo, t)
    return hi, lo


def _top12(x: np.ndarray) -> np.ndarray:
    """x rounded to float32 and cut to its 12 leading significant bits."""
    bits = np.asarray(x, dtype=np.float32).view(np.uint32)
    return (bits & np.uint32(0xFFFFF000)).view(np.float32).astype(np.float64)


def key_terms(a, b, dtype: str, n: int) -> np.ndarray:
    """(5, n) rows a_hi, a_lo, b1, b2, b3 of the keys a + b*v, padded
    with zeros to `n` (see above)."""
    a = np.asarray(a, dtype=np.float64)
    b = np.asarray(b, dtype=np.float64)
    out = np.zeros((5, n))
    if dtype == "float64":
        out[0, :len(a)] = a
        out[2, :len(b)] = b
        return out
    a_hi = a.astype(np.float32).astype(np.float64)
    b1 = _top12(b)
    b2 = _top12(b - b1)
    out[0, :len(a)] = a_hi
    out[1, :len(a)] = (a - a_hi).astype(np.float32)
    out[2, :len(b)] = b1
    out[3, :len(b)] = b2
    out[4, :len(b)] = (b - b1 - b2).astype(np.float32)
    return out


def _lowest(hi, lo, ok):
    """Mask of the `ok` keys within KEY_RTOL of the lowest `ok` key:
    each i whose difference to every ok j is at most KEY_RTOL |key_j|."""
    d = (hi[:, None] - hi[None, :]) + (lo[:, None] - lo[None, :])
    within = (d <= KEY_RTOL * jnp.abs(hi)[None, :]) | ~ok[None, :]
    return ok & jnp.all(within, axis=1)


@lru_cache(maxsize=None)
def _build_fairshare(chunk: int, unroll: int):
    """The fused fair-share pass: a `lax.while_loop` over slices, each
    one (1) the schedd with the lowest key among those with a live
    entry, ties to the lower index, (2) its live entry whose user has
    the lowest key, ties to the lower name rank, (3) the chunked
    water-fill of `_make_steps` over that entry's cohorts with budget
    `quantum`, its drain guard re-derived from the live demand as in
    `_build_cycles_scan`, (4) the slice's cores charged to both keys,
    and (5) the entry retired if it claimed fewer than `quantum`.  Free
    capacity, demand, the summed takes and both virtual ledgers stay on
    the device; the pass returns its summed takes, its free capacity
    and its slice count (`base.sequential_match_fairshare` is the
    reference it reproduces)."""
    _inner, chunk_step = _make_steps(unroll)

    def waterfill_fairshare(freeT, d_s, want_s, safe_s, big_s, crow_s,
                            cent_s, cores_s, ent_s, ent_u, live, s_keys,
                            u_keys, quantum, max_slices):
        dt = freeT.dtype
        E = live.shape[0]
        slots = jnp.arange(E)

        def body(c):
            freeT, d_s, takes, v_s, v_u, live, n = c
            s_ok = jnp.any((ent_s[None, :] == slots[:, None])
                           & live[None, :], axis=1)
            si = jnp.argmax(_lowest(*_dd_affine(s_keys, v_s), s_ok))
            mine = live & (ent_s == si)
            u_hi, u_lo = _dd_affine(u_keys, v_u)
            cand = _lowest(u_hi[ent_u], u_lo[ent_u], mine)
            e = jnp.argmin(jnp.where(cand, ent_u, E))
            d_e = jnp.where(cent_s == e, d_s, 0.0)
            minreq = jnp.min(
                jnp.where((d_e > 0)[..., None], want_s, jnp.inf), axis=1)
            (freeT, _left), (tk, _ran) = lax.scan(
                chunk_step, (freeT, quantum),
                (want_s, safe_s, big_s, d_e, crow_s, minreq))
            got_c = jnp.sum(tk, axis=2).astype(dt)
            got = jnp.sum(got_c)
            cores = jnp.sum(got_c * cores_s)
            live = live.at[e].set(got >= quantum)
            return (freeT, d_s - got_c, takes + tk,
                    v_s.at[ent_s[e]].add(cores),
                    v_u.at[ent_u[e]].add(cores), live, n + 1)

        def cond(c):
            return jnp.any(c[5]) & (c[6] < max_slices)

        init = (freeT, d_s, jnp.zeros(crow_s.shape, jnp.int32),
                jnp.zeros(E, dt), jnp.zeros(E, dt), live,
                jnp.zeros((), jnp.int32))
        freeT, _d, takes, _vs, _vu, _live, n = lax.while_loop(
            cond, body, init)
        return takes, freeT, n

    return jax.jit(waterfill_fairshare, donate_argnums=(0,))


class _RoundTrip:
    """One matchmaker call's device round trip, from its first
    host-to-device copy (`put`) to its answer on the host (`get`):
    ``roundtrip_s``, ``h2d_bytes`` and ``d2h_bytes`` (the nbytes of the
    padded device arrays sent and fetched) added to ``last_call``, and,
    with `spans` on and a profiler session collecting, a
    `repro.device.roundtrip` TraceMe."""

    __slots__ = ("lc", "spans", "h2d", "d2h", "t0", "tm")

    def __init__(self, lc: dict, spans: bool):
        self.lc = lc
        self.spans = spans
        self.h2d = self.d2h = 0

    def __enter__(self):
        self.tm = (trace_me("repro.device.roundtrip", path=self.lc["kind"],
                            fresh=self.lc["compiled"])
                   if self.spans else None)
        self.t0 = time.perf_counter()
        return self

    def put(self, x, dtype=None):
        a = jnp.asarray(x, dtype=dtype)
        self.h2d += a.nbytes
        return a

    def get(self, a, dtype=None) -> np.ndarray:
        self.d2h += a.nbytes
        return np.asarray(a, dtype=dtype)

    def __exit__(self, *exc):
        self.lc.update(roundtrip_s=time.perf_counter() - self.t0,
                       h2d_bytes=self.h2d, d2h_bytes=self.d2h)
        if self.tm is not None:
            self.tm.__exit__(*exc)


class JaxMatchmaker:
    """The XLA backend (`make_matchmaker("jax")`)."""

    name = "jax"

    def __init__(self, *, dtype: str | None = None, chunk: int = 64,
                 unroll: int = 4):
        if dtype is None:
            dtype = ("float32" if jax.default_backend() == "tpu"
                     else "float64")
        if dtype not in ("float64", "float32"):
            raise ValueError(f"dtype must be float64|float32, got {dtype!r}")
        self.dtype = dtype
        self.chunk = int(chunk)
        self.unroll = int(unroll)
        self._fn = _build_scan(self.chunk, self.unroll)
        self._fn_cycles = _build_cycles_scan(self.chunk, self.unroll)
        self._fn_fairshare = _build_fairshare(self.chunk, self.unroll)
        #: set by a fair-share pool: each fresh `match` bucket compiles
        #: the fair-share program of its (C, W) bucket beside it, for
        #: every entry bucket seen, so that warming `match` warms both
        self.fairshare_twins = False
        self._fs_entry_buckets = {_FS_ENTRY_LANES}
        # unroll=1 for preview: the preview path is compile-bound, not
        # dispatch-bound (a handful of memo-missing calls per replay,
        # each on a fresh lane bucket as the pool grows), and a rolled
        # scan body halves the XLA trace cost for the same steady-state
        # latency (245ms vs 509ms trace, ~0.86ms/call either way).
        self._fn_preview = _build_preview_scan(self.chunk, 1)
        # one-entry preview session: the cohort-side constants of the
        # last previewed problem (requests/compat, permuted + padded +
        # shipped to the device).  The collector's preview problems
        # repeat their structure across reconciles while only free
        # capacity and demand move, so a session hit ships (R, Wp)
        # floats per candidate instead of rebuilding ~4 (Cp, ...)
        # tensors — measured 0.44ms vs 8.2ms per preview on the 2k
        # diurnal replay.  Validated on (caller token, order, shape);
        # demand is NEVER cached (it changes within a session).
        self._preview_session: dict | None = None
        # compile-vs-execute telemetry: XLA retraces per padded-shape
        # bucket, so the first call on a fresh bucket pays the trace +
        # compile and every repeat hits the executable cache.  The
        # profiler reads `last_call` after each match.
        self._seen_buckets: set[tuple] = set()
        self.last_call: dict | None = None
        #: open `repro.device.roundtrip` TraceMes; a `Collector` with
        #: telemetry on turns this on
        self.spans = False

    def _note_call(self, kind: str, bucket: tuple) -> _RoundTrip:
        """Start `last_call` for one call; returns its round trip."""
        compiled = bucket not in self._seen_buckets
        self._seen_buckets.add(bucket)
        self.last_call = {"kind": kind, "bucket": bucket,
                          "compiled": compiled}
        return _RoundTrip(self.last_call, self.spans)

    def warm_preview(self):
        """Pre-compile the canonical preview bucket: nch=1 cohort
        chunks, the `_PREVIEW_LANES` lane floor, one candidate.  The
        floor exists precisely so that every small-to-medium pool lands
        on this one bucket, which makes it pre-compilable — a long-lived
        pool (the Collector calls this at construction) pays the ~0.25s
        XLA trace at startup instead of inside the first reconcile's
        preview.  The executable lands in the process-shared builder
        cache, so repeat warms are free.  `_seen_buckets` is left
        untouched: compile telemetry still reports the first live call
        on the bucket as a fresh trace (which it was, just earlier)."""
        chunk, Wp = self.chunk, _PREVIEW_LANES
        R = len(RESOURCE_KEYS)
        with self._precision():
            z = lambda *s: jnp.zeros(s, dtype=self.dtype)
            self._fn_preview(
                z(1, R, Wp), z(1, 1, chunk), z(1, chunk, R),
                jnp.ones((1, chunk, R), dtype=self.dtype), z(1, chunk, R),
                jnp.zeros((1, chunk, Wp), dtype=jnp.uint8),
            ).block_until_ready()

    def _precision(self):
        """64-bit JAX types on for float64, and explicitly OFF for
        float32, so no Python scalar of the float32 path traces as a
        64-bit value (Mosaic refuses those)."""
        return jax.enable_x64(self.dtype == "float64")

    def _require_exact(self, what: str, x, limit: int = _F32_QUANTITY_LIMIT):
        """In float32, refuse values outside its exact domain."""
        if self.dtype != "float32":
            return
        x = np.asarray(x, dtype=np.float64)
        if not (np.all(np.floor(x) == x) and np.all(np.abs(x) < limit)):
            raise ValueError(
                f"float32 matchmaking is exact only on integer {what} "
                f"below {limit}; this problem needs dtype='float64'")

    def _prep(self, p: MatchProblem, active=None, *, lanes=None):
        """Order-permuted, padded host arrays (pad cohorts have demand 0
        and pad workers have zero free capacity — both take nothing).
        ``lanes`` widens the worker padding beyond the default 128-lane
        granularity — the preview path passes a power-of-two bucket so
        a pool growing through many widths retraces once or twice per
        run instead of once per 128-lane step."""
        self._require_exact("requests", p.requests)
        self._require_exact("free capacity", p.free)
        self._require_exact("demand", p.demand, _F32_DEMAND_LIMIT)
        C, W = p.compat.shape
        R = p.requests.shape[1]
        chunk = self.chunk
        Cp = max(chunk, ((C + chunk - 1) // chunk) * chunk)
        Wp = max(_W_LANES, ((W + _W_LANES - 1) // _W_LANES) * _W_LANES)
        if lanes is not None:
            Wp = max(Wp, int(lanes))
        order = np.concatenate(
            [np.asarray(p.order, dtype=np.int64),
             np.arange(C, Cp, dtype=np.int64)])
        req_o = np.zeros((Cp, R))
        req_o[:C] = p.requests[order[:C]]
        d_o = np.zeros(Cp)
        d_o[:C] = p.demand[order[:C]]
        if active is not None:
            d_o[:C] *= active[order[:C]]
        crow_o = np.zeros((Cp, Wp), dtype=np.uint8)
        crow_o[:C, :W] = p.compat[order[:C]]
        freeT = np.zeros((R, Wp))
        freeT[:, :W] = p.free.T
        pos = req_o > 0
        safe = np.where(pos, req_o, 1.0)
        big = np.where(pos, 0.0, _ZERO_WANT_BIG)
        return order, req_o, d_o, crow_o, freeT, safe, big, Cp, Wp

    def match(self, p: MatchProblem, *, budget: int | None = None,
              active: np.ndarray | None = None) -> MatchPlan:
        C, W = p.compat.shape
        R = p.requests.shape[1]
        chunk = self.chunk
        (order, req_o, d_o, crow_o, freeT, safe, big,
         Cp, Wp) = self._prep(p, active)
        # per-chunk componentwise-min request among demanding cohorts
        # (the drain guard's lower bound; inf where a chunk is empty)
        req_live = np.where((d_o > 0)[:, None], req_o, np.inf)
        chunk_min = req_live.reshape(-1, chunk, R).min(axis=1)
        nch = Cp // chunk
        if budget is not None:
            self._require_exact("budget", budget)
        left = math.inf if budget is None else float(budget)
        if self.fairshare_twins:
            for Ep in sorted(self._fs_entry_buckets):
                self._warm_fairshare(nch, Wp, Ep)
        rt = self._note_call("match", (nch, Wp, self.dtype))

        with self._precision(), rt:
            takes_j, freeT_j, ran_j = self._run(
                rt.put, self.dtype, freeT, left, req_o, safe, big, d_o,
                crow_o, chunk_min, nch, chunk, R, Wp)
            takes_j = rt.get(takes_j)
            freeT_j = rt.get(freeT_j, np.float64)
            ran = rt.get(ran_j)

        # scatter back to original cohort rows — only chunks that ran
        # (skipped chunks are all-zero by construction)
        takes_flat = takes_j.reshape(Cp, Wp)
        takes = np.zeros((Cp, W), dtype=np.int64)
        live = np.nonzero(np.repeat(ran, chunk))[0]
        takes[order[live]] = takes_flat[live, :W]
        return MatchPlan(takes=takes[:C],
                         free_after=freeT_j[:, :W].T.copy())

    def match_fairshare(self, p: MatchProblem, entries: FairshareEntries,
                        quantum: int) -> FairsharePlan:
        """A whole fair-share pass in ONE device call (`_build_fairshare`)
        — see `base.sequential_match_fairshare` for the reference
        semantics this reproduces claim for claim.  Entries pad to a
        power-of-two bucket (floor `_FS_ENTRY_LANES`), cohorts and
        workers to `match`'s buckets."""
        C, W = p.compat.shape
        (order, req_o, d_o, crow_o, freeT, safe, big,
         Cp, Wp) = self._prep(p)
        nch = Cp // self.chunk
        E = entries.n_entries
        Ep = max(_FS_ENTRY_LANES, 1 << max(0, E - 1).bit_length())
        self._fs_entry_buckets.add(Ep)
        quantum = int(quantum)
        cores_o = np.zeros(Cp)
        cores_o[:C] = entries.cohort_cores[order[:C]]
        self._require_exact("budget", quantum)
        self._require_exact("cores per job", cores_o)
        # the virtual ledgers never exceed the cores the demand can claim
        self._require_exact("cores charged", float(cores_o @ d_o))
        cent_o = np.full(Cp, -1, dtype=np.int32)
        cent_o[:C] = entries.cohort_entry[order[:C]]
        args = self._fairshare_args(
            freeT, d_o, req_o, safe, big, crow_o, cent_o, cores_o,
            entries, Ep, quantum, int(d_o.sum()) // quantum + E + 1)
        rt = self._note_call("fairshare", ("fairshare", nch, Wp, Ep,
                                           self.dtype))
        with self._precision(), rt:
            takes_j, freeT_j, n = self._fn_fairshare(
                *(rt.put(x, d) for x, d in args))
            takes_j = rt.get(takes_j)
            freeT_j = rt.get(freeT_j, np.float64)
            n = int(rt.get(n))
        self.last_call["slices"] = n
        takes = np.zeros((C, W), dtype=np.int64)
        takes[order[:C]] = takes_j.reshape(Cp, Wp)[:C, :W]
        return FairsharePlan(takes=takes, free_after=freeT_j[:, :W].T.copy(),
                             slices=n, calls=1)

    def _fairshare_args(self, freeT, d_o, req_o, safe, big, crow_o, cent_o,
                        cores_o, entries, Ep, quantum, max_slices):
        """The fair-share program's arguments as (host array, dtype)
        pairs, in its order: one path for real calls and warm-ups, so
        both trace the same program."""
        dt = self.dtype
        R, Wp = freeT.shape
        nch = len(d_o) // self.chunk
        E = entries.n_entries
        ent_s = np.zeros(Ep, dtype=np.int32)
        ent_s[:E] = entries.schedd
        ent_u = np.zeros(Ep, dtype=np.int32)
        ent_u[:E] = entries.user
        live = np.arange(Ep) < E
        by_chunk = (nch, self.chunk)
        return (
            (freeT, dt), (d_o.reshape(by_chunk), dt),
            (req_o.reshape(nch, self.chunk, R), dt),
            (safe.reshape(nch, self.chunk, R), dt),
            (big.reshape(nch, self.chunk, R), dt),
            (crow_o.reshape(nch, self.chunk, Wp), None),   # uint8 mask
            (cent_o.reshape(by_chunk), np.int32),
            (cores_o.reshape(by_chunk), dt),
            (ent_s, np.int32), (ent_u, np.int32), (live, bool),
            (key_terms(entries.owed, entries.owed_slope, dt, Ep), dt),
            (key_terms(entries.prio, entries.prio_slope, dt, Ep), dt),
            (np.asarray(quantum, dtype=np.float64), dt),
            (np.asarray(max_slices, dtype=np.int32), np.int32))

    def _warm_fairshare(self, nch: int, Wp: int, Ep: int):
        """Compile the fair-share program for one bucket, on an empty
        pass, unless a call has met the bucket already."""
        bucket = ("fairshare", nch, Wp, Ep, self.dtype)
        if bucket in self._seen_buckets:
            return
        self._seen_buckets.add(bucket)
        R = len(RESOURCE_KEYS)
        Cp = nch * self.chunk
        none = np.zeros(0, dtype=np.int64)
        entries = FairshareEntries(
            cohort_entry=none, cohort_cores=np.zeros(0), schedd=none,
            user=none, owed=np.zeros(0), owed_slope=np.zeros(0),
            prio=np.zeros(0), prio_slope=np.zeros(0))
        args = self._fairshare_args(
            np.zeros((R, Wp)), np.zeros(Cp), np.zeros((Cp, R)),
            np.ones((Cp, R)), np.zeros((Cp, R)),
            np.zeros((Cp, Wp), dtype=np.uint8),
            np.full(Cp, -1, dtype=np.int32), np.zeros(Cp), entries, Ep, 1, 1)
        with self._precision():
            out = self._fn_fairshare(
                *(jnp.asarray(x, dtype=d) for x, d in args))
            jax.block_until_ready(out)

    def preview_many(self, p: MatchProblem, frees: list,
                     demands: list | None = None, *,
                     session=None) -> list[np.ndarray]:
        """N independent candidate previews in ONE vmapped dispatch —
        see `base.sequential_preview_many` for the reference semantics
        this reproduces bit-for-bit (the inner scan body is shared with
        `match`).  ``session`` is an opaque hashable token naming the
        problem STRUCTURE (cohort keys + worker shapes): consecutive
        calls with the same token and cohort order reuse the device-
        resident request/compat constants and ship only the stacked
        free matrices and demand vectors."""
        N = len(frees)
        if N == 0:
            return []
        C, W = p.compat.shape
        R = p.requests.shape[1]
        chunk = self.chunk
        dt = self.dtype
        order_key = np.asarray(p.order, dtype=np.int64).tobytes()
        for f in frees:
            self._require_exact("free capacity", f)
        for dv in (demands if demands is not None else [p.demand]):
            self._require_exact("demand", dv, _F32_DEMAND_LIMIT)

        def run():
            sess = self._preview_session
            ship = None       # cohort constants to send, on a miss
            if (session is not None and sess is not None
                    and sess["token"] == session
                    and sess["shape"] == (C, W, R)
                    and sess["order"] == order_key):
                order = sess["order_arr"]
                Cp, Wp = sess["pad"]
                consts = sess["consts"]
            else:
                # power-of-two lane bucket with a 512-lane floor: the
                # live pool's worker count drifts through many 128-lane
                # widths over a replay and each width is a fresh XLA
                # trace (~0.25s), while a 512-wide steady-state call is
                # <1ms — so one wide compile beats three narrow ones.
                # Pad workers have zero free and take nothing, so
                # results are unchanged.
                lanes = max(_PREVIEW_LANES, 1 << max(0, W - 1).bit_length())
                (order, req_o, _d_o, crow_o, _freeT, safe, big,
                 Cp, Wp) = self._prep(p, lanes=lanes)
                nch = Cp // chunk
                ship = ((req_o.reshape(nch, chunk, R), dt),
                        (safe.reshape(nch, chunk, R), dt),
                        (big.reshape(nch, chunk, R), dt),
                        (crow_o.reshape(nch, chunk, Wp), None))
            nch = Cp // chunk
            if demands is None:
                d_o = np.zeros(Cp)
                d_o[:C] = np.asarray(p.demand, dtype=np.float64)[order[:C]]
                dd = np.broadcast_to(
                    d_o.reshape(1, nch, chunk), (N, nch, chunk))
            else:
                dd = np.zeros((N, Cp))
                for i, dv in enumerate(demands):
                    dd[i, :C] = np.asarray(
                        dv, dtype=np.float64)[order[:C]]
                dd = dd.reshape(N, nch, chunk)
            fstack = np.zeros((N, R, Wp))
            for i, f in enumerate(frees):
                fstack[i, :, :W] = np.asarray(f, dtype=np.float64).T
            with self._note_call("preview", (nch, Wp, N, self.dtype)) as rt:
                if ship is not None:
                    # the cohort constants ship once per session
                    consts = tuple(rt.put(x, d) for x, d in ship)
                    self._preview_session = None if session is None else {
                        "token": session, "shape": (C, W, R),
                        "order": order_key, "order_arr": order,
                        "pad": (Cp, Wp), "consts": consts,
                    }
                absorbed = self._fn_preview(
                    rt.put(fstack, dt), rt.put(dd, dt), *consts)
                return order, Cp, rt.get(absorbed)

        with self._precision():
            order, Cp, absorbed = run()

        flat = absorbed.reshape(N, Cp)
        out: list[np.ndarray] = []
        for i in range(N):
            res = np.zeros(C, dtype=np.int64)
            res[order[:C]] = flat[i, :C]
            out.append(res)
        return out

    def match_cycles(self, p: MatchProblem,
                     deltas: list[CycleDelta]) -> list[MatchPlan]:
        """K fused negotiation cycles in ONE device dispatch — see
        `base.sequential_match_cycles` for the reference semantics this
        must (and does, bit-for-bit) reproduce.  The free matrix and the
        live demand never leave the device between cycles; only the
        staged deltas ship down and only the K plans ship back."""
        if not deltas:
            return []
        C, W = p.compat.shape
        R = p.requests.shape[1]
        chunk = self.chunk
        (order, req_o, d_o, crow_o, freeT, safe, big,
         Cp, Wp) = self._prep(p)
        nch = Cp // chunk
        K = len(deltas)
        rt = self._note_call("match_cycles", (nch, Wp, K, self.dtype))

        arrivals = np.zeros((K, Cp))
        free_addT = np.zeros((K, R, Wp))
        budgets = np.empty(K)
        for k, d in enumerate(deltas):
            arrivals[k, :C] = np.asarray(d.arrivals, dtype=np.float64)[
                order[:C]]
            if d.free_add is not None:
                free_addT[k, :, :W] = np.asarray(d.free_add).T
            if d.budget is not None:
                self._require_exact("budget", d.budget)
            budgets[k] = math.inf if d.budget is None else float(d.budget)
        # claims only shrink demand and free capacity, so the deltas'
        # running totals bound every value the K cycles carry
        self._require_exact("free capacity",
                            freeT + np.cumsum(free_addT, axis=0))
        self._require_exact("demand", d_o + np.cumsum(arrivals, axis=0),
                            _F32_DEMAND_LIMIT)

        with self._precision(), rt:
            takes_j, ran_j, free_per = self._run_cycles(
                rt.put, self.dtype, freeT, d_o, arrivals, free_addT,
                budgets, req_o, safe, big, crow_o, nch, chunk, R, Wp)
            takes_j = rt.get(takes_j)
            ran = rt.get(ran_j)
            free_per = rt.get(free_per, np.float64)

        plans: list[MatchPlan] = []
        for k in range(K):
            takes_flat = takes_j[k].reshape(Cp, Wp)
            takes = np.zeros((Cp, W), dtype=np.int64)
            live = np.nonzero(np.repeat(ran[k], chunk))[0]
            takes[order[live]] = takes_flat[live, :W]
            plans.append(MatchPlan(takes=takes[:C],
                                   free_after=free_per[k][:, :W].T.copy()))
        return plans

    def _run_cycles(self, put, dt, freeT, d_o, arrivals, free_addT,
                    budgets, req_o, safe, big, crow_o, nch, chunk, R, Wp):
        K = arrivals.shape[0]
        return self._fn_cycles(
            put(freeT, dt),
            put(d_o.reshape(nch, chunk), dt),
            put(arrivals.reshape(K, nch, chunk), dt),
            put(free_addT, dt),
            put(budgets, dt),
            put(req_o.reshape(nch, chunk, R), dt),
            put(safe.reshape(nch, chunk, R), dt),
            put(big.reshape(nch, chunk, R), dt),
            put(crow_o.reshape(nch, chunk, Wp)),   # uint8 mask
        )

    def _run(self, put, dt, freeT, left, req_o, safe, big, d_o, crow_o,
             chunk_min, nch, chunk, R, Wp):
        """The single-cycle dispatch; `put` copies one host array to the
        device (and counts its bytes)."""
        return self._fn(
            put(freeT, dt),
            put(left, dt),
            put(req_o.reshape(nch, chunk, R), dt),
            put(safe.reshape(nch, chunk, R), dt),
            put(big.reshape(nch, chunk, R), dt),
            put(d_o.reshape(nch, chunk), dt),
            put(crow_o.reshape(nch, chunk, Wp)),   # uint8 mask
            put(chunk_min, dt),
        )
