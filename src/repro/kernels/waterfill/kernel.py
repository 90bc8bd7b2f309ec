"""Pallas TPU water-fill: the negotiation claim/absorb inner loop.

One negotiation cycle walks cohorts in processing order and, per cohort,
converts the request row into per-worker takes against the shrinking
free-resource matrix — ``fits = floor(min_r free_r/want_r + eps)`` then
the greedy prefix allocation ``take = clip(d - exclusive_cumsum(fits),
0, fits)``.  The jax backend runs this as a chunked `lax.scan`; here the
same chunk walk is a Pallas kernel so the free matrix lives in VMEM for
the whole cycle instead of round-tripping through HBM per scan step.

Tiling: grid = (nch,) with the single chunk axis sequential
("arbitrary") — chunk c+1 must observe chunk c's claims.  The free
matrix is the output block itself: its index map is constant, so it
stays resident in VMEM across grid steps (initialised from the input at
``program_id == 0``) and is written back once, after the last step.
Per grid step the kernel holds:

  crow     (chunk, Wp)   uint8 compat mask block (VMEM), Wp a lane
                         multiple; widened once per live chunk into
  crow_s   (chunk, Wp)   a 32-bit VMEM scratch, whose rows the cohort
                         loop reads at a dynamic sublane offset
  free     (R, Wp)       THE carry (VMEM output block)
  takes    (chunk, Wp)   int32 output block (VMEM)
  want     (chunk*R,)    request scalars (SMEM); each cohort's R values
                         are gathered into an (R, 1) column
  demand   (chunk,)      cohort demand scalars (SMEM)
  cmin     (R,)          the chunk's componentwise-min live request (SMEM)
  left     (1,)          remaining claim budget: SMEM scratch carry
  ran      (1,)          int32 SMEM output — 1 if the chunk executed

The per-chunk SMEM operands are (nch, 1, n) arrays blocked (1, 1, n):
Mosaic requires a block's last two dims to be tile-aligned or whole,
and (1, n) is whole for every n.

The drain guard is identical to the jax backend's: a chunk whose
componentwise-minimum request exceeds every worker's free vector in some
resource is provably empty and skips its cohort loop via pl.when (takes
rows are pre-zeroed, so skipping is claim-exact).

dtype passes through: float64 only in interpret mode (bit-identical to
the jax/numpy backends on any quantities), float32 compiled for the TPU
(Mosaic has no float64; exact on the integer quantities the matchmaker
admits in float32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.matchmaker.base import FIT_EPS
from repro.core.matchmaker.jax_backend import _ZERO_WANT_BIG, exact_floor_f32


def _column(ref, base, n: int, dt):
    """The SMEM scalars ``ref[0, 0, base:base+n]`` as an (n, 1) vector."""
    rows = lax.broadcasted_iota(jnp.int32, (n, 1), 0)
    col = jnp.zeros((n, 1), dt)
    for r in range(n):
        col = jnp.where(rows == r, ref[0, 0, base + r], col)
    return col


def _cumsum_lanes(x):
    """Inclusive prefix sum along the lane axis of a (1, Wp) row, as
    log2(Wp) shift-and-add steps (Mosaic has no cumsum).  The summation
    order differs from `jnp.cumsum`, but the sums are of whole numbers
    in the exactly representable range, so every order gives the same
    bits."""
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    k = 1
    while k < x.shape[1]:
        x = x + jnp.where(lane >= k, pltpu.roll(x, k, 1), 0.0)
        k *= 2
    return x


def _waterfill_kernel(
    freeT_ref,    # (R, Wp)       VMEM  initial free matrix (read once)
    left_ref,     # (1,)          SMEM  initial claim budget (read once)
    want_ref,     # (1, 1, chunk*R) SMEM request scalars, cohort-major
    d_ref,        # (1, 1, chunk)   SMEM cohort demand
    cmin_ref,     # (1, 1, R)       SMEM chunk componentwise-min request
    crow_ref,     # (1, chunk, Wp) VMEM uint8 compat mask
    takes_ref,    # (1, chunk, Wp) VMEM int32 out
    ran_ref,      # (1, 1, 1)       SMEM int32 out — 1 if the chunk ran
    free_ref,     # (R, Wp)       VMEM  out — the free carry, final
    left_s,       # (1,)          SMEM  scratch: budget carry
    crow_s,       # (chunk, Wp)   VMEM  scratch: widened compat mask
    *,
    chunk: int,
):
    R = free_ref.shape[0]
    dt = free_ref.dtype

    @pl.when(pl.program_id(0) == 0)
    def _init():
        free_ref[...] = freeT_ref[...]
        left_s[0] = left_ref[0]

    # drain guard — same arithmetic as the jax backend's chunk_step: a
    # worker below the chunk's min live request in ANY resource fits no
    # cohort of the chunk; all workers failing somewhere skips the loop
    cmin = _column(cmin_ref, 0, R, dt)
    ok = jnp.where(free_ref[...] >= cmin * (1.0 - 2 * FIT_EPS), 1.0, 0.0)
    alive = (jnp.max(jnp.min(ok, axis=0)) > 0) & (left_s[0] > 0)

    takes_ref[...] = jnp.zeros_like(takes_ref)
    ran_ref[0, 0, 0] = alive.astype(jnp.int32)

    @pl.when(alive)
    def _run():
        crow_s[...] = crow_ref[0].astype(jnp.int32).astype(dt)

        def body(c, left):
            want = _column(want_ref, c * R, R, dt)
            pos = want > 0
            safe = jnp.where(pos, want, 1.0)
            big = jnp.where(pos, 0.0, _ZERO_WANT_BIG)
            d = jnp.minimum(d_ref[0, 0, c], left)
            free = free_ref[...]
            ratio = free / safe + big
            fits = jnp.maximum(jnp.floor(
                jnp.min(ratio, axis=0, keepdims=True) + FIT_EPS), 0.0)
            if dt == jnp.float32:
                fits = exact_floor_f32(fits, free, want)
            fits = jnp.minimum(fits, d) * crow_s[pl.ds(c, 1), :]
            cum = _cumsum_lanes(fits)
            take = jnp.clip(d - (cum - fits), 0.0, fits)
            takes_ref[0, pl.ds(c, 1), :] = jnp.round(take).astype(jnp.int32)
            free_ref[...] = free - want * take
            return left - jnp.sum(take)

        left_s[0] = lax.fori_loop(0, chunk, body, left_s[0])


@functools.partial(jax.jit, static_argnames=("interpret",))
def waterfill_pallas(
    freeT: jax.Array,      # (R, Wp)
    left: jax.Array,       # (1,)
    want: jax.Array,       # (nch, 1, chunk*R)
    demand: jax.Array,     # (nch, 1, chunk)
    chunk_min: jax.Array,  # (nch, 1, R)
    crow: jax.Array,       # (nch, chunk, Wp) uint8
    *,
    interpret: bool = False,
):
    """Returns (takes (nch, chunk, Wp) int32, ran (nch, 1, 1) int32,
    freeT_after (R, Wp))."""
    nch, chunk, Wp = crow.shape
    R = freeT.shape[0]
    dt = freeT.dtype

    def smem(n):                # one chunk's row of an (nch, 1, n) array
        return pl.BlockSpec((1, 1, n), lambda i: (i, 0, 0),
                            memory_space=pltpu.SMEM)

    return pl.pallas_call(
        functools.partial(_waterfill_kernel, chunk=chunk),
        grid=(nch,),
        in_specs=[
            pl.BlockSpec((R, Wp), lambda i: (0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            smem(chunk * R),
            smem(chunk),
            smem(R),
            pl.BlockSpec((1, chunk, Wp), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, Wp), lambda i: (i, 0, 0)),
            smem(1),
            pl.BlockSpec((R, Wp), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nch, chunk, Wp), jnp.int32),
            jax.ShapeDtypeStruct((nch, 1, 1), jnp.int32),
            jax.ShapeDtypeStruct((R, Wp), dt),
        ],
        scratch_shapes=[
            pltpu.SMEM((1,), dt),
            pltpu.VMEM((chunk, Wp), dt),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(freeT, left, want, demand, chunk_min, crow)
