"""Water-fill entry point: shape adaptation for the Pallas kernel.

`waterfill` takes the matchmaker's chunked device layout — the same
(nch, chunk, R) / (R, Wp) tensors the jax backend's scan consumes —
lays the per-cohort scalars out as one row per chunk for the
kernel's SMEM blocks, and runs
the kernel.  It compiles for the TPU unless the caller passes
``interpret=True``: the identical program evaluated by XLA on the host,
which is how the CPU differential suite pins bit-identity against the
jax and numpy backends.  Nothing picks interpret mode by itself, so a
TPU caller can never run the interpreter by accident.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.kernels.waterfill.kernel import waterfill_pallas
from repro.kernels.waterfill.ref import waterfill_reference


def waterfill(
    freeT: np.ndarray,       # (R, Wp)
    left: float,             # claim budget (may be inf)
    want: np.ndarray,        # (nch, chunk, R)
    demand: np.ndarray,      # (nch, chunk)
    crow: np.ndarray,        # (nch, chunk, Wp) uint8
    chunk_min: np.ndarray,   # (nch, R)
    *,
    dtype,
    interpret: bool = False,
):
    """Returns (takes (nch, chunk, Wp) int32, freeT_after (R, Wp),
    ran (nch,) bool) — the jax backend's `_run` contract."""
    nch = crow.shape[0]
    row = lambda x: jnp.asarray(x.reshape(nch, 1, -1), dtype=dtype)
    takes, ran, free_out = waterfill_pallas(
        jnp.asarray(freeT, dtype=dtype),
        jnp.full((1,), left, dtype=dtype),
        row(want), row(demand), row(chunk_min),
        jnp.asarray(crow),                           # uint8 mask
        interpret=interpret,
    )
    return takes, free_out, ran.reshape(nch) != 0


__all__ = ["waterfill", "waterfill_reference"]
