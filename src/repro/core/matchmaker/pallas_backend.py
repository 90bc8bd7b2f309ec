"""Pallas matchmaker: the single-cycle water-fill as a fused TPU kernel.

`make_matchmaker("pallas")` — identical host-side plumbing to the jax
backend (same `_prep` padding/ordering, same scatter-back), but the
chunked claim loop runs as ONE Pallas program with the free matrix
resident in VMEM across every chunk (src/repro/kernels/waterfill/).
The kernel is compiled for the TPU; ``interpret=True`` evaluates it on
the host instead, which is how the CPU tests pin bit-identity with the
jax and numpy backends (in float64 as well as float32).

Multi-cycle fusion (`match_cycles`) and batched previews are inherited
from the jax backend: the K-cycle batch is an outer lax.scan around the
identical chunk arithmetic, so a pallas-selected pool still gets
device-resident fused batches — the kernel covers the steady-state
per-cycle path, which dominates the paper's demand >> supply
negotiation profile.
"""
from __future__ import annotations

from repro.core.matchmaker.jax_backend import JaxMatchmaker
# a module reference, not the function: the kernel imports FIT_EPS from
# repro.core, so when the kernel package is imported first this module
# runs while `ops` is still initialising
from repro.kernels.waterfill import ops as waterfill_ops


class PallasMatchmaker(JaxMatchmaker):
    """The Pallas water-fill backend (`make_matchmaker("pallas")`)."""

    name = "pallas"

    def __init__(self, *, dtype: str | None = None, chunk: int = 64,
                 unroll: int = 4, interpret: bool = False):
        super().__init__(dtype=dtype, chunk=chunk, unroll=unroll)
        self.interpret = interpret

    def _run(self, put, dt, freeT, left, req_o, safe, big, d_o, crow_o,
             chunk_min, nch, chunk, R, Wp):
        # safe/big are re-derived from the requests inside the kernel
        return waterfill_ops.waterfill(
            put(freeT, dt), put(left, dt),
            put(req_o.reshape(nch, chunk, R), dt),
            put(d_o.reshape(nch, chunk), dt),
            put(crow_o.reshape(nch, chunk, Wp)), put(chunk_min, dt),
            dtype=dt, interpret=self.interpret,
        )
