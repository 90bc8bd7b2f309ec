"""The device matchmaker compiles for a TPU v5e at the 1m-job tier.

No chip is needed: the TPU compiler is installed with JAX and compiles
for a described, unattached v5e chip.  This catches what interpret mode
and the CPU backend cannot: Mosaic's tiling and lowering rules for the
Pallas water-fill, float64 leaking into the float32 path, and programs
that do not fit the chip.  Shapes are the `bench_matchmaking` 1m tier
(C=16,384 cohorts, W=1,024 workers) in float32, the dtype the backends
pick on a TPU; the fair-share pass compiles at the `federation.fairshare`
cell's bucket (128 cohorts, 640 workers, 32 entries).

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and pytest-xdist workers all
import this file.
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

from repro.core.matchmaker.jax_backend import (  # noqa: E402
    _build_cycles_scan, _build_fairshare, _build_scan,
)
from repro.kernels.waterfill.kernel import waterfill_pallas  # noqa: E402

C, W, R = 16_384, 1_024, 6        # the 1m tier
CHUNK, UNROLL, K = 64, 4, 8       # backend defaults; 8 fused cycles
NCH = C // CHUNK
F32 = jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:            # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
            for shape, dt in specs]


def test_match_scan_compiles_for_v5e(one_chip):
    args = _shapes(one_chip,
                   ((R, W), F32), ((), F32),
                   ((NCH, CHUNK, R), F32), ((NCH, CHUNK, R), F32),
                   ((NCH, CHUNK, R), F32), ((NCH, CHUNK), F32),
                   ((NCH, CHUNK, W), jnp.uint8), ((NCH, R), F32))
    with jax.enable_x64(False):
        compiled = _build_scan(CHUNK, UNROLL).lower(*args).compile()
    assert compiled.memory_analysis().output_size_in_bytes >= (
        C * W * 4)                      # the int32 takes come back


def test_match_cycles_compiles_for_v5e(one_chip):
    args = _shapes(one_chip,
                   ((R, W), F32), ((NCH, CHUNK), F32),
                   ((K, NCH, CHUNK), F32), ((K, R, W), F32), ((K,), F32),
                   ((NCH, CHUNK, R), F32), ((NCH, CHUNK, R), F32),
                   ((NCH, CHUNK, R), F32), ((NCH, CHUNK, W), jnp.uint8))
    with jax.enable_x64(False):
        compiled = _build_cycles_scan(CHUNK, UNROLL).lower(*args).compile()
    assert compiled.memory_analysis().output_size_in_bytes >= (
        K * C * W * 4)


def test_pallas_waterfill_compiles_for_v5e(one_chip):
    args = _shapes(one_chip,
                   ((R, W), F32), ((1,), F32),
                   ((NCH, 1, CHUNK * R), F32), ((NCH, 1, CHUNK), F32),
                   ((NCH, 1, R), F32), ((NCH, CHUNK, W), jnp.uint8))
    with jax.enable_x64(False):
        compiled = waterfill_pallas.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fairshare_pass_compiles_for_v5e(one_chip):
    nch, wp, ep = 2, 640, 32
    args = _shapes(one_chip,
                   ((R, wp), F32), ((nch, CHUNK), F32),
                   ((nch, CHUNK, R), F32), ((nch, CHUNK, R), F32),
                   ((nch, CHUNK, R), F32), ((nch, CHUNK, wp), jnp.uint8),
                   ((nch, CHUNK), jnp.int32), ((nch, CHUNK), F32),
                   ((ep,), jnp.int32), ((ep,), jnp.int32), ((ep,), bool),
                   ((5, ep), F32), ((5, ep), F32), ((), F32),
                   ((), jnp.int32))
    with jax.enable_x64(False):
        compiled = _build_fairshare(CHUNK, UNROLL).lower(*args).compile()
    text = compiled.as_text()
    assert "while" in text and "f64" not in text
