"""Compile the main path's blocked-ELL kernels for a described TPU v5e chip.

Nothing runs here: each test lowers a kernel at the shapes the chip path uses
and hands it to the TPU compiler (Mosaic for the Pallas body, XLA around it),
which raises what the chip would raise -- unsupported gathers, misaligned
blocks, more VMEM than a kernel may use.  Interpret-mode tests cannot see any
of that.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and each test worker imports every
test file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.spmv_ell import num_row_tiles, spmm_ell, spmv_ell

#: ``thermal_like(1_048_576)`` on one rank: a 1024 x 1024 5-point stencil,
#: diag block K = 5, empty off block padded to K = 1 over a 1-slot halo
STENCIL_ROWS = 1_048_576
STENCIL_K = 5
#: the same stencil over four ranks: 262,144 rows each, one off entry per row
#: over a 2,048-slot halo (the two neighbouring grid lines)
RANK_ROWS = 262_144
HALO = 2_048
#: ``random_block(262_144, 16 / 262_144)`` over four ranks: diag K = 17,
#: off K = 29 over a 193,104-slot halo
RANDOM_DIAG_K = 17
RANDOM_OFF_K = 29
RANDOM_HALO = 193_104
#: ``DistributedSpMV.matmat`` payload width checked on the chip
MM_WIDTH = 8


@pytest.fixture(scope="module")
def v5e():
    """One described (not attached) v5e chip, with the persistent compile
    cache off: a described-chip compile can be written to the cache but
    never read back without a chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler can be loaded in this process
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **kw):
    compiled = fn.lower(*args, interpret=False, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize(
    "rows,k,n",
    [
        (STENCIL_ROWS, STENCIL_K, STENCIL_ROWS),  # 1-rank diag block
        (STENCIL_ROWS, 1, 1),  # 1-rank off block over its 1-slot halo
        (RANK_ROWS, STENCIL_K, RANK_ROWS),  # 4-rank diag block
        (RANK_ROWS, 1, HALO),  # 4-rank off block
        (RANK_ROWS, RANDOM_DIAG_K, RANK_ROWS),  # random_block diag block
        (RANK_ROWS, RANDOM_OFF_K, RANDOM_HALO),  # random_block off block
    ],
)
@pytest.mark.parametrize("masked", [False, True])
def test_spmv_ell_compiles(v5e, rows, k, n, masked):
    data = _spec((rows, k), jnp.float32, v5e)
    cols = _spec((rows, k), jnp.int32, v5e)
    x = _spec((n,), jnp.float32, v5e)
    kw = {}
    if masked:
        kw["tile_mask"] = _spec((num_row_tiles(rows),), jnp.int32, v5e)
    _compile(spmv_ell, data, cols, x, **kw)


@pytest.mark.parametrize(
    "rows,k,n",
    [
        (STENCIL_ROWS, STENCIL_K, STENCIL_ROWS),  # the size chip_smoke.py runs
        (STENCIL_ROWS, 1, 1),
        (RANK_ROWS, RANDOM_OFF_K, RANDOM_HALO),
    ],
)
@pytest.mark.parametrize("masked", [False, True])
def test_spmm_ell_compiles(v5e, rows, k, n, masked):
    data = _spec((rows, k), jnp.float32, v5e)
    cols = _spec((rows, k), jnp.int32, v5e)
    x = _spec((n, MM_WIDTH), jnp.float32, v5e)
    kw = {}
    if masked:
        kw["tile_mask"] = _spec((num_row_tiles(rows),), jnp.int32, v5e)
    _compile(spmm_ell, data, cols, x, **kw)
