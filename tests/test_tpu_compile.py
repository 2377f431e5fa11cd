"""Ahead-of-time compiles of the stitch kernels for a described TPU v5e.

Interpret mode runs a kernel body in Python and checks none of the TPU's
tiling or VMEM limits. These tests hand the kernels to the TPU compiler at
their real size: 2 MiB bf16 chunks laid out for the KV geometries of
smollm-135m (3 kv heads x 64) and h2o-danube-3-4b (8 x 120), neither of
whose token rows divides 2 MiB. Nothing runs; a compile that passes is not
a chip run.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and every test worker imports
this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.kvcache import KVCacheConfig
from repro.kernels import ops

GEOMETRIES = {
    # (n_kv, head_dim, n_heads)
    "smollm-135m": (3, 64, 9),
    "h2o-danube-3-4b": (8, 120, 32),
}
N_PHYS, BATCH, SEQ_CHUNKS, N_LOGICAL = 64, 8, 4, 5


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one; keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _arena(geometry, sharding):
    n_kv, head_dim, _ = GEOMETRIES[geometry]
    cfg = KVCacheConfig(n_layers=1, n_kv=n_kv, head_dim=head_dim, n_chunks=N_PHYS)
    assert cfg.chunk_rows * cfg.row_lanes * 2 <= 2 * 2**20
    return cfg, _spec((N_PHYS, cfg.chunk_rows, cfg.row_lanes), jnp.bfloat16, sharding)


def _assert_kernel_compiled(lowered):
    compiled = lowered.compile()  # raises what the chip's compiler would raise
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_stitch_gather_compiles_for_v5e(one_chip, geometry):
    _, arena = _arena(geometry, one_chip)
    cmap = _spec((N_LOGICAL,), jnp.int32, one_chip)
    _assert_kernel_compiled(ops.gather.lower(arena, cmap))


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_stitch_scatter_compiles_for_v5e(one_chip, geometry):
    _, arena = _arena(geometry, one_chip)
    cmap = _spec((N_LOGICAL,), jnp.int32, one_chip)
    values = _spec((N_LOGICAL,) + arena.shape[1:], jnp.bfloat16, one_chip)
    _assert_kernel_compiled(ops.scatter.lower(arena, cmap, values))


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_stitched_decode_attention_compiles_for_v5e(one_chip, geometry):
    cfg, arena = _arena(geometry, one_chip)
    n_heads = GEOMETRIES[geometry][2]
    q = _spec((BATCH, n_heads, cfg.head_dim), jnp.bfloat16, one_chip)
    table = _spec((BATCH, SEQ_CHUNKS), jnp.int32, one_chip)
    lens = _spec((BATCH,), jnp.int32, one_chip)
    _assert_kernel_compiled(ops.decode_attention.lower(
        q, arena, arena, table, lens, table, n_kv=cfg.n_kv))
