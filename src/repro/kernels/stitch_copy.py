"""Stitched gather/scatter Pallas kernels — the TPU analogue of cuMemMap.

On GPU, GMLake's stitch re-maps page tables so a virtually-contiguous tensor
reads non-contiguous physical chunks for free. TPUs have no user page tables,
so the indirection moves into the kernel: a scalar-prefetched ``chunk_map``
(logical chunk -> physical chunk id) names the source (gather) or target
(scatter) chunk of each HBM->HBM DMA (chunks are 2 MB — far above the
~512 B threshold below which TPU DMA efficiency degrades).

Both kernels are pure data movement and never stage through VMEM: every
operand stays in HBM (``memory_space=ANY``) and the kernel keeps up to
``INFLIGHT`` whole-chunk DMAs outstanding. A chunk is whatever the arena's
trailing dimensions are (``Arena`` uses lane-dense ``(rows, 128k)`` chunks),
so one kernel serves flat tensors and token-structured KV alike.
``stitch_scatter`` aliases the arena in/out (``input_output_aliases``) so
untouched chunks are preserved without copying the whole arena.
"""

from __future__ import annotations

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: outstanding chunk DMAs per kernel (one DMA semaphore each)
INFLIGHT = 8


def _pipelined_copies(n: int, copy) -> None:
    """Run ``copy(i)`` for i in [0, n) with at most INFLIGHT in flight."""
    k = min(n, INFLIGHT)
    for i in range(k):
        copy(i).start()

    def body(i, carry):
        copy(i).wait()

        @pl.when(i + k < n)
        def _():
            copy(i + k).start()

        return carry

    jax.lax.fori_loop(0, n, body, 0)


def _call(kernel, n_logical, out_shape, operands, *, aliases, interpret):
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * (len(operands) - 1),
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA((min(n_logical, INFLIGHT),))],
        ),
        out_shape=out_shape,
        input_output_aliases=aliases,
        interpret=interpret,
        name=kernel.__name__,
    )(*operands)


def stitch_gather(
    arena: jax.Array,  # (n_phys_chunks, *chunk_shape)
    chunk_map: jax.Array,  # (n_logical_chunks,) int32: logical -> physical
    *,
    interpret: bool = False,
) -> jax.Array:
    """Gather logical chunks out of the arena: out[i] = arena[chunk_map[i]]."""
    n_logical = chunk_map.shape[0]

    def stitch_gather_kernel(cmap_ref, src_ref, dst_ref, sems):
        _pipelined_copies(n_logical, lambda i: pltpu.make_async_copy(
            src_ref.at[cmap_ref[i]], dst_ref.at[i], sems.at[i % INFLIGHT]))

    return _call(
        stitch_gather_kernel, n_logical,
        jax.ShapeDtypeStruct((n_logical,) + arena.shape[1:], arena.dtype),
        (chunk_map, arena), aliases={}, interpret=interpret,
    )


def stitch_scatter(
    arena: jax.Array,  # (n_phys_chunks, *chunk_shape)
    chunk_map: jax.Array,  # (n_logical_chunks,) int32: logical -> physical
    values: jax.Array,  # (n_logical_chunks, *chunk_shape)
    *,
    interpret: bool = False,
) -> jax.Array:
    """Scatter logical chunks into the arena: arena[chunk_map[i]] = values[i].

    The arena is aliased in/out, so this lowers to in-place chunk-granular
    DMAs — the write-side of the stitch.
    """
    n_logical = chunk_map.shape[0]
    if values.shape != (n_logical,) + arena.shape[1:] or values.dtype != arena.dtype:
        raise ValueError(
            f"values {values.shape} {values.dtype} do not match {n_logical} "
            f"chunks of arena {arena.shape} {arena.dtype}"
        )

    def stitch_scatter_kernel(cmap_ref, val_ref, arena_in_ref, arena_out_ref, sems):
        del arena_in_ref  # aliased to arena_out_ref
        _pipelined_copies(n_logical, lambda i: pltpu.make_async_copy(
            val_ref.at[i], arena_out_ref.at[cmap_ref[i]], sems.at[i % INFLIGHT]))

    # alias indices count the scalar-prefetch operand: 0=chunk_map,
    # 1=values, 2=arena -> output 0
    return _call(
        stitch_scatter_kernel, n_logical,
        jax.ShapeDtypeStruct(arena.shape, arena.dtype),
        (chunk_map, values, arena), aliases={2: 0}, interpret=interpret,
    )
