"""Jit'd public wrappers for the Pallas kernels.

``interpret`` is threaded through for CPU validation (the kernels target
TPU; interpret=True executes the kernel body in Python). The wrappers also
bridge the host-side allocator metadata (extent tables) to the device-side
int32 arrays the kernels prefetch.
"""

from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .ref import (
    stitch_gather_ref,
    stitch_scatter_ref,
    stitched_decode_attention_ref,
)
from .stitch_copy import stitch_gather, stitch_scatter
from .stitched_attention import stitched_decode_attention


def chunk_map_from_extents(extents, pad_to: int | None = None) -> jax.Array:
    """Flatten an extent table (list of (start, n) runs) into the dense
    logical->physical chunk map consumed by the kernels."""
    ids: List[int] = []
    for e in extents:
        ids.extend(range(e.start, e.start + e.n))
    if pad_to is not None:
        assert len(ids) <= pad_to, f"extents cover {len(ids)} chunks > pad {pad_to}"
        ids = ids + [0] * (pad_to - len(ids))
    return jnp.asarray(np.asarray(ids, dtype=np.int32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather(arena, chunk_map, *, interpret: bool = False):
    return stitch_gather(arena, chunk_map, interpret=interpret)


# the arena is donated: the kernel writes it in place
@functools.partial(jax.jit, static_argnames=("interpret",), donate_argnums=(0,))
def scatter(arena, chunk_map, values, *, interpret: bool = False):
    return stitch_scatter(arena, chunk_map, values, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("n_kv", "interpret"))
def decode_attention(
    q, k_arena, v_arena, page_table, seq_lens, page_table_v=None, *,
    n_kv: int, interpret: bool = False,
):
    return stitched_decode_attention(
        q, k_arena, v_arena, page_table, seq_lens,
        n_kv=n_kv, page_table_v=page_table_v, interpret=interpret,
    )


# reference implementations (jit'd): the oracles the kernels are checked
# against, in tests and in the chip smoke
gather_ref = jax.jit(stitch_gather_ref)
scatter_ref = jax.jit(stitch_scatter_ref)
decode_attention_ref = jax.jit(stitched_decode_attention_ref,
                               static_argnames=("n_kv",))
