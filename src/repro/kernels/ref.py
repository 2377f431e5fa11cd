"""Pure-jnp oracles for every Pallas kernel (the correctness contract)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def stitch_gather_ref(arena: jax.Array, chunk_map: jax.Array) -> jax.Array:
    """out[i] = arena[chunk_map[i]]"""
    return jnp.take(arena, chunk_map, axis=0)


def stitch_scatter_ref(
    arena: jax.Array, chunk_map: jax.Array, values: jax.Array
) -> jax.Array:
    """arena[chunk_map[i]] = values[i] (functional)."""
    return arena.at[chunk_map].set(values)


def stitched_decode_attention_ref(
    q: jax.Array,  # (B, H, D)
    k_arena: jax.Array,  # (n_phys, R, W): g = W // (KVH*D) tokens per row
    v_arena: jax.Array,  # (n_phys, R, W)
    page_table: jax.Array,  # (B, C) int32
    seq_lens: jax.Array,  # (B,) int32
    page_table_v: jax.Array | None = None,
    *,
    n_kv: int,
    scale: float | None = None,
) -> jax.Array:
    """Gather-then-softmax reference for the stitched decode attention."""
    batch, n_heads, head_dim = q.shape
    _, rows, lanes = k_arena.shape
    chunk_tokens = rows * lanes // (n_kv * head_dim)
    group = n_heads // n_kv
    n_chunks = page_table.shape[1]
    scale = (head_dim**-0.5) if scale is None else scale
    if page_table_v is None:
        page_table_v = page_table

    # materialise each sequence's logical KV: (B, C*T_c, KVH, D); tokens
    # are packed in order, so a chunk's (R, W) rows flatten to token rows
    k = jnp.take(k_arena, page_table, axis=0).reshape(
        batch, n_chunks * chunk_tokens, n_kv, head_dim
    )
    v = jnp.take(v_arena, page_table_v, axis=0).reshape(
        batch, n_chunks * chunk_tokens, n_kv, head_dim
    )
    pos = jnp.arange(n_chunks * chunk_tokens)[None, :]  # (1, T)
    valid = pos < seq_lens[:, None]  # (B, T)

    qg = (q * scale).reshape(batch, n_kv, group, head_dim).astype(jnp.float32)
    s = jnp.einsum("bkgd,btkd->bkgt", qg, k.astype(jnp.float32), precision=HIGHEST)
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    o = jnp.einsum("bkgt,btkd->bkgd", p, v.astype(jnp.float32), precision=HIGHEST)
    return o.reshape(batch, n_heads, head_dim).astype(q.dtype)
