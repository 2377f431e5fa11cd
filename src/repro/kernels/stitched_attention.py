"""Stitched decode attention: flash-decoding directly over the KV arena.

The serving engine stores each sequence's KV history as a GMLake allocation —
physically scattered 2 MB chunks made virtually contiguous by an extent
table. This kernel is the consumer side: decode attention for one new token
per sequence, reading K/V straight out of the arena through the per-sequence
page table (no gather materialisation), with the numerically-stable
flash-decoding running max/sum accumulated across chunks in VMEM scratch.

Layout: the arena is lane-dense, ``(n_phys_chunks, R, W)``. A token row of
``KVH * D`` elements is packed ``g = W // (KVH * D)`` to a lane row, so
``W`` is a multiple of 128 and no tile is padded in HBM or VMEM; a chunk
holds ``R * g`` tokens in order (``core/kvcache.py`` picks ``R`` and ``W``).
Grid = (batch, chunks-per-seq, sub-blocks-per-chunk). Each step pulls an
``(R_s, W)`` sub-block of K and of V, sized so that both double buffers fit
the scoped VMEM. The chunk and sub-block axes are minor, so scratch carries
(m, l, acc) across a sequence's tokens. Sub-blocks past the sequence length
map to the last valid one, so the pipeline issues no DMA for them.

Scores come from one MXU matmul per sub-block against a block-diagonal
query: row ``(j, h)`` holds head ``h``'s query under lane slot ``j`` and
kv-head ``h // G``, zero elsewhere. So ``s[(j, h), r]`` is head ``h``'s score
for token ``r * g + j``, with bf16 operands and f32 accumulation and no
lane slicing in the kernel. Each ``(j, h)`` row is its own online-softmax
stream; the wrapper merges the ``g`` streams of a head and keeps the
diagonal ``(slot j, kv-head)`` block of each accumulator row.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(-1e30)
#: VMEM bytes per K (or V) sub-block; K+V double-buffered is 4x this
SUB_BLOCK_BYTES = 1 << 20


def sublanes(dtype) -> int:
    """Rows of one native (sublane, 128) tile: 8 for 32-bit, 16 for bf16."""
    return 32 // jnp.dtype(dtype).itemsize


def sub_block_rows(rows: int, lanes: int, dtype) -> int:
    """Largest divisor of ``rows`` that is a whole number of sublane tiles
    and keeps one (rows, lanes) sub-block within SUB_BLOCK_BYTES."""
    tile = sublanes(dtype)
    if rows % tile:
        raise ValueError(f"chunk rows {rows} are not a multiple of {tile}")
    cap = max(tile, SUB_BLOCK_BYTES // (lanes * jnp.dtype(dtype).itemsize))
    return max(r for r in range(tile, min(rows, cap) + 1, tile) if rows % r == 0)


def _decode_attn_kernel(
    # scalar prefetch
    page_table_k_ref,  # (B, C) int32
    page_table_v_ref,  # (B, C) int32
    seq_lens_ref,  # (B,) int32
    # inputs
    q_ref,  # (1, g*H, W) block-diagonal query
    k_ref,  # (R_s, W)
    v_ref,  # (R_s, W)
    # outputs
    acc_out_ref,  # (1, g*H, W) f32
    m_out_ref,  # (1, g*H, 1) f32
    l_out_ref,  # (1, g*H, 1) f32
    # scratch
    m_ref,  # (g*H, 1) f32
    l_ref,  # (g*H, 1) f32
    acc_ref,  # (g*H, W) f32
    *,
    n_heads: int,
    slots: int,
    sub_rows: int,
):
    b, c, s = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    n_sub = pl.num_programs(2)
    seq_len = seq_lens_ref[b]
    first_tok = (c * n_sub + s) * (sub_rows * slots)

    @pl.when((c == 0) & (s == 0))
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(first_tok < seq_len)
    def _accumulate():
        rows = slots * n_heads
        # token of score (i, r) is first_tok + r * slots + (i // n_heads)
        row_i = jax.lax.broadcasted_iota(jnp.int32, (rows, sub_rows), 0)
        slot = jnp.zeros_like(row_i)
        for j in range(1, slots):
            slot = slot + (row_i >= j * n_heads).astype(jnp.int32)
        pos = (first_tok + slot
               + slots * jax.lax.broadcasted_iota(jnp.int32, (rows, sub_rows), 1))
        valid = pos < seq_len
        k = k_ref[...]
        s_ = jax.lax.dot_general(
            q_ref[0], k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (g*H, R_s)
        s_ = jnp.where(valid, s_, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s_, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s_ - m_new), 0.0)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        v = v_ref[...]
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (g*H, W)
        acc_ref[...] = alpha * acc_ref[...] + pv
        m_ref[...] = m_new

    @pl.when((c == pl.num_programs(1) - 1) & (s == n_sub - 1))
    def _finalize():
        acc_out_ref[0] = acc_ref[...]
        m_out_ref[0] = m_ref[...]
        l_out_ref[0] = l_ref[...]


def stitched_decode_attention(
    q: jax.Array,  # (B, H, D)
    k_arena: jax.Array,  # (n_phys, R, W)
    v_arena: jax.Array,  # (n_phys, R, W)
    page_table: jax.Array,  # (B, C) int32, physical chunk per logical chunk
    seq_lens: jax.Array,  # (B,) int32
    *,
    n_kv: int,
    page_table_v: jax.Array | None = None,  # defaults to sharing page_table
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Decode attention over the stitched KV arena. Returns (B, H, D).

    K and V may live in the same arena buffer under different page tables
    (pass the buffer twice + ``page_table_v``), or in separate buffers under
    one shared table.
    """
    batch, n_heads, head_dim = q.shape
    _, rows, lanes = k_arena.shape
    token_elems = n_kv * head_dim
    if lanes % token_elems or v_arena.shape != k_arena.shape:
        raise ValueError(
            f"arena {k_arena.shape}/{v_arena.shape} does not hold "
            f"{n_kv}x{head_dim} token rows"
        )
    if n_heads % n_kv:
        raise ValueError(f"GQA needs H % KVH == 0, got {n_heads} % {n_kv}")
    slots = lanes // token_elems
    group = n_heads // n_kv
    n_chunks = page_table.shape[1]
    if page_table_v is None:
        page_table_v = page_table
    if page_table.shape != (batch, n_chunks) or page_table_v.shape != page_table.shape:
        raise ValueError(f"page tables {page_table.shape}/{page_table_v.shape} "
                         f"are not (batch={batch}, chunks)")
    sub_rows = sub_block_rows(rows, lanes, k_arena.dtype)
    n_sub = rows // sub_rows
    sub_tokens = sub_rows * slots

    scale = (head_dim**-0.5) if scale is None else scale
    # block-diagonal query (B, g*H, W): row (j, k, h) carries q[k*G + h]
    # at lanes j*KVH*D + k*D ... + D, zeros elsewhere
    q4 = (q.astype(jnp.float32) * scale).reshape(batch, n_kv, group, head_dim)
    q_bd = jnp.einsum(
        "bkhd,ji,kl->bjkhild", q4,
        jnp.eye(slots, dtype=jnp.float32), jnp.eye(n_kv, dtype=jnp.float32),
    ).reshape(batch, slots * n_heads, lanes).astype(k_arena.dtype)

    def kv_block(b, c, s, pt, sl):
        # clamp sub-blocks past the sequence to the last valid one: an
        # unchanged block index makes the pipeline skip the DMA
        last = jnp.maximum(sl[b] - 1, 0) // sub_tokens
        j = jnp.minimum(c * n_sub + s, last)
        return pt[b, j // n_sub], j % n_sub, 0

    rows_q = slots * n_heads
    per_seq = lambda b, c, s, ptk, ptv, sl: (b, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(batch, n_chunks, n_sub),
        in_specs=[
            pl.BlockSpec((1, rows_q, lanes), per_seq),
            pl.BlockSpec((None, sub_rows, lanes),
                         lambda b, c, s, ptk, ptv, sl: kv_block(b, c, s, ptk, sl)),
            pl.BlockSpec((None, sub_rows, lanes),
                         lambda b, c, s, ptk, ptv, sl: kv_block(b, c, s, ptv, sl)),
        ],
        out_specs=[
            pl.BlockSpec((1, rows_q, lanes), per_seq),
            pl.BlockSpec((1, rows_q, 1), per_seq),
            pl.BlockSpec((1, rows_q, 1), per_seq),
        ],
        scratch_shapes=[
            pltpu.VMEM((rows_q, 1), jnp.float32),
            pltpu.VMEM((rows_q, 1), jnp.float32),
            pltpu.VMEM((rows_q, lanes), jnp.float32),
        ],
    )
    acc, m, l = pl.pallas_call(
        functools.partial(_decode_attn_kernel, n_heads=n_heads, slots=slots,
                          sub_rows=sub_rows),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((batch, rows_q, lanes), jnp.float32),
            jax.ShapeDtypeStruct((batch, rows_q, 1), jnp.float32),
            jax.ShapeDtypeStruct((batch, rows_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
        name="stitched_decode_attention",
    )(page_table, page_table_v, seq_lens, q_bd, k_arena, v_arena)

    # keep each row's own (slot j, kv-head) block, then merge the g
    # per-slot softmax streams of every head
    acc = acc.reshape(batch, slots, n_kv, group, slots, n_kv, head_dim)
    acc = jnp.einsum("bjkhild,ji,kl->bjkhd", acc,
                     jnp.eye(slots, dtype=acc.dtype), jnp.eye(n_kv, dtype=acc.dtype))
    acc = acc.reshape(batch, slots, n_heads, head_dim)
    m = m.reshape(batch, slots, n_heads)
    l = l.reshape(batch, slots, n_heads)
    m_all = jnp.max(m, axis=1, keepdims=True)
    w = jnp.exp(m - m_all)
    l_all = jnp.sum(l * w, axis=1)
    o = jnp.sum(acc * w[..., None], axis=1)
    o = o / jnp.where(l_all > 0.0, l_all, 1.0)[..., None]
    return o.astype(q.dtype)
