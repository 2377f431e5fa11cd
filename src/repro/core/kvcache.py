"""StitchedKVCache: per-sequence KV history backed by the GMLake arena.

The serving-side integration of the paper's technique. vLLM pages KV into
small fixed blocks and pays a table indirection per block; GMLake-style
stitching instead hands each sequence *variable-size* blocks (whole
allocations that grow geometrically), so the page table stays short and the
attention kernel walks long physically-contiguous extents — fewer, larger
DMAs on TPU.

Token layout: one 2 MB chunk holds ``chunk_tokens`` tokens of K (or V) for
ONE layer, packed in order into a lane-dense ``(chunk_rows, row_lanes)``
slab: ``tokens_per_row`` token rows of ``n_kv * head_dim`` elements share a
lane row, so ``row_lanes`` is a multiple of 128 and any head geometry tiles
without padding (smollm-135m's 3x64 and h2o-danube-3-4b's 8x120 rows do not
divide 2 MB). The few bytes a chunk cannot fit stay unused. K and V of every
layer share the single arena (one memory lake), each with its own
allocation per sequence.
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..alloc.caching_allocator import Allocation
from ..alloc.chunks import CHUNK_SIZE
from ..kernels import ops
from ..kernels.stitched_attention import sublanes
from .arena import LANE, Arena, ArenaConfig
from .trace import TraceRecorder


@dataclass(frozen=True)
class KVCacheConfig:
    n_layers: int
    n_kv: int
    head_dim: int
    dtype: jnp.dtype = jnp.bfloat16
    n_chunks: int = 1024
    #: new allocations grow by at least this fraction of current capacity
    growth: float = 0.5
    interpret: bool = False
    use_reference_ops: bool = False

    @property
    def itemsize(self) -> int:
        return jnp.dtype(self.dtype).itemsize

    @property
    def token_elems(self) -> int:
        return self.n_kv * self.head_dim

    @property
    def token_bytes(self) -> int:
        return self.token_elems * self.itemsize

    @property
    def tokens_per_row(self) -> int:
        return LANE // math.gcd(self.token_elems, LANE)

    @property
    def row_lanes(self) -> int:
        return self.tokens_per_row * self.token_elems

    @property
    def chunk_rows(self) -> int:
        """Lane rows per chunk: whole sublane tiles within CHUNK_SIZE."""
        tile = sublanes(self.dtype)
        rows = CHUNK_SIZE // (self.row_lanes * self.itemsize) // tile * tile
        if rows == 0:
            raise ValueError(
                f"{self.tokens_per_row} x {self.n_kv}x{self.head_dim} token rows "
                f"times {tile} sublanes do not fit one {CHUNK_SIZE}-byte chunk"
            )
        return rows

    @property
    def chunk_tokens(self) -> int:
        return self.chunk_rows * self.tokens_per_row


@dataclass
class _SeqState:
    length: int = 0
    capacity_tokens: int = 0
    # one allocation list per (layer, k|v); growth appends allocations and
    # their extents concatenate into the logical block — the stitch.
    allocs: Dict[Tuple[int, str], List[Allocation]] = field(default_factory=dict)


class StitchedKVCache:
    def __init__(
        self,
        config: KVCacheConfig,
        recorder: Optional[TraceRecorder] = None,
        allocator=None,
    ):
        """``allocator``: any ``repro.alloc`` registry key or backend
        instance, forwarded to the ``Arena`` (default gmlake). Device-side
        access paths need an extent-carrying (stitching) backend; pure
        accounting runs work with any."""
        self.config = config
        self.arena = Arena(
            ArenaConfig(
                n_chunks=config.n_chunks,
                dtype=config.dtype,
                interpret=config.interpret,
                use_reference_ops=config.use_reference_ops,
                chunk_shape=(config.chunk_rows, config.row_lanes),
            ),
            allocator=allocator,
            recorder=recorder,
        )
        self.seqs: Dict[int, _SeqState] = {}

    # ------------------------------------------------------------------
    # host-side sequence management
    # ------------------------------------------------------------------
    def add_sequence(self, seq_id: int, n_tokens: int) -> None:
        assert seq_id not in self.seqs
        state = _SeqState()
        self.seqs[seq_id] = state
        self._grow_to(state, n_tokens)
        state.length = n_tokens

    def append_tokens(self, seq_id: int, n: int = 1) -> None:
        state = self.seqs[seq_id]
        if state.length + n > state.capacity_tokens:
            want = max(
                state.length + n,
                int(state.capacity_tokens * (1.0 + self.config.growth)),
            )
            self._grow_to(state, want)
        state.length += n

    def free_sequence(self, seq_id: int) -> None:
        state = self.seqs.pop(seq_id)
        for allocs in state.allocs.values():
            for a in allocs:
                self.arena.free(a)

    def _grow_to(self, state: _SeqState, n_tokens: int) -> None:
        c = self.config
        need_chunks = -(-n_tokens // c.chunk_tokens)
        have_chunks = state.capacity_tokens // c.chunk_tokens
        if need_chunks <= have_chunks:
            return
        delta = (need_chunks - have_chunks) * self.arena.config.chunk_elems
        for layer in range(c.n_layers):
            for kv in ("k", "v"):
                key = (layer, kv)
                state.allocs.setdefault(key, []).append(
                    self.arena.alloc_elems(delta, f"kv.{kv}.L{layer}")
                )
        state.capacity_tokens = need_chunks * c.chunk_tokens

    # ------------------------------------------------------------------
    # device-side access
    # ------------------------------------------------------------------
    def _extent_chunks(self, seq_id: int, layer: int, kv: str) -> List[int]:
        self.arena.require_stitching()
        out: List[int] = []
        for a in self.seqs[seq_id].allocs[(layer, kv)]:
            for e in a.block.extents:
                out.extend(range(e.start, e.stop))
        return out

    def page_table(
        self, seq_ids: List[int], layer: int, kv: str, pad_chunks: Optional[int] = None
    ) -> Tuple[jax.Array, jax.Array]:
        """(B, C) physical-chunk table + (B,) seq lengths for the kernels."""
        rows = [self._extent_chunks(s, layer, kv) for s in seq_ids]
        width = pad_chunks or max(len(r) for r in rows)
        table = np.zeros((len(rows), width), np.int32)
        for i, r in enumerate(rows):
            assert len(r) <= width
            table[i, : len(r)] = r
        lens = np.array([self.seqs[s].length for s in seq_ids], np.int32)
        return jnp.asarray(table), jnp.asarray(lens)

    def write_tokens(
        self, seq_id: int, layer: int, kv: str, start: int, tokens: jax.Array
    ) -> None:
        """Write ``tokens`` (T, KVH, D) at logical position ``start``."""
        c = self.config
        chunks = np.asarray(self._extent_chunks(seq_id, layer, kv), np.int32)
        pos = start + np.arange(tokens.shape[0])
        in_chunk = pos % c.chunk_tokens
        self.arena.buf = _scatter_token_rows(
            self.arena.buf,
            jnp.asarray(chunks[pos // c.chunk_tokens]),
            jnp.asarray((in_chunk // c.tokens_per_row).astype(np.int32)),
            jnp.asarray((in_chunk % c.tokens_per_row * c.token_elems).astype(np.int32)),
            tokens.reshape(tokens.shape[0], c.token_elems).astype(c.dtype),
        )

    def decode_attention(self, seq_ids: List[int], layer: int, q: jax.Array) -> jax.Array:
        """q: (B, H, D) one token per sequence -> (B, H, D).

        K and V share the arena buffer; each carries its own page table.
        """
        c = self.config
        ptk, lens = self.page_table(seq_ids, layer, "k")
        ptv, _ = self.page_table(seq_ids, layer, "v", pad_chunks=ptk.shape[1])
        buf = self.arena.buf
        if c.use_reference_ops:
            return ops.decode_attention_ref(q, buf, buf, ptk, lens, ptv, n_kv=c.n_kv)
        return ops.decode_attention(
            q, buf, buf, ptk, lens, ptv, n_kv=c.n_kv, interpret=c.interpret
        )

    # ------------------------------------------------------------------
    def utilization(self) -> float:
        return self.arena.utilization


@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_token_rows(buf, chunk, row, lane0, vals):
    """buf[chunk[t], row[t], lane0[t]:lane0[t] + E] = vals[t] for every t."""
    lanes = lane0[:, None] + jnp.arange(vals.shape[1], dtype=jnp.int32)[None, :]
    return buf.at[chunk[:, None], row[:, None], lanes].set(vals)
