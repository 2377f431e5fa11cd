"""The KV arena's geometry comes from the model family's declared layout:
K and V of ``n_kv x dh`` per layer for the attention families, one latent
row per layer for MLA. Each growth makes one allocation per stream per
layer, and retirement frees every one."""

import jax.numpy as jnp

from repro.alloc.chunks import CHUNK_SIZE
from repro.configs import deepseek_v2_lite, h2o_danube3_4b, smollm_135m
from repro.serve.engine import EngineConfig, ServeEngine


def engine_for(cfg, n_chunks):
    # the KV arena's accounting needs no weights
    return ServeEngine(cfg, None, EngineConfig(max_batch=1, max_len=4096,
                                               n_chunks=n_chunks))


def record_mallocs(kv):
    alloc = kv.arena.allocator
    sizes, frees = [], []
    malloc, free = alloc.malloc, alloc.free

    def counted_malloc(size):
        sizes.append(size)
        return malloc(size)

    def counted_free(a):
        frees.append(a.req_size)
        return free(a)

    alloc.malloc, alloc.free = counted_malloc, counted_free
    return sizes, frees


def test_latent_geometry_makes_one_allocation_per_layer_per_growth():
    cfg = deepseek_v2_lite.FULL
    eng = engine_for(cfg, n_chunks=27 * 3)
    kv = eng.kv
    assert kv.config.streams == ("latent",)
    assert (kv.config.n_layers, kv.config.token_elems) == (27, 512 + 64)
    # 576-element bf16 rows, 2 to a 1152-lane row, 896 rows of 16-row tiles
    assert (kv.config.tokens_per_row, kv.config.chunk_rows) == (2, 896)
    assert kv.config.chunk_tokens == 1792
    sizes, frees = record_mallocs(kv)
    kv.add_sequence(0, 1000)
    assert sizes == [CHUNK_SIZE] * 27
    kv.append_tokens(0, 792)  # fills the first chunk exactly: no growth
    assert len(sizes) == 27
    kv.append_tokens(0, 1)  # 1793 tokens: at least 1.5 x 1792 -> 2 chunks
    assert sizes[27:] == [CHUNK_SIZE] * 27
    assert sorted(kv.seqs[0].allocs) == [(layer, "latent") for layer in range(27)]
    kv.free_sequence(0)
    assert len(frees) == 54 and sorted(frees) == sorted(sizes)
    assert kv.arena.allocator.stats.active_bytes == 0


def test_attention_geometry_keeps_k_and_v_per_layer_as_before():
    # danube-3-4b's 8 x 120 rows: 1088 tokens a chunk; admitted at 1000
    # tokens, then grown past 1088: 2 allocations per layer each time, of
    # the same sizes the K/V-only cache made
    eng = engine_for(h2o_danube3_4b.FULL, n_chunks=24 * 2 * 3)
    kv = eng.kv
    assert kv.config.streams == ("k", "v")
    assert (kv.config.n_kv, kv.config.head_dim, kv.config.chunk_tokens) == (8, 120, 1088)
    sizes, frees = record_mallocs(kv)
    kv.add_sequence(0, 1000)
    assert sizes == [CHUNK_SIZE] * 48
    kv.append_tokens(0, 89)  # 1089 tokens: at least 1.5 x 1088 -> 2 chunks
    assert sizes[48:] == [CHUNK_SIZE] * 48
    assert sorted(kv.seqs[0].allocs) == sorted(
        (layer, s) for layer in range(24) for s in ("k", "v"))
    kv.free_sequence(0)
    assert len(frees) == 96 and kv.arena.allocator.stats.active_bytes == 0


def test_smollm_geometry_is_unchanged():
    eng = engine_for(smollm_135m.FULL, n_chunks=60)
    c = eng.kv.config
    assert (c.n_layers, c.n_kv, c.head_dim, c.streams) == (30, 3, 64, ("k", "v"))
    assert c.dtype == jnp.bfloat16 and c.chunk_tokens == 5440
    sizes, _ = record_mallocs(eng.kv)
    eng.kv.add_sequence(0, 256)
    assert sizes == [CHUNK_SIZE] * 60
