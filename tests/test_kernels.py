"""Pallas kernel validation: shape/dtype sweeps against the pure-jnp oracles
(interpret=True executes the TPU kernel bodies on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.alloc.chunks import CHUNK_SIZE
from repro.kernels import ops
from repro.kernels.ref import (
    stitch_gather_ref,
    stitch_scatter_ref,
    stitched_decode_attention_ref,
)

KEY = jax.random.PRNGKey(42)


def rand(key, shape, dtype):
    if jnp.issubdtype(dtype, jnp.integer):
        return jax.random.randint(key, shape, -8, 8).astype(dtype)
    return jax.random.normal(key, shape).astype(dtype)


# ---------------------------------------------------------------------------
# stitch gather / scatter
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
@pytest.mark.parametrize(
    "n_phys,chunk_elems,n_logical", [(8, 256, 3), (32, 512, 32), (4, 128, 1), (64, 1024, 17)]
)
def test_stitch_gather_matches_ref(dtype, n_phys, chunk_elems, n_logical):
    k1, k2 = jax.random.split(KEY)
    arena = rand(k1, (n_phys, chunk_elems), dtype)
    cmap = jax.random.permutation(k2, n_phys)[:n_logical].astype(jnp.int32)
    out = ops.gather(arena, cmap, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(stitch_gather_ref(arena, cmap)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n_phys,chunk_elems,n_logical", [(8, 256, 3), (16, 512, 16)])
def test_stitch_scatter_matches_ref(dtype, n_phys, chunk_elems, n_logical):
    k1, k2, k3 = jax.random.split(KEY, 3)
    arena = rand(k1, (n_phys, chunk_elems), dtype)
    cmap = jax.random.permutation(k2, n_phys)[:n_logical].astype(jnp.int32)
    vals = rand(k3, (n_logical, chunk_elems), dtype)
    ref = np.asarray(stitch_scatter_ref(arena, cmap, vals))
    out = ops.scatter(arena, cmap, vals, interpret=True)  # donates arena
    np.testing.assert_array_equal(np.asarray(out), ref)


def test_scatter_preserves_unmapped_chunks():
    arena = jnp.arange(8 * 128, dtype=jnp.float32).reshape(8, 128)
    before = np.asarray(arena)
    cmap = jnp.array([2, 5], jnp.int32)
    vals = jnp.zeros((2, 128), jnp.float32)
    out = ops.scatter(arena, cmap, vals, interpret=True)
    untouched = [i for i in range(8) if i not in (2, 5)]
    np.testing.assert_array_equal(np.asarray(out)[untouched], before[untouched])
    assert float(jnp.abs(out[jnp.array([2, 5])]).max()) == 0.0


def test_gather_scatter_roundtrip():
    """scatter(gather(x)) through a permutation is the identity, on
    lane-dense (rows, 128) chunks as the arena lays them out."""
    arena = jax.random.normal(KEY, (16, 8, 128), jnp.float32)
    cmap = jax.random.permutation(jax.random.fold_in(KEY, 1), 16).astype(jnp.int32)
    got = ops.gather(arena, cmap, interpret=True)
    back = ops.scatter(jnp.zeros_like(arena), cmap, got, interpret=True)
    np.testing.assert_array_equal(np.asarray(back), np.asarray(arena))


# ---------------------------------------------------------------------------
# stitched decode attention
# ---------------------------------------------------------------------------


def kv_arena(key, n_phys, rows, n_kv, head_dim, dtype):
    """Random lane-dense KV arena (n_phys, rows, lcm(KVH*D, 128)) and the
    number of tokens one of its chunks holds."""
    lanes = np.lcm(n_kv * head_dim, 128)
    return rand(key, (n_phys, rows, lanes), dtype), rows * lanes // (n_kv * head_dim)


ATTN_CASES = [
    # (B, H, KVH, D, chunk_rows, n_chunks, n_phys)
    (1, 8, 8, 64, 16, 2, 4),  # MHA
    (4, 16, 4, 64, 32, 3, 12),  # GQA 4:1
    (2, 12, 1, 128, 16, 4, 8),  # MQA
    (3, 9, 3, 64, 16, 5, 16),  # smollm-like heads: 2 tokens per lane row
]


@pytest.mark.parametrize("case", ATTN_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_matches_ref(case, dtype):
    B, H, KVH, D, R, C, NP = case
    ks = jax.random.split(KEY, 5)
    q = rand(ks[0], (B, H, D), dtype)
    ka, tc = kv_arena(ks[1], NP, R, KVH, D, dtype)
    va, _ = kv_arena(ks[2], NP, R, KVH, D, dtype)
    pt = jax.random.randint(ks[3], (B, C), 0, NP)
    max_len = C * tc
    sl = jax.random.randint(ks[4], (B,), 1, max_len + 1)
    out = ops.decode_attention(q, ka, va, pt, sl, n_kv=KVH, interpret=True)
    ref = stitched_decode_attention_ref(q, ka, va, pt, sl, n_kv=KVH)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), rtol=tol, atol=tol
    )


def test_decode_attention_separate_kv_tables():
    B, H, KVH, D, R, C, NP = 2, 8, 4, 64, 16, 3, 12
    ks = jax.random.split(KEY, 6)
    q = rand(ks[0], (B, H, D), jnp.float32)
    arena, _ = kv_arena(ks[1], NP, R, KVH, D, jnp.float32)
    ptk = jax.random.randint(ks[2], (B, C), 0, NP)
    ptv = jax.random.randint(ks[3], (B, C), 0, NP)
    sl = jnp.array([20, 48], jnp.int32)
    out = ops.decode_attention(q, arena, arena, ptk, sl, ptv, n_kv=KVH, interpret=True)
    ref = stitched_decode_attention_ref(q, arena, arena, ptk, sl, ptv, n_kv=KVH)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_decode_attention_short_sequences():
    """seq_len smaller than one chunk; padding chunks must not contribute."""
    B, H, KVH, D, R, C, NP = 2, 4, 2, 64, 32, 4, 8
    ks = jax.random.split(KEY, 4)
    q = rand(ks[0], (B, H, D), jnp.float32)
    ka, _ = kv_arena(ks[1], NP, R, KVH, D, jnp.float32)
    va, _ = kv_arena(ks[2], NP, R, KVH, D, jnp.float32)
    pt = jax.random.randint(ks[3], (B, C), 0, NP)
    sl = jnp.array([1, 7], jnp.int32)
    out = ops.decode_attention(q, ka, va, pt, sl, n_kv=KVH, interpret=True)
    ref = stitched_decode_attention_ref(q, ka, va, pt, sl, n_kv=KVH)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# arena + kv cache integration (uses the kernels through the public API)
# ---------------------------------------------------------------------------


def test_arena_store_load_roundtrip():
    from repro.core.arena import Arena, ArenaConfig

    a = Arena(ArenaConfig(n_chunks=32, dtype=jnp.float32, interpret=True))
    x = jax.random.normal(KEY, (123, 457), jnp.float32)
    alloc = a.alloc_elems(x.size)
    a.store(alloc, x)
    y = a.load(alloc, x.shape)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    # a second tensor reuses freed chunks
    a.free(alloc)
    alloc2 = a.alloc_elems(x.size)
    assert a.allocator.state_counts["S1"] >= 1


def test_kvcache_grow_and_decode():
    from repro.core.kvcache import KVCacheConfig, StitchedKVCache

    cfg = KVCacheConfig(
        n_layers=1, n_kv=2, head_dim=64, dtype=jnp.float32, n_chunks=64, interpret=True
    )
    kv = StitchedKVCache(cfg)
    kv.add_sequence(0, 100)
    toks = jax.random.normal(KEY, (100, 2, 64), jnp.float32)
    kv.write_tokens(0, 0, "k", 0, toks)
    kv.write_tokens(0, 0, "v", 0, toks)
    kv.append_tokens(0, cfg.chunk_tokens * 2)  # force growth across chunks
    more = jax.random.normal(jax.random.fold_in(KEY, 1), (cfg.chunk_tokens * 2, 2, 64))
    kv.write_tokens(0, 0, "k", 100, more)
    kv.write_tokens(0, 0, "v", 100, more)
    q = jax.random.normal(jax.random.fold_in(KEY, 2), (1, 4, 64), jnp.float32)
    out = kv.decode_attention([0], 0, q)
    # oracle over the dense concatenation
    k = jnp.concatenate([toks, more])
    qg = (q[0] * 64**-0.5).reshape(2, 2, 64)
    s = jnp.einsum("kgd,tkd->kgt", qg, k)
    p = jax.nn.softmax(s, -1)
    exp = jnp.einsum("kgt,tkd->kgd", p, k).reshape(4, 64)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(exp), rtol=1e-4, atol=1e-4)


def dense_decode_oracle(q, k, v, n_kv):
    """Softmax attention of q (H, D) over dense k, v (T, KVH, D), in f32."""
    h, d = q.shape
    qg = np.asarray(q, np.float32).reshape(n_kv, h // n_kv, d) * d**-0.5
    s = np.einsum("kgd,tkd->kgt", qg, np.asarray(k, np.float32))
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("kgt,tkd->kgd", p, np.asarray(v, np.float32)).reshape(h, d)


@pytest.mark.parametrize("n_kv,head_dim,n_heads", [(3, 64, 9), (8, 120, 32)])
def test_kvcache_odd_token_rows_across_stitched_chunks(n_kv, head_dim, n_heads):
    """Token rows that do not divide 2 MB (smollm-135m 3x64, h2o-danube-3-4b
    8x120, bf16), written through write_tokens across a chunk boundary of a
    non-contiguous allocation, and read back by the decode kernel."""
    from repro.core.kvcache import KVCacheConfig, StitchedKVCache

    cfg = KVCacheConfig(n_layers=2, n_kv=n_kv, head_dim=head_dim,
                        n_chunks=24, interpret=True)
    assert CHUNK_SIZE % cfg.token_bytes  # the geometry this test is about
    kv = StitchedKVCache(cfg)
    tc = cfg.chunk_tokens
    lens = {0: tc - 5, 1: 40}
    for sid, n in lens.items():
        kv.add_sequence(sid, n)
    kv.append_tokens(0, 17)  # seq 0 grows past its first chunk
    lens[0] += 17
    chunks = kv._extent_chunks(0, 1, "k")
    assert len(chunks) == 2 and chunks[1] != chunks[0] + 1  # stitched

    rng = np.random.default_rng(0)
    data = {}
    for sid, n in lens.items():
        for name in ("k", "v"):
            x = jnp.asarray(rng.standard_normal((n, n_kv, head_dim)), jnp.bfloat16)
            data[sid, name] = x
            # two writes: the second straddles seq 0's chunk boundary
            kv.write_tokens(sid, 1, name, 0, x[: n - 20])
            kv.write_tokens(sid, 1, name, n - 20, x[n - 20:])
    q = jnp.asarray(rng.standard_normal((2, n_heads, head_dim)), jnp.bfloat16)
    out = np.asarray(kv.decode_attention([0, 1], 1, q), np.float32)
    for i, sid in enumerate(lens):
        exp = dense_decode_oracle(q[i], data[sid, "k"], data[sid, "v"], n_kv)
        np.testing.assert_allclose(out[i], exp, rtol=2e-2, atol=2e-2)


def test_offload_manager_roundtrip():
    from repro.core.arena import Arena, ArenaConfig
    from repro.core.offload import OffloadManager

    a = Arena(ArenaConfig(n_chunks=64, dtype=jnp.float32, interpret=True))
    om = OffloadManager(a)
    x = jax.random.normal(KEY, (100, 300), jnp.float32)
    om.put("opt.m", x)
    om.spill("opt.m")
    assert not om.is_resident("opt.m")
    y = om.get("opt.m")  # staged back through a fresh arena allocation
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))
    om.drop("opt.m")
    assert a.active_bytes == 0
