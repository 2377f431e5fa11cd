"""Cross-path model consistency: chunked/parallel training forms must match
sequential recurrences, and (prefill + decode) must match full forward.

These are the invariants that make serving trustworthy: any drift between
the train-time parallel form and the decode-time recurrence silently
corrupts generation.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# flash attention (custom VJP) vs dense oracle — all mask regimes
# ---------------------------------------------------------------------------


def dense_attn_ref(q, k, v, causal=True, window=None, prefix=None):
    from repro.models import layers as L

    b, sq, h, d = q.shape
    skv = k.shape[1]
    kf, vf = L._expand_kv(k, h), L._expand_kv(v, h)
    s = jnp.einsum("bqhd,bkhd->bhqk", q * d**-0.5, kf)
    qp, kp = jnp.arange(sq)[:, None], jnp.arange(skv)[None, :]
    vis = kp <= qp if causal else jnp.ones((sq, skv), bool)
    if window is not None:
        vis &= kp > qp - window
    if prefix is not None:
        pl = jnp.asarray(prefix)
        if pl.ndim:
            vis = vis[None] | (kp[None] < pl[:, None, None])
        else:
            vis = vis | (kp < pl)
    vis = vis if vis.ndim == 3 else vis[None]
    s = jnp.where(vis[:, None], s, -1e30)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vf)


CASES = [
    ("causal_mha", dict(), dict(), (8, 8)),
    ("causal_gqa", dict(), dict(), (8, 2)),
    ("swa", dict(window=24), dict(window=24), (4, 4)),
    ("prefix_static", dict(prefix_len=16), dict(prefix=16), (4, 2)),
    ("prefix_traced", dict(prefix_len=jnp.array([10., 20.])),
     dict(prefix=jnp.array([10, 20])), (4, 1)),
]


@pytest.mark.parametrize("name,fkw,rkw,heads", CASES, ids=[c[0] for c in CASES])
def test_flash_attention_fwd_bwd_vs_dense(name, fkw, rkw, heads):
    from repro.models import layers as L

    h, kv = heads
    q = jax.random.normal(jax.random.fold_in(KEY, 1), (2, 64, h, 32))
    k = jax.random.normal(jax.random.fold_in(KEY, 2), (2, 64, kv, 32))
    v = jax.random.normal(jax.random.fold_in(KEY, 3), (2, 64, kv, 32))
    out = L.flash_attention(q, k, v, kv_block=16, **fkw)
    ref = dense_attn_ref(q, k, v, **rkw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)

    g1 = jax.grad(lambda *a: jnp.sum(jnp.sin(
        L.flash_attention(*a, kv_block=16, **fkw))), argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(lambda *a: jnp.sum(jnp.sin(dense_attn_ref(*a, **rkw))),
                  argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=3e-4, atol=3e-4)


# ---------------------------------------------------------------------------
# dense decode attention: grouped read == expanded (jnp.repeat) reference
# ---------------------------------------------------------------------------


def expanded_decode_ref(q, k, v, lengths, window=None):
    """f32 decode attention with K and V repeated to every query head."""
    h, d = q.shape[2], q.shape[3]
    kf = jnp.repeat(k, h // k.shape[2], axis=2)
    vf = jnp.repeat(v, h // v.shape[2], axis=2)
    s = jnp.einsum("bhd,bshd->bhs", q[:, 0] * d**-0.5, kf)
    pos = jnp.arange(k.shape[1])[None, :]
    valid = pos < lengths[:, None]
    if window is not None:
        valid &= pos > lengths[:, None] - 1 - window
    s = jnp.where(valid[:, None], s, -1e30)
    return jnp.einsum("bhs,bshd->bhd", jax.nn.softmax(s, -1), vf)[:, None]


DECODE_CASES = [  # name, H, KVH, D, window
    ("mha", 4, 4, 32, None),
    ("mha_window", 4, 4, 32, 5),
    ("gqa3", 9, 3, 64, None),
    ("gqa3_window", 9, 3, 64, 7),
    ("danube", 32, 8, 120, None),
    ("danube_window", 32, 8, 120, 16),
    ("head_order", 8, 2, 16, None),
]


@pytest.mark.parametrize("name,h,kvh,d,window", DECODE_CASES,
                         ids=[c[0] for c in DECODE_CASES])
def test_decode_attention_dense_vs_expanded(name, h, kvh, d, window):
    from repro.models import layers as L

    b, s = 3, 48
    lengths = jnp.array([1, 29, s])  # ragged: one token, part, full
    q = jax.random.normal(jax.random.fold_in(KEY, 11), (b, 1, h, d))
    k = jax.random.normal(jax.random.fold_in(KEY, 12), (b, s, kvh, d))
    v = jax.random.normal(jax.random.fold_in(KEY, 13), (b, s, kvh, d))
    if name == "head_order":  # KV head k holds the value k everywhere
        v = jnp.broadcast_to(jnp.arange(kvh, dtype=jnp.float32)[:, None],
                             (b, s, kvh, d))
    out = L.decode_attention_dense(q, k, v, lengths, window=window)
    assert out.shape == (b, 1, h, d)
    ref = expanded_decode_ref(q, k, v, lengths, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)
    if name == "head_order":  # query head h = k*G + g reads KV head k
        want = np.repeat(np.arange(kvh), h // kvh)
        np.testing.assert_allclose(np.asarray(out)[:, 0, :, 0],
                                   np.broadcast_to(want, (b, h)), atol=1e-6)


def _eqn_shapes(jaxpr):
    """Output shapes of every equation, nested jaxprs included."""
    for eqn in jaxpr.eqns:
        yield from (tuple(o.aval.shape) for o in eqn.outvars)
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else (p,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqn_shapes(inner)


def test_decode_attention_dense_never_expands_kv():
    from repro.models import layers as L

    b, s, h, kvh, d = 2, 64, 8, 2, 16
    jaxpr = jax.make_jaxpr(L.decode_attention_dense)(
        jnp.zeros((b, 1, h, d)), jnp.zeros((b, s, kvh, d)),
        jnp.zeros((b, s, kvh, d)), jnp.full((b,), s))
    shapes = list(_eqn_shapes(jaxpr.jaxpr))
    assert (b, 1, h, d) in shapes  # the walk reached the output
    assert (b, s, h, d) not in shapes  # no K or V copied per query head


# ---------------------------------------------------------------------------
# mamba2: chunked SSD == sequential recurrence; decode == forward
# ---------------------------------------------------------------------------


def test_mamba2_chunked_equals_sequential():
    from repro.models import layers as L
    from repro.models import mamba2 as M

    cfg = M.Mamba2Config(d_model=32, d_state=16, head_p=8, expand=2, chunk=8)
    p = jax.tree.map(lambda x: x[0], M.block_init(cfg, KEY, n_layers=1))
    x = jax.random.normal(KEY, (2, 24, 32), jnp.float32) * 0.5
    y = M.apply_block(cfg, p, x)

    # sequential oracle
    b, s, _ = x.shape
    h, pp, n = cfg.n_heads, cfg.head_p, cfg.d_state
    zx = jnp.einsum("bsd,dk->bsk", x, p["in_proj"])
    z, xbc, dt = M._split_proj(cfg, zx)
    xbc = M._causal_conv(cfg, p["conv_w"], p["conv_b"], xbc)
    xi = xbc[..., : cfg.d_inner].reshape(b, s, h, pp)
    bm = xbc[..., cfg.d_inner : cfg.d_inner + n]
    cm = xbc[..., cfg.d_inner + n :]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a_coef = -jnp.exp(p["A_log"])
    hs = jnp.zeros((b, h, pp, n))
    ys = []
    for t in range(s):
        at = jnp.exp(dt[:, t] * a_coef)
        hs = at[..., None, None] * hs + jnp.einsum(
            "bhp,bn,bh->bhpn", xi[:, t], bm[:, t], dt[:, t])
        ys.append(jnp.einsum("bhpn,bn->bhp", hs, cm[:, t]))
    yr = jnp.stack(ys, 1) + p["D"][None, None, :, None] * xi
    yr = L.rmsnorm(yr.reshape(b, s, cfg.d_inner) * jax.nn.silu(z), p["norm"])
    ref = jnp.einsum("bsk,kd->bsd", yr, p["out_proj"])
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-4, atol=1e-4)

    # decode recurrence reaches the same final output
    st = M.init_state(cfg, 2)
    for t in range(24):
        out, st = M.decode_block(cfg, p, st, x[:, t])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref[:, -1]),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# per-family: prefill+decode == full forward on the reduced configs
# ---------------------------------------------------------------------------


def test_transformer_decode_equals_forward():
    from repro.models import transformer as T

    cfg = T.TransformerConfig(name="t", n_layers=3, d_model=64, n_heads=4,
                              n_kv=2, d_ff=128, vocab=257, dtype=jnp.float32,
                              remat=False)
    params = T.init_params(cfg, KEY)
    toks = jax.random.randint(KEY, (2, 32), 0, 257)
    cache = T.init_cache(cfg, 2, 64)
    lp, cache = T.prefill(cfg, params, {"tokens": toks}, cache)
    nxt = jnp.argmax(lp[:, -1], -1)
    ld, _ = T.decode_step(cfg, params, cache, nxt)
    toks2 = jnp.concatenate([toks, nxt[:, None]], 1)
    x = T.embed_tokens(cfg, params, toks2)
    pos = jnp.broadcast_to(jnp.arange(33), (2, 33))
    h, _ = T.forward(cfg, params, x, pos)
    ref = T.logits_from_hidden(cfg, params, h[:, -1:])[:, 0]
    np.testing.assert_allclose(np.asarray(ld), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_zamba2_decode_equals_forward():
    from repro.models import zamba2 as Z

    cfg = Z.Zamba2Config(name="t", n_layers=5, d_model=32, n_heads=4, n_kv=2,
                         d_ff=64, vocab=101, d_state=16, attn_every=2, chunk=8,
                         dtype=jnp.float32, remat=False)
    params = Z.init_params(cfg, KEY)
    toks = jax.random.randint(KEY, (2, 16), 0, 101)
    cache = Z.init_cache(cfg, 2, 32)
    lp, cache = Z.prefill(cfg, params, {"tokens": toks}, cache)
    nxt = jnp.argmax(lp[:, -1], -1)
    ld, _ = Z.decode_step(cfg, params, cache, nxt)
    toks2 = jnp.concatenate([toks, nxt[:, None]], 1)
    x = params["embed"][toks2]
    pos = jnp.broadcast_to(jnp.arange(17), (2, 17))
    h, _ = Z.forward(cfg, params, x, pos)
    ref = jnp.einsum("bd,dv->bv", h[:, -1], params["embed"].T)
    np.testing.assert_allclose(np.asarray(ld), np.asarray(ref), rtol=2e-4, atol=2e-4)


def test_rwkv6_chunked_prefill_equals_sequential_decode():
    from repro.models import rwkv6 as R

    cfg = R.RWKV6Config(name="t", n_layers=3, d_model=64, d_ff=128, vocab=101,
                        head_size=16, decay_lora=8, chunk=8, dtype=jnp.float32,
                        remat=False)
    params = R.init_params(cfg, KEY)
    toks = jax.random.randint(KEY, (2, 24), 0, 101)
    lp, cache = R.prefill(cfg, params, {"tokens": toks}, R.init_cache(cfg, 2))
    c = R.init_cache(cfg, 2)
    for t in range(24):
        lo, c = R.decode_step(cfg, params, c, toks[:, t])
    np.testing.assert_allclose(np.asarray(lo), np.asarray(lp[:, -1]),
                               rtol=2e-4, atol=2e-4)
    # and the carried states agree on the NEXT step
    nxt = jnp.argmax(lp[:, -1], -1)
    a, _ = R.decode_step(cfg, params, cache, nxt)
    b, _ = R.decode_step(cfg, params, c, nxt)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)


def test_whisper_decode_equals_train_path():
    from repro.models import whisper as W

    cfg = W.WhisperConfig(name="t", n_layers=2, d_model=64, n_heads=4, n_kv=4,
                          d_ff=128, vocab=101, max_positions=64,
                          dtype=jnp.float32, remat=False)
    params = W.init_params(cfg, KEY)
    frames = jax.random.normal(KEY, (2, 16, 64), jnp.float32)
    toks = jax.random.randint(KEY, (2, 12), 0, 101)
    cache = W.init_cache(cfg, 2, 32, 16)
    lp, cache = W.prefill(cfg, params, {"frames": frames, "tokens": toks}, cache)
    nxt = jnp.argmax(lp[:, -1], -1)
    ld, _ = W.decode_step(cfg, params, cache, nxt)
    mem = W.encode(cfg, params, frames)
    toks2 = jnp.concatenate([toks, nxt[:, None]], 1)
    ref, _ = W.decode_train(cfg, params, toks2, mem)
    np.testing.assert_allclose(np.asarray(ld), np.asarray(ref[:, -1]),
                               rtol=3e-4, atol=3e-4)


def test_rwkv6_long_context_decode_is_o1():
    """The long_500k cell's premise: RWKV decode state is O(1) in history."""
    from repro.models import rwkv6 as R

    cfg = R.RWKV6Config(name="t", n_layers=2, d_model=32, d_ff=64, vocab=53,
                        head_size=16, decay_lora=8, dtype=jnp.float32, remat=False)
    cache = R.init_cache(cfg, 1)
    total = sum(x.size for x in jax.tree.leaves(cache))
    params = R.init_params(cfg, KEY)
    for t in range(20):
        _, cache = R.decode_step(cfg, params, cache,
                                 jnp.array([t % 53], jnp.int32))
    assert sum(x.size for x in jax.tree.leaves(cache)) == total
