"""Shared neural-net layers (pure functions over param dicts).

Conventions:
  * params are nested dicts of jnp arrays; per-layer weights are STACKED on
    a leading ``layers`` axis and consumed through ``jax.lax.scan`` so the
    HLO stays O(1) in depth (compile-time control at 512 fake devices).
  * attention is blocked "flash" style in pure JAX: the outer q-block loop
    is python-unrolled (<= MAX_Q_BLOCKS blocks) so each q block scans only
    the kv blocks its mask can reach — causal/sliding-window compute is NOT
    wasted on fully-masked blocks, which keeps HLO FLOPs ~= model FLOPs.
  * GQA expands K/V to the full head count before the einsum; the expansion
    is free under sharding when KV heads are replicated and q heads are
    sharded (a local broadcast), and it makes every head-sharding case
    (KVH % axis != 0 included) uniform.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

MAX_Q_BLOCKS = 16
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# initialisers / norms / activations
# ---------------------------------------------------------------------------


def dense_init(key, shape, in_axis: int = 0, dtype=jnp.float32):
    fan_in = shape[in_axis]
    return (jax.random.normal(key, shape) * (1.0 / math.sqrt(fan_in))).astype(dtype)


def rmsnorm(x, scale, eps: float = 1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale


def layernorm(x, scale, bias, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * scale + bias


ACTIVATIONS: dict = {
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "relu": jax.nn.relu,
    "sqrelu": lambda x: jnp.square(jax.nn.relu(x)),
}


# ---------------------------------------------------------------------------
# rotary position embeddings
# ---------------------------------------------------------------------------


def rope_angles(positions, head_dim: int, theta: float = 10000.0):
    """positions (...,) -> cos/sin (..., head_dim//2)."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, positions, theta: float = 10000.0):
    """x (..., S, H, D); positions broadcastable to (..., S)."""
    head_dim = x.shape[-1]
    cos, sin = rope_angles(positions, head_dim, theta)  # (..., S, D/2)
    cos, sin = cos[..., None, :], sin[..., None, :]  # head axis
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# blocked flash attention (training / prefill)
# ---------------------------------------------------------------------------


def _expand_kv(k, n_heads: int):
    """(B, S, KVH, D) -> (B, S, H, D) by repeating each kv head."""
    n_kv = k.shape[2]
    if n_kv == n_heads:
        return k
    return jnp.repeat(k, n_heads // n_kv, axis=2)


def _block_layout(sq: int, skv: int, kv_block: int) -> Tuple[int, int, int, int]:
    n_q_blocks = max(1, min(MAX_Q_BLOCKS, sq // max(kv_block, 1)))
    while sq % n_q_blocks:
        n_q_blocks -= 1
    q_block = sq // n_q_blocks
    kvb = min(kv_block, skv)
    while skv % kvb:
        kvb -= 1
    return n_q_blocks, q_block, kvb, skv // kvb


def _kv_range(qi, q_block, kvb, n_kv_blocks, causal, window, has_prefix, q_offset):
    """Static kv-block range [lo, hi) reachable by q block ``qi``.

    The prefix-LM mask lets prefix rows attend forward within the prefix,
    so causal block skipping is disabled when a prefix is present.
    """
    q_end = q_offset + (qi + 1) * q_block
    if causal and not has_prefix:
        hi = min(n_kv_blocks, -(-q_end // kvb))
    else:
        hi = n_kv_blocks
    if window is not None and not has_prefix:
        lo = max(0, (q_offset + qi * q_block - window) // kvb)
    else:
        lo = 0
    return lo, hi


def _mask_bias(q_pos, kv_pos, causal, window, prefix_len):
    """Additive mask bias (0 = visible, NEG_INF = masked).

    Additive form (instead of ``jnp.where`` on scores) keeps predicate
    tensors out of the autodiff residuals — the saved-pred broadcasts were
    the dominant HBM term before (EXPERIMENTS.md §Perf).
    """
    vis = jnp.ones((q_pos.shape[0], kv_pos.shape[0]), bool)
    if causal:
        vis = kv_pos[None, :] <= q_pos[:, None]
    if window is not None:
        vis &= kv_pos[None, :] > (q_pos[:, None] - window)
    if prefix_len is not None:
        pl = jnp.asarray(prefix_len)
        if pl.ndim:  # (B,) per-sequence prefix
            vis = vis[None] | (kv_pos[None, None, :] < pl[:, None, None])
            return jnp.where(vis, 0.0, NEG_INF)[:, None]  # (B,1,q,k)
        vis = vis | (kv_pos[None, :] < pl)
    return jnp.where(vis, 0.0, NEG_INF)[None, None]  # (1,1,q,k)


def _flash_fwd_blocks(q, kf, vf, prefix_len, causal, window, q_offset, kv_block, scale):
    """Forward flash pass. Returns o plus per-position (m, l) statistics."""
    b, sq, h, d = q.shape
    skv = kf.shape[1]
    n_q, q_block, kvb, n_kv = _block_layout(sq, skv, kv_block)
    kb = kf.reshape(b, n_kv, kvb, h, d)
    vb = vf.reshape(b, n_kv, kvb, h, d)
    has_prefix = prefix_len is not None

    outs, ms, ls = [], [], []
    for qi in range(n_q):
        qs = q[:, qi * q_block : (qi + 1) * q_block] * scale
        q_pos = q_offset + qi * q_block + jnp.arange(q_block)
        lo, hi = _kv_range(qi, q_block, kvb, n_kv, causal, window, has_prefix, q_offset)

        def kv_step(carry, blk, qs=qs, q_pos=q_pos):
            m_prev, l_prev, acc = carry
            kj, vj, kv_start = blk
            kv_pos = kv_start + jnp.arange(kvb)
            s = jnp.einsum("bqhd,bkhd->bhqk", qs, kj,
                           preferred_element_type=jnp.float32)
            s = s + _mask_bias(q_pos, kv_pos, causal, window, prefix_len)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[..., None])
            l_new = alpha * l_prev + jnp.sum(p, axis=-1)
            pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(kj.dtype), vj,
                            preferred_element_type=jnp.float32)
            acc_new = alpha.transpose(0, 2, 1)[..., None] * acc + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, q_block), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, q_block), jnp.float32)
        a0 = jnp.zeros((b, q_block, h, d), jnp.float32)
        ks = kb[:, lo:hi].transpose(1, 0, 2, 3, 4)
        vs = vb[:, lo:hi].transpose(1, 0, 2, 3, 4)
        starts = (jnp.arange(lo, hi) * kvb).astype(jnp.int32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (ks, vs, starts))
        lsafe = jnp.where(l > 0, l, 1.0)
        outs.append((acc / lsafe.transpose(0, 2, 1)[..., None]).astype(q.dtype))
        ms.append(m)
        ls.append(lsafe)
    o = jnp.concatenate(outs, axis=1)
    return o, jnp.concatenate(ms, -1), jnp.concatenate(ls, -1)  # (B,H,Sq)


def _flash_bwd_blocks(res, do, causal, window, q_offset, kv_block, scale):
    """FlashAttention-2 style backward: recompute p from (q,k,m,l); no
    O(S^2) residuals are ever stored."""
    q, kf, vf, prefix_len, o, m, l = res
    b, sq, h, d = q.shape
    skv = kf.shape[1]
    n_q, q_block, kvb, n_kv = _block_layout(sq, skv, kv_block)
    kb = kf.reshape(b, n_kv, kvb, h, d)
    vb = vf.reshape(b, n_kv, kvb, h, d)
    has_prefix = prefix_len is not None
    dof = do.astype(jnp.float32)
    # delta = rowsum(do * o): (B,H,Sq)
    delta = jnp.einsum("bqhd,bqhd->bhq", dof, o.astype(jnp.float32))

    dq = jnp.zeros((b, sq, h, d), jnp.float32)
    dk = jnp.zeros((b, skv, h, d), jnp.float32)
    dv = jnp.zeros((b, skv, h, d), jnp.float32)

    for qi in range(n_q):
        sl = slice(qi * q_block, (qi + 1) * q_block)
        qs = q[:, sl] * scale
        doq = dof[:, sl]
        mi = m[..., sl]  # (B,H,qb)
        li = l[..., sl]
        di = delta[..., sl]
        q_pos = q_offset + qi * q_block + jnp.arange(q_block)
        lo, hi = _kv_range(qi, q_block, kvb, n_kv, causal, window, has_prefix, q_offset)

        def kv_step(dq_acc, blk, qs=qs, doq=doq, mi=mi, li=li, di=di, q_pos=q_pos):
            kj, vj, kv_start = blk
            kv_pos = kv_start + jnp.arange(kvb)
            s = jnp.einsum("bqhd,bkhd->bhqk", qs, kj,
                           preferred_element_type=jnp.float32)
            s = s + _mask_bias(q_pos, kv_pos, causal, window, prefix_len)
            p = jnp.exp(s - mi[..., None]) / li[..., None]  # (B,H,q,k)
            dvj = jnp.einsum("bhqk,bqhd->bkhd", p, doq)
            dp = jnp.einsum("bqhd,bkhd->bhqk", doq, vj.astype(jnp.float32))
            ds = p * (dp - di[..., None])
            dq_acc = dq_acc + jnp.einsum("bhqk,bkhd->bqhd", ds,
                                         kj.astype(jnp.float32))
            dkj = jnp.einsum("bhqk,bqhd->bkhd", ds, qs.astype(jnp.float32))
            return dq_acc, (dkj, dvj)

        ks = kb[:, lo:hi].transpose(1, 0, 2, 3, 4)
        vs = vb[:, lo:hi].transpose(1, 0, 2, 3, 4)
        starts = (jnp.arange(lo, hi) * kvb).astype(jnp.int32)
        dq0 = jnp.zeros((b, q_block, h, d), jnp.float32)
        dqi, (dks, dvs) = jax.lax.scan(kv_step, dq0, (ks, vs, starts))
        dq = dq.at[:, sl].set(dqi * scale)
        span = slice(lo * kvb, hi * kvb)
        dk = dk.at[:, span].add(
            dks.transpose(1, 0, 2, 3, 4).reshape(b, (hi - lo) * kvb, h, d)
        )
        dv = dv.at[:, span].add(
            dvs.transpose(1, 0, 2, 3, 4).reshape(b, (hi - lo) * kvb, h, d)
        )
    return dq, dk, dv


def flash_attention(
    q: jax.Array,  # (B, Sq, H, D)
    k: jax.Array,  # (B, Skv, KVH, D)
    v: jax.Array,  # (B, Skv, KVH, D)
    *,
    causal: bool = True,
    window: Optional[int] = None,  # sliding-window size (SWA)
    prefix_len=None,  # traced (B,) or scalar: bidirectional prefix (prefix-LM)
    q_offset: int = 0,
    kv_block: int = 512,
    scale: Optional[float] = None,
) -> jax.Array:
    """Blocked flash attention with a custom (recomputing) VJP.

    O(block) live memory in both passes; residuals are q, k, v, o and the
    per-row (m, l) softmax statistics only. Static block skipping covers
    causal + sliding-window reach, so HLO FLOPs track model FLOPs. GQA K/V
    expansion happens inside; cotangents fold back onto the KV heads.
    """
    d = q.shape[-1]
    scale = (d**-0.5) if scale is None else scale
    n_heads = q.shape[2]
    n_kv = k.shape[2]

    # python-int / None prefixes are static (closed over); array prefixes are
    # traced primals (converted to f32 so the cotangent is well-typed)
    if prefix_len is None or isinstance(prefix_len, int):
        static_prefix, traced_prefix = prefix_len, None
    else:
        static_prefix, traced_prefix = None, jnp.asarray(prefix_len, jnp.float32)
    has_prefix = prefix_len is not None

    def pick(prefix):
        return prefix if prefix is not None else static_prefix

    @jax.custom_vjp
    def _attn(q, k, v, prefix):
        o, _, _ = _flash_fwd_blocks(q, _expand_kv(k, n_heads), _expand_kv(v, n_heads),
                                    pick(prefix), causal, window, q_offset,
                                    kv_block, scale)
        return o

    def _attn_fwd(q, k, v, prefix):
        kf, vf = _expand_kv(k, n_heads), _expand_kv(v, n_heads)
        o, m, l = _flash_fwd_blocks(q, kf, vf, pick(prefix), causal, window,
                                    q_offset, kv_block, scale)
        return o, (q, kf, vf, prefix, o, m, l)

    def _attn_bwd(res, do):
        q, kf, vf, prefix, o, m, l = res
        dq, dkf, dvf = _flash_bwd_blocks((q, kf, vf, pick(prefix), o, m, l), do,
                                         causal, window, q_offset, kv_block, scale)
        b, skv, hh, dd = dkf.shape
        if n_kv != hh:  # fold expanded-head cotangents back onto KV heads
            dkf = dkf.reshape(b, skv, n_kv, hh // n_kv, dd).sum(3)
            dvf = dvf.reshape(b, skv, n_kv, hh // n_kv, dd).sum(3)
        dprefix = None if prefix is None else jnp.zeros_like(prefix)
        return (dq.astype(q.dtype), dkf.astype(q.dtype), dvf.astype(q.dtype),
                dprefix)

    _attn.defvjp(_attn_fwd, _attn_bwd)
    return _attn(q, k, v, traced_prefix)


def decode_attention_dense(
    q: jax.Array,  # (B, 1, H, D)
    k_cache: jax.Array,  # (B, S, KVH, D)
    v_cache: jax.Array,  # (B, S, KVH, D)
    lengths: jax.Array,  # (B,) valid tokens in cache (new token included)
    *,
    window: Optional[int] = None,
    scale: Optional[float] = None,
) -> jax.Array:
    """Single-token decode over a dense KV cache (serve_step path).

    Grouped-query form: the query carries the group, so K and V are read
    as stored and never copied per query head. Query head ``h = k*G + g``
    attends with KV head ``k`` (``jnp.repeat``'s order); ``G == 1`` is MHA.
    """
    b, _, h, d = q.shape
    s, n_kv = k_cache.shape[1], k_cache.shape[2]
    scale = (d**-0.5) if scale is None else scale
    qg = (q * scale).reshape(b, n_kv, h // n_kv, d)
    logits = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                        preferred_element_type=jnp.float32)
    pos = jnp.arange(s)[None, :]
    valid = pos < lengths[:, None]
    if window is not None:
        valid &= pos > (lengths[:, None] - 1 - window)
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgs,bskd->bkgd", p, v_cache, preferred_element_type=jnp.float32)
    return o.reshape(b, 1, h, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def mlp_apply(params: dict, x: jax.Array, act: str = "gelu", gated: bool = False):
    a = ACTIVATIONS[act]
    h = jnp.einsum("...d,df->...f", x, params["wi"])
    if gated:
        g = jnp.einsum("...d,df->...f", x, params["wg"])
        h = a(g) * h
    else:
        h = a(h)
    return jnp.einsum("...f,fd->...d", h, params["wo"])


def mlp_init(key, d_model: int, d_ff: int, gated: bool, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 3)
    p = {
        "wi": dense_init(ks[0], (d_model, d_ff), dtype=dtype),
        "wo": dense_init(ks[1], (d_ff, d_model), dtype=dtype),
    }
    if gated:
        p["wg"] = dense_init(ks[2], (d_model, d_ff), dtype=dtype)
    return p


def mlp_axes(gated: bool) -> dict:
    p = {"wi": ("embed", "ffn"), "wo": ("ffn", "embed")}
    if gated:
        p["wg"] = ("embed", "ffn")
    return p


# ---------------------------------------------------------------------------
# cross-entropy
# ---------------------------------------------------------------------------


def softmax_xent(logits: jax.Array, targets: jax.Array, mask=None) -> jax.Array:
    """logits (..., V) float, targets (...) int32 -> mean xent."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
