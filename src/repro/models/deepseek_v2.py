"""DeepSeek-V2 family: multi-head latent attention (MLA) and a mixture of
experts with shared experts (DeepSeek-V2-Lite; without q compression, the
block of DeepSeek-V3, Kimi and GLM-Flash less their extra heads).

Written from the published equations (arXiv 2405.04434, and the model's
``modeling_deepseek.py``):

* attention: q = x Wq split into a no-position part (``qk_nope_head_dim``)
  and a rotary part (``qk_rope_head_dim``); x W_kv_a gives a latent row
  c (``kv_lora_rank``, RMS-normed) and one rotary key k_pe shared by every
  head; per head, k = [c W_uk ; k_pe] and v = c W_uv. The rotary halves are
  de-interleaved (even lanes, then odd) before rotate-half, and the angles
  are YaRN's. Scores are scaled by ``qk_head_dim ** -0.5 * mscale ** 2``.
* layers ``[0, first_k_dense_replace)`` have a dense SwiGLU MLP; the rest
  route each token over every routed expert (softmax, greedy top-k, the
  weights not renormalised unless ``norm_topk_prob``) and add the shared
  experts (``moe.shared_moe``).

Prefill runs the decompressed path. Decode runs the absorbed one: W_uk is
folded into the query and W_uv applied after attention, so a step reads
only the cache's latent rows, ``{"ckv": (L, B, S, kv_lora_rank), "kpe":
(L, B, S, qk_rope_head_dim), "length": (B,)}``; the KV arena accounts the
same row as one stream per layer (``api.KVLayout``).

Expert share: a chip holds experts ``[expert_offset, expert_offset +
experts_held)`` of each MoE layer; the router keeps its published width
(``n_experts_routed``) and computes the part of the result its experts give.
Nothing stands in for the experts held elsewhere.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from . import layers as L
from . import moe
from .transformer import Sharder, _id_sharder, embed_tokens, logits_from_hidden

ACT = "silu"


@dataclass(frozen=True)
class DeepseekV2Config:
    name: str
    n_layers: int  # every layer, the leading dense ones included
    d_model: int
    n_heads: int
    vocab: int
    d_ff: int  # the dense layers' MLP width
    moe_d_ff: int  # one expert's width
    n_experts_routed: int  # the router's width
    experts_held: int  # routed experts of each MoE layer held here
    top_k: int
    expert_offset: int = 0
    n_shared_experts: int = 2
    first_k_dense_replace: int = 1
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    norm_topk_prob: bool = False
    routed_scaling_factor: float = 1.0
    rope_theta: float = 10_000.0
    #: YaRN (``rope_scaling``); a factor of 1 is plain RoPE
    rope_factor: float = 1.0
    rope_original_max: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0
    tie_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    remat: bool = True
    embed_scale = False  # read by transformer.embed_tokens; no scale here

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """Elements of one token's cached row: latent plus rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.first_k_dense_replace

    @property
    def softmax_scale(self) -> float:
        m = yarn_mscale(self.rope_factor, self.mscale_all_dim) if self.mscale_all_dim else 1.0
        return self.qk_head_dim ** -0.5 * m * m

    @property
    def n_params(self) -> int:
        """Parameters held here (the held experts only)."""
        d, h, r = self.d_model, self.n_heads, self.kv_lora_rank
        attn = (d * h * self.qk_head_dim + d * self.latent_dim + r
                + r * h * (self.qk_nope_head_dim + self.v_head_dim) + h * self.v_head_dim * d)
        dense = attn + 3 * d * self.d_ff + 2 * d
        routed = d * self.n_experts_routed + self.experts_held * 3 * d * self.moe_d_ff
        shared = 3 * d * self.n_shared_experts * self.moe_d_ff
        moe_layer = attn + routed + shared + 2 * d
        head = 0 if self.tie_embeddings else d * self.vocab
        return (self.first_k_dense_replace * dense + self.n_moe_layers * moe_layer
                + self.vocab * d + head + d)


# ---------------------------------------------------------------------------
# YaRN rotary positions
# ---------------------------------------------------------------------------


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: DeepseekV2Config) -> np.ndarray:
    """Per-pair rotary frequencies: the base ones below YaRN's correction
    range, those over ``rope_factor`` above it, a linear ramp between."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if cfg.rope_factor <= 1:
        return extra.astype(np.float32)

    def correction_dim(rotations):
        return (dim * math.log(cfg.rope_original_max / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(cfg.beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inter = extra / cfg.rope_factor
    return (inter * ramp + extra * (1 - ramp)).astype(np.float32)


def apply_rope(cfg: DeepseekV2Config, x: jax.Array, positions: jax.Array) -> jax.Array:
    """x (B, S, H, P) at positions (B, S): even lanes then odd ones, then
    rotate-half by YaRN's angles (cos and sin times the mscale ratio)."""
    m = 1.0
    if cfg.mscale_all_dim:
        m = yarn_mscale(cfg.rope_factor, cfg.mscale) / yarn_mscale(cfg.rope_factor,
                                                                   cfg.mscale_all_dim)
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(yarn_inv_freq(cfg))
    cos, sin = (m * jnp.cos(ang))[..., None, :], (m * jnp.sin(ang))[..., None, :]
    p = x.shape[-1]
    xf = x.astype(jnp.float32).reshape(x.shape[:-1] + (p // 2, 2))
    x1, x2 = xf[..., 0], xf[..., 1]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def _attn_init(cfg, key, n):
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    ks = jax.random.split(key, 5)
    init = lambda k, shape: L.dense_init(k, shape, in_axis=1, dtype=cfg.dtype)  # noqa: E731
    return {
        "wq": init(ks[0], (n, d, h * cfg.qk_head_dim)),
        "wkv_a": init(ks[1], (n, d, cfg.latent_dim)),
        "kv_norm": {"scale": jnp.ones((n, r), cfg.dtype)},
        "w_uk": init(ks[2], (n, r, h, cfg.qk_nope_head_dim)),
        "w_uv": init(ks[3], (n, r, h, cfg.v_head_dim)),
        "wo": init(ks[4], (n, h * cfg.v_head_dim, d)),
    }


def _mlp_init(cfg, key, lead, d_ff):
    d = cfg.d_model
    ks = jax.random.split(key, 3)
    return {
        "wi": L.dense_init(ks[0], lead + (d, d_ff), in_axis=len(lead), dtype=cfg.dtype),
        "wg": L.dense_init(ks[1], lead + (d, d_ff), in_axis=len(lead), dtype=cfg.dtype),
        "wo": L.dense_init(ks[2], lead + (d_ff, d), in_axis=len(lead), dtype=cfg.dtype),
    }


def _norm(n, d, dtype):
    return {"scale": jnp.ones((n, d), dtype)}


def init_params(cfg: DeepseekV2Config, key) -> Dict:
    k, n, d = cfg.first_k_dense_replace, cfg.n_moe_layers, cfg.d_model
    ks = jax.random.split(key, 8)
    params = {
        "embed": L.dense_init(ks[0], (cfg.vocab, d), in_axis=1, dtype=cfg.dtype),
        "dense": {
            "ln1": _norm(k, d, cfg.dtype), "attn": _attn_init(cfg, ks[1], k),
            "ln2": _norm(k, d, cfg.dtype), "mlp": _mlp_init(cfg, ks[2], (k,), cfg.d_ff),
        },
        "moe": {
            "ln1": _norm(n, d, cfg.dtype), "attn": _attn_init(cfg, ks[3], n),
            "ln2": _norm(n, d, cfg.dtype),
            "router": L.dense_init(ks[4], (n, d, cfg.n_experts_routed), in_axis=1,
                                   dtype=cfg.dtype),
            "experts": _mlp_init(cfg, ks[5], (n, cfg.experts_held), cfg.moe_d_ff),
            "shared": _mlp_init(cfg, ks[6], (n,), cfg.n_shared_experts * cfg.moe_d_ff),
        },
        "final_norm": {"scale": jnp.ones((d,), cfg.dtype)},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(ks[7], (d, cfg.vocab), in_axis=0, dtype=cfg.dtype)
    return params


def param_axes(cfg: DeepseekV2Config) -> Dict:
    def attn():
        return {"wq": ("layers", "embed", "heads"), "wkv_a": ("layers", "embed", None),
                "kv_norm": {"scale": ("layers", None)},
                "w_uk": ("layers", None, "heads", None),
                "w_uv": ("layers", None, "heads", None),
                "wo": ("layers", "heads", "embed")}

    mlp = {k: ("layers",) + v for k, v in L.mlp_axes(True).items()}
    norm = {"scale": ("layers", "embed")}
    axes = {
        "embed": ("vocab", "embed"),
        "dense": {"ln1": norm, "attn": attn(), "ln2": norm, "mlp": mlp},
        "moe": {
            "ln1": norm, "attn": attn(), "ln2": norm,
            "router": ("layers", "embed", None),
            "experts": {"wi": ("layers", "expert", "embed", "ffn"),
                        "wg": ("layers", "expert", "embed", "ffn"),
                        "wo": ("layers", "expert", "ffn", "embed")},
            "shared": mlp,
        },
        "final_norm": {"scale": ("embed",)},
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


# ---------------------------------------------------------------------------
# layers
# ---------------------------------------------------------------------------


def _project(cfg, p, x, positions):
    """Queries, latent rows and rotary keys of x (B, S, d): q_nope (B, S, H,
    nope), q_pe (B, S, H, rope) and k_pe (B, S, rope) rotated, c (B, S, r)."""
    b, s, _ = x.shape
    r = cfg.kv_lora_rank
    q = jnp.einsum("bsd,dk->bsk", x, p["wq"]).reshape(b, s, cfg.n_heads, cfg.qk_head_dim)
    q_nope, q_pe = q[..., :cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim:]
    ckv = jnp.einsum("bsd,dk->bsk", x, p["wkv_a"])
    c = L.rmsnorm(ckv[..., :r], p["kv_norm"]["scale"])
    k_pe = apply_rope(cfg, ckv[..., None, r:], positions)[..., 0, :]
    return q_nope, apply_rope(cfg, q_pe, positions), c, k_pe


def _attn_prefill(cfg, p, x, positions):
    """Decompressed MLA over the whole prompt: (out (B, S, d), (c, k_pe))."""
    with jax.named_scope("mla.attention"):
        b, s, _ = x.shape
        h, vd = cfg.n_heads, cfg.v_head_dim
        q_nope, q_pe, c, k_pe = _project(cfg, p, x, positions)
        k_nope = jnp.einsum("bsr,rhn->bshn", c, p["w_uk"])
        v = jnp.einsum("bsr,rhv->bshv", c, p["w_uv"])
        q = jnp.concatenate([q_nope, q_pe], -1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe[:, :, None], (b, s, h, cfg.qk_rope_head_dim))], -1)
        # flash_attention keeps one head width for q, k and v: v is padded
        # with zeros to the qk width and the pad is cut from the output
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, cfg.qk_head_dim - vd)))
        o = L.flash_attention(q, k, v, causal=True, scale=cfg.softmax_scale)[..., :vd]
        out = jnp.einsum("bsk,kd->bsd", o.reshape(b, s, h * vd), p["wo"])
    return out, (c, k_pe)


def _attn_decode(cfg, p, x, ckv, kpe, layer, lengths):
    """Absorbed MLA for one token per sequence, x (B, 1, d). Writes the new
    latent row of layer ``layer`` at each sequence's length, then attends
    over the cache's rows ``[0, length]``. Returns (out (B, 1, d), ckv, kpe)."""
    with jax.named_scope("mla.attention"):
        b = x.shape[0]
        q_nope, q_pe, c, k_pe = _project(cfg, p, x, lengths[:, None])
        rows = jnp.arange(b)
        ckv = ckv.at[layer, rows, lengths].set(c[:, 0].astype(ckv.dtype))
        kpe = kpe.at[layer, rows, lengths].set(k_pe[:, 0].astype(kpe.dtype))
        c_l, kpe_l = ckv[layer], kpe[layer]  # (B, S, r), (B, S, rope)
        q_lat = jnp.einsum("bhn,rhn->bhr", q_nope[:, 0], p["w_uk"])
        f32 = jnp.float32
        scores = (jnp.einsum("bhr,bsr->bhs", q_lat, c_l, preferred_element_type=f32)
                  + jnp.einsum("bhp,bsp->bhs", q_pe[:, 0], kpe_l,
                               preferred_element_type=f32)) * cfg.softmax_scale
        valid = jnp.arange(c_l.shape[1])[None, :] <= lengths[:, None]
        scores = jnp.where(valid[:, None, :], scores, L.NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(c_l.dtype)
        o_lat = jnp.einsum("bhs,bsr->bhr", probs, c_l,
                           preferred_element_type=f32).astype(c_l.dtype)
        o = jnp.einsum("bhr,rhv->bhv", o_lat, p["w_uv"])
        out = jnp.einsum("bk,kd->bd", o.reshape(b, -1), p["wo"])[:, None]
    return out, ckv, kpe


def _dense_mlp(cfg, lp, x):
    return L.mlp_apply(lp["mlp"], x, ACT, gated=True)


def _moe_mlp(cfg, lp, x):
    return moe.shared_moe(lp, x, top_k=cfg.top_k, offset=cfg.expert_offset,
                          norm_topk_prob=cfg.norm_topk_prob,
                          scaling=cfg.routed_scaling_factor, act=ACT)


def _ln(lp, name, x):
    return L.rmsnorm(x, lp[name]["scale"])


def forward(cfg, params, x, positions, collect_kv: bool = False):
    """x (B, S, d) embedded -> (final hidden, latent rows per layer or None):
    the dense layers, then the MoE layers, each scanned."""
    def stack(mlp):
        def body(h, lp):
            a, lat = _attn_prefill(cfg, lp["attn"], _ln(lp, "ln1", h), positions)
            h = h + a
            h = h + mlp(cfg, lp, _ln(lp, "ln2", h))
            return h, lat if collect_kv else None
        return jax.checkpoint(body) if cfg.remat else body

    h, lat_dense = jax.lax.scan(stack(_dense_mlp), x, params["dense"])
    h, lat_moe = jax.lax.scan(stack(_moe_mlp), h, params["moe"])
    h = L.rmsnorm(h, params["final_norm"]["scale"])
    if not collect_kv:
        return h, None
    return h, jax.tree.map(lambda a, b: jnp.concatenate([a, b]), lat_dense, lat_moe)


def loss_fn(cfg: DeepseekV2Config, params, batch, sharder: Sharder = _id_sharder):
    tokens = batch["tokens"]
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    h, _ = forward(cfg, params, embed_tokens(cfg, params, tokens), positions)
    logits = logits_from_hidden(cfg, params, h[:, :-1])
    return L.softmax_xent(logits, tokens[:, 1:], batch.get("loss_mask"))


# ---------------------------------------------------------------------------
# serving: prefill + latent-cache decode
# ---------------------------------------------------------------------------


def init_cache(cfg: DeepseekV2Config, batch: int, max_len: int) -> Dict:
    lead = (cfg.n_layers, batch, max_len)
    return {
        "ckv": jnp.zeros(lead + (cfg.kv_lora_rank,), cfg.dtype),
        "kpe": jnp.zeros(lead + (cfg.qk_rope_head_dim,), cfg.dtype),
        "length": jnp.zeros((batch,), jnp.int32),
    }


def cache_axes(cfg: DeepseekV2Config) -> Dict:
    return {
        "ckv": ("layers", "batch", "kv_seq", None),
        "kpe": ("layers", "batch", "kv_seq", None),
        "length": ("batch",),
    }


def prefill(cfg, params, batch, cache, sharder: Sharder = _id_sharder):
    """The prompt through the decompressed path; its latent rows into the
    cache. Returns the last position's logits and the cache."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    h, (c, k_pe) = forward(cfg, params, embed_tokens(cfg, params, tokens), positions,
                           collect_kv=True)
    cache = {
        "ckv": jax.lax.dynamic_update_slice(cache["ckv"], c.astype(cfg.dtype), (0, 0, 0, 0)),
        "kpe": jax.lax.dynamic_update_slice(cache["kpe"], k_pe.astype(cfg.dtype),
                                            (0, 0, 0, 0)),
        "length": jnp.full((b,), s, jnp.int32),
    }
    return logits_from_hidden(cfg, params, h[:, -1:]), cache


def decode_step(cfg, params, cache, tokens, sharder: Sharder = _id_sharder):
    """One token per sequence through the absorbed path. tokens: (B,).
    The cache rides in the layer scans' carry, and each layer writes only
    its new row."""
    lengths = cache["length"]
    x = embed_tokens(cfg, params, tokens[:, None])

    def stack(mlp):
        def body(carry, scanned):
            h, ckv, kpe = carry
            lp, layer = scanned
            a, ckv, kpe = _attn_decode(cfg, lp["attn"], _ln(lp, "ln1", h), ckv, kpe,
                                       layer, lengths)
            h = h + a
            h = h + mlp(cfg, lp, _ln(lp, "ln2", h))
            return (h, ckv, kpe), None
        return body

    k = cfg.first_k_dense_replace
    carry = (x, cache["ckv"], cache["kpe"])
    carry, _ = jax.lax.scan(stack(_dense_mlp), carry, (params["dense"], jnp.arange(k)))
    carry, _ = jax.lax.scan(stack(_moe_mlp), carry,
                            (params["moe"], jnp.arange(k, cfg.n_layers)))
    h, ckv, kpe = carry
    logits = logits_from_hidden(cfg, params, L.rmsnorm(h, params["final_norm"]["scale"]))
    return logits[:, 0], {"ckv": ckv, "kpe": kpe, "length": lengths + 1}
