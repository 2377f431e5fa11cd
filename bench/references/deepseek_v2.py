"""Plain reference of DeepSeek-V2 (multi-head latent attention, shared and
routed experts), for the ``dsv2lite`` configuration.

Written from the published equations (arXiv 2405.04434; the model's
``modeling_deepseek.py``), in ``jax.numpy`` and float32 at ``highest``
matmul precision, with no cache and no batching:

* RMSNorm (``rms_norm_eps``); the embedding, no scale; an output head of its
  own where ``tie_word_embeddings`` is false.
* Attention, decompressed: q = x Wq, split into ``qk_nope_head_dim`` and
  ``qk_rope_head_dim``; [c ; k_pe] = x W_kv_a with c RMS-normed; per head
  k = [c W_uk ; k_pe] (k_pe shared by the heads) and v = c W_uv. The rotary
  lanes are de-interleaved (even, then odd) and rotated half against half
  by YaRN's frequencies (``rope_scaling``: ``factor``, ``beta_fast``,
  ``beta_slow``, ``original_max_position_embeddings``), with cos and sin
  times mscale(``mscale``) / mscale(``mscale_all_dim``). Scores are causal
  and scaled by (nope + rope) ** -0.5 times mscale(``mscale_all_dim``) ** 2.
* Layers before ``first_k_dense_replace``: a SwiGLU MLP of
  ``intermediate_size``. The others: the router's softmax over
  ``n_routed_experts_published`` experts, the greedy top
  ``num_experts_per_tok``, weights renormalised only if ``norm_topk_prob``,
  times ``routed_scaling_factor``; plus ``n_shared_experts`` SwiGLU experts
  of ``moe_intermediate_size`` that every token passes through.

Departures from the published model, each the configuration's cut:

* Only ``n_routed_experts`` experts are held, from ``expert_offset``: the
  routed sum runs over those, computed for every token and weighted by its
  routing weight (0 where the expert is not among its top k). What the
  experts held elsewhere would add is left out, as the program leaves it.
* No auxiliary balance loss (``seq_aux``): it is a training term only.

It imports nothing of the program. ``make_weights`` draws the served weights
from a seed on the device, in one jitted call, in the layout the program
takes (``repro.models.deepseek_v2``: layers stacked, the leading dense ones
apart) and the configuration's dtype; the reference upcasts one layer at a
time inside its scans. ``lowp=True`` is the control: every matmul operand
rounded to float8 (e4m3), the precision below the configuration's bfloat16.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def key_of(seed: int):
    """A PRNG key for any non-negative seed (more than 32 bits allowed)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF), seed >> 31)


def _freeze(cfg: dict) -> tuple:
    out = []
    for k, v in sorted(cfg.items()):
        if isinstance(v, dict):
            v = tuple(sorted((a, b) for a, b in v.items() if not isinstance(b, (dict, list))))
        elif not isinstance(v, (int, float, str, type(None))):
            continue
        out.append((k, v))
    return tuple(out)


def _thaw(frozen) -> dict:
    return {k: dict(v) if isinstance(v, tuple) else v for k, v in frozen}


def make_weights(cfg: dict, seed: int):
    return _make_weights(_freeze(cfg), key_of(seed))


@functools.partial(jax.jit, static_argnums=0)
def _make_weights(frozen, key):
    cfg = _thaw(frozen)
    d, h, v = cfg["hidden_size"], cfg["num_attention_heads"], cfg["vocab_size"]
    r, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    rope, vd = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    k = cfg["first_k_dense_replace"]
    n = cfg["num_hidden_layers"] - k
    f, fe = cfg["intermediate_size"], cfg["moe_intermediate_size"]
    held, fs = cfg["n_routed_experts"], cfg["n_shared_experts"] * fe
    dtype = DTYPES[cfg["torch_dtype"]]
    ks = iter(jax.random.split(key, 40))

    def dense(shape, fan_in):
        return (jax.random.normal(next(ks), shape) / math.sqrt(fan_in)).astype(dtype)

    def scale(shape):  # norm gains near 1, not all 1, so a lost gain shows
        return (1.0 + 0.1 * jax.random.normal(next(ks), shape)).astype(dtype)

    def attn(m):
        return {"wq": dense((m, d, h * (nope + rope)), d),
                "wkv_a": dense((m, d, r + rope), d),
                "kv_norm": {"scale": scale((m, r))},
                "w_uk": dense((m, r, h, nope), r), "w_uv": dense((m, r, h, vd), r),
                "wo": dense((m, h * vd, d), h * vd)}

    def mlp(lead, width):
        return {"wi": dense(lead + (d, width), d), "wg": dense(lead + (d, width), d),
                "wo": dense(lead + (width, d), width)}

    params = {
        "embed": dense((v, d), d),
        "dense": {"ln1": {"scale": scale((k, d))}, "attn": attn(k),
                  "ln2": {"scale": scale((k, d))}, "mlp": mlp((k,), f)},
        "moe": {"ln1": {"scale": scale((n, d))}, "attn": attn(n),
                "ln2": {"scale": scale((n, d))},
                "router": dense((n, d, cfg["n_routed_experts_published"]), d),
                "experts": mlp((n, held), fe), "shared": mlp((n,), fs)},
        "final_norm": {"scale": scale((d,))},
    }
    if not cfg["tie_word_embeddings"]:
        params["lm_head"] = dense((d, v), d)
    return params


def _round(x, lowp: bool):
    x = x.astype(jnp.float32)
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32) if lowp else x


def _mm(spec, a, b, lowp):
    return jnp.einsum(spec, _round(a, lowp), _round(b, lowp),
                      precision=jax.lax.Precision.HIGHEST)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: dict) -> np.ndarray:
    """YaRN's per-pair frequencies (``DeepseekV2YarnRotaryEmbedding``)."""
    dim, base, rs = cfg["qk_rope_head_dim"], cfg["rope_theta"], cfg["rope_scaling"]
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    freq_inter = freq_extra / rs["factor"]
    orig = rs["original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / ((high + 0.001 if low == high else high)
                                                  - low), 0, 1)
    extra_mask = 1.0 - ramp
    return freq_inter * (1 - extra_mask) + freq_extra * extra_mask


def softmax_scale(cfg: dict) -> float:
    rs = cfg["rope_scaling"]
    m = yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(cfg, x, pos):
    """x (S, H, P): de-interleave, then rotate-half."""
    rs = cfg["rope_scaling"]
    m = yarn_mscale(rs["factor"], rs["mscale"]) / yarn_mscale(rs["factor"], rs["mscale_all_dim"])
    inv = jnp.asarray(yarn_inv_freq(cfg), jnp.float32)
    ang = pos[:, None].astype(jnp.float32) * inv  # (S, P/2)
    emb = jnp.concatenate([ang, ang], -1)[:, None, :]
    s, hh, p = x.shape
    x = x.reshape(s, hh, p // 2, 2).transpose(0, 1, 3, 2).reshape(s, hh, p)
    rotated = jnp.concatenate([-x[..., p // 2:], x[..., :p // 2]], -1)
    return x * (jnp.cos(emb) * m) + rotated * (jnp.sin(emb) * m)


@functools.partial(jax.jit, static_argnums=(0, 3))
def _forward(frozen, params, tokens, lowp):
    cfg = _thaw(frozen)
    h, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]
    r, nope, vd = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    top_k, scale = cfg["num_experts_per_tok"], softmax_scale(cfg)
    offset, held = cfg["expert_offset"], cfg["n_routed_experts"]
    s = tokens.shape[0]
    pos = jnp.arange(s)
    visible = pos[None, :] <= pos[:, None]
    x = params["embed"][tokens].astype(jnp.float32)

    def attention(p, a):
        q = _mm("sd,dk->sk", a, p["wq"], lowp).reshape(s, h, -1)
        ckv = _mm("sd,dk->sk", a, p["wkv_a"], lowp)
        c = _rms(ckv[:, :r], p["kv_norm"]["scale"], eps)
        k_pe = _rope(cfg, ckv[:, None, r:], pos)
        k = jnp.concatenate([_mm("sr,rhn->shn", c, p["w_uk"], lowp),
                             jnp.broadcast_to(k_pe, (s, h, k_pe.shape[-1]))], -1)
        q = jnp.concatenate([q[..., :nope], _rope(cfg, q[..., nope:], pos)], -1)
        v = _mm("sr,rhv->shv", c, p["w_uv"], lowp)

        def head(qkv):  # one head at a time: (S, S) scores
            qh, kh, vh = qkv
            sc = _mm("qd,kd->qk", qh, kh, lowp) * scale
            return _mm("qk,kd->qd", jax.nn.softmax(jnp.where(visible, sc, -jnp.inf), -1),
                       vh, lowp)

        o = jax.lax.map(head, (q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                               v.transpose(1, 0, 2)))  # (H, S, vd)
        return _mm("sk,kd->sd", o.transpose(1, 0, 2).reshape(s, h * vd), p["wo"], lowp)

    def swiglu(p, a):
        up = _mm("sd,df->sf", a, p["wi"], lowp)
        gate = _mm("sd,df->sf", a, p["wg"], lowp)
        return _mm("sf,fd->sd", jax.nn.silu(gate) * up, p["wo"], lowp)

    def routed(lp, a):
        probs = jax.nn.softmax(_mm("sd,de->se", a, lp["router"], lowp), -1)
        top_w, top_i = jax.lax.top_k(probs, top_k)
        if cfg["norm_topk_prob"]:
            top_w = top_w / top_w.sum(-1, keepdims=True)
        top_w = top_w * cfg["routed_scaling_factor"]
        mine = offset + jnp.arange(held)
        gate = jnp.sum(jnp.where(top_i[:, :, None] == mine, top_w[:, :, None], 0.0), 1)
        ex = lp["experts"]  # every held expert for every token: (E, S, d)
        up = _mm("sd,edf->esf", a, ex["wi"], lowp)
        g = _mm("sd,edf->esf", a, ex["wg"], lowp)
        out = _mm("esf,efd->esd", jax.nn.silu(g) * up, ex["wo"], lowp)
        return jnp.einsum("esd,se->sd", out, gate, precision=jax.lax.Precision.HIGHEST)

    def layer(mlp):
        def body(x, lp):
            lp = jax.tree.map(lambda w: w.astype(jnp.float32), lp)
            x = x + attention(lp["attn"], _rms(x, lp["ln1"]["scale"], eps))
            return x + mlp(lp, _rms(x, lp["ln2"]["scale"], eps)), None
        return body

    x, _ = jax.lax.scan(layer(lambda lp, a: swiglu(lp["mlp"], a)), x, params["dense"])
    x, _ = jax.lax.scan(layer(lambda lp, a: routed(lp, a) + swiglu(lp["shared"], a)),
                        x, params["moe"])
    x = _rms(x, params["final_norm"]["scale"].astype(jnp.float32), eps)
    if cfg["tie_word_embeddings"]:
        return _mm("sd,vd->sv", x, params["embed"], lowp)
    return _mm("sd,dv->sv", x, params["lm_head"], lowp)


def logits(cfg: dict, params, tokens, lowp: bool = False, pad_to: int = 512):
    """float32 logits of one sequence, one row per position. ``tokens`` is
    padded at the end to a multiple of ``pad_to``, so that few lengths
    compile; the rows past ``len(tokens)`` belong to the pad (causal, and
    routing is per token, so no earlier row sees it)."""
    padded = -(-len(tokens) // pad_to) * pad_to
    toks = np.zeros((padded,), np.int32)
    toks[: len(tokens)] = tokens
    return _forward(_freeze(cfg), params, jnp.asarray(toks), lowp)
