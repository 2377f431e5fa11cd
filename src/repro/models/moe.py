"""Mixture-of-Experts transformer (dbrx-132b: 16e top-4, grok-1-314b: 8e top-2).

Token-choice top-k routing with capacity + sort-based dispatch: static
shapes (jit/pjit friendly), expert-parallel via the ``expert`` logical axis
on the (E, C, d) dispatch buffers and (L, E, d, f) expert weights. Attention
stack is shared with the dense transformer.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from . import layers as L
from .transformer import (
    Sharder,
    TransformerConfig,
    _apply_norm,
    _attn_block,
    _id_sharder,
    _norm_axes,
    _norm_init,
    _write_token,
    cache_axes,
    embed_tokens,
    init_cache,
    logits_from_hidden,
)


@dataclass(frozen=True)
class MoEConfig(TransformerConfig):
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    #: expert tensor-parallel split ("virtual experts"): each expert's FFN is
    #: split into ``expert_shards`` halves along d_ff, giving
    #: n_experts * expert_shards shardable units. Lets E=8 experts use a
    #: 16-way model axis (grok on the v5e pod) — EXPERIMENTS.md §Perf.
    expert_shards: int = 1
    #: local routing + all-to-all dispatch (shard_map) instead of the
    #: global-scatter pjit dispatch — EXPERIMENTS.md §Perf grok iteration 5
    a2a_dispatch: bool = False

    @property
    def n_virtual(self) -> int:
        return self.n_experts * self.expert_shards

    @property
    def ff_shard(self) -> int:
        assert self.d_ff % self.expert_shards == 0
        return self.d_ff // self.expert_shards

    @property
    def n_params(self) -> int:
        d, h, kv, dh, f, v = (
            self.d_model, self.n_heads, self.n_kv, self.dh, self.d_ff, self.vocab,
        )
        per_layer = d * (h * dh) + 2 * d * (kv * dh) + (h * dh) * d
        per_layer += self.n_experts * d * f * (3 if self.gated else 2)
        per_layer += d * self.n_experts + 2 * d
        return self.n_layers * per_layer + v * d + d

    @property
    def n_active_params(self) -> int:
        """Parameters touched per token (for MoE roofline: 6*N_active*D)."""
        d, h, kv, dh, f, v = (
            self.d_model, self.n_heads, self.n_kv, self.dh, self.d_ff, self.vocab,
        )
        per_layer = d * (h * dh) + 2 * d * (kv * dh) + (h * dh) * d
        per_layer += self.top_k * d * f * (3 if self.gated else 2)
        per_layer += d * self.n_experts
        return self.n_layers * per_layer + v * d


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------


def init_params(cfg: MoEConfig, key) -> Dict:
    from .transformer import layer_init  # attention + norms

    k_embed, k_layers, k_moe, k_head = jax.random.split(key, 4)
    params = {
        "embed": L.dense_init(k_embed, (cfg.vocab, cfg.d_model), in_axis=1, dtype=cfg.dtype),
        "layers": layer_init(cfg, k_layers),
        "final_norm": _norm_init(cfg, (cfg.d_model,)),
    }
    # replace the dense MLP with experts; weights live in the "virtual
    # expert" layout (E * expert_shards, d, ff/expert_shards)
    ks = jax.random.split(k_moe, 4)
    ldf = (cfg.n_layers, cfg.n_virtual, cfg.d_model, cfg.ff_shard)
    lfd = (cfg.n_layers, cfg.n_virtual, cfg.ff_shard, cfg.d_model)
    moe = {
        "router": L.dense_init(ks[0], (cfg.n_layers, cfg.d_model, cfg.n_experts),
                               in_axis=1, dtype=jnp.float32),
        "wi": L.dense_init(ks[1], ldf, in_axis=2, dtype=cfg.dtype),
        "wo": L.dense_init(ks[2], lfd, in_axis=2, dtype=cfg.dtype),
    }
    if cfg.gated:
        moe["wg"] = L.dense_init(ks[3], ldf, in_axis=2, dtype=cfg.dtype)
    params["layers"]["mlp"] = moe
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(
            k_head, (cfg.d_model, cfg.vocab), in_axis=0, dtype=cfg.dtype
        )
    return params


def param_axes(cfg: MoEConfig) -> Dict:
    from .transformer import param_axes as dense_axes

    axes = dense_axes(cfg)
    moe = {
        "router": ("layers", "embed", None),
        "wi": ("layers", "expert", "embed", "ffn"),
        "wo": ("layers", "expert", "ffn", "embed"),
    }
    if cfg.gated:
        moe["wg"] = ("layers", "expert", "embed", "ffn")
    axes["layers"]["mlp"] = moe
    return axes


# ---------------------------------------------------------------------------
# MoE FFN: token-choice top-k with capacity
# ---------------------------------------------------------------------------


def _expert_slots(flat_e: jax.Array):
    """Assignments sorted by expert: ``(order, sorted_e, rank)``, where
    ``rank`` is each sorted assignment's place among its expert's."""
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    rank = jnp.arange(flat_e.shape[0]) - jnp.searchsorted(sorted_e, sorted_e, side="left")
    return order, sorted_e, rank


def moe_apply(cfg: MoEConfig, p: Dict, x: jax.Array, sharder: Sharder):
    """x (B, S, d) -> (out (B, S, d), aux_loss scalar)."""
    mesh = getattr(sharder, "mesh", None)
    if cfg.a2a_dispatch and mesh is not None:
        from .moe_a2a import moe_apply_a2a

        zero = "data" if getattr(sharder, "zero_params", False) else None
        return moe_apply_a2a(cfg, p, x, mesh, zero_axis=zero)
    # ZeRO-3 (zero_params) stores expert weights data-sharded; re-constrain
    # the per-layer slice to its TP-only layout HERE so XLA emits one small
    # per-layer all-gather instead of flowing partial contractions through
    # the token buffers (26.6 TB/step of all-reduce measured without this —
    # EXPERIMENTS.md §Perf grok iteration 3)
    p = dict(p)
    for key_ in ("wi", "wg"):
        if key_ in p:
            p[key_] = sharder(p[key_], ("expert", "embed", "ffn"))
    p["wo"] = sharder(p["wo"], ("expert", "ffn", "embed"))
    b, s, d = x.shape
    n_tok = b * s
    e, k = cfg.n_experts, cfg.top_k
    xf = x.reshape(n_tok, d)

    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)  # (T, E)
    topv, topi = jax.lax.top_k(probs, k)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)

    # capacity floor: tiny (decode-sized) batches must never drop tokens —
    # a hot expert can legitimately receive every token of a small batch
    capacity = max(int(cfg.capacity_factor * n_tok * k / e), min(n_tok, 16))
    order, sorted_e, rank = _expert_slots(topi.reshape(-1))
    tok = order // k  # source token per sorted slot

    # dispatch: (E, C, d); slots past capacity are dropped
    buf = jnp.zeros((e, capacity, d), x.dtype)
    buf = buf.at[sorted_e, rank].set(xf[tok], mode="drop")

    if cfg.expert_shards > 1:
        # virtual experts: every token buffer feeds its expert's FFN shards
        buf = jnp.repeat(buf, cfg.expert_shards, axis=0)  # (Ev, C, d)
    buf = sharder(buf, ("expert", "capacity", "embed"))

    h = jnp.einsum("ecd,edf->ecf", buf, p["wi"])
    if cfg.gated:
        h = L.ACTIVATIONS[cfg.act](jnp.einsum("ecd,edf->ecf", buf, p["wg"])) * h
    else:
        h = L.ACTIVATIONS[cfg.act](h)
    y = jnp.einsum("ecf,efd->ecd", h, p["wo"])
    y = sharder(y, ("expert", "capacity", "embed"))
    if cfg.expert_shards > 1:
        # partial outputs of the ff shards sum back to real experts
        y = y.reshape(e, cfg.expert_shards, capacity, d).sum(1)

    # combine: gather expert outputs back to token slots, weighted
    gathered = y.at[sorted_e, rank].get(mode="fill", fill_value=0)  # (T*k, d)
    w = topv.reshape(-1)[order].astype(y.dtype)
    out = jnp.zeros((n_tok, d), y.dtype).at[tok].add(gathered * w[:, None])

    # load-balancing auxiliary loss (Switch/GShard style)
    dispatch_frac = jnp.mean(
        (jax.nn.one_hot(topi, e, dtype=jnp.float32)).sum(1), axis=0
    )  # fraction of tokens whose top-k includes e (scaled by k)
    prob_frac = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(dispatch_frac / k * prob_frac)
    return out.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# MoE FFN with shared experts and a held share of the routed ones
# (DeepSeek-V2): dropless, no capacity
# ---------------------------------------------------------------------------


def route(x: jax.Array, router: jax.Array, top_k: int, norm_topk_prob: bool,
          scaling: float = 1.0):
    """Greedy softmax top-k over every routed expert, in float32.

    x (T, d), router (d, E) -> (weights (T, k) float32, experts (T, k)).
    The weights are the softmax probabilities themselves unless
    ``norm_topk_prob`` renormalises them over the k chosen, then times
    ``scaling``."""
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), router.astype(jnp.float32))
    weights, experts = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights * scaling, experts


def held_experts(p: Dict, x: jax.Array, weights: jax.Array, experts: jax.Array,
                 offset: int, act: str = "silu") -> jax.Array:
    """The routed part of the result that the experts held here give.

    ``p`` holds experts ``[offset, offset + H)`` of the router's, as gated
    FFNs ``wi``/``wg`` (H, d, f) and ``wo`` (H, f, d). Each assignment
    (token, expert) that lands on a held expert is computed and weighted by
    its routing weight; the others are left out. Dropless: top-k picks k
    distinct experts, so no expert is given one token twice, and a slot per
    token (H, T, d) holds every assignment. x (T, d) -> (T, d)."""
    n_tok, d = x.shape
    held = p["wi"].shape[0]
    k = experts.shape[1]
    local = experts.reshape(-1) - offset
    local = jnp.where((local >= 0) & (local < held), local, held)  # held: not here
    order, sorted_e, rank = _expert_slots(local)
    # source token of each (expert, slot); n_tok (a zero row) where empty
    src = jnp.full((held, n_tok), n_tok, jnp.int32).at[sorted_e, rank].set(
        (order // k).astype(jnp.int32), mode="drop")
    xpad = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])
    buf = xpad[src]  # (H, T, d)
    h = jnp.einsum("etd,edf->etf", buf, p["wi"])
    h = L.ACTIVATIONS[act](jnp.einsum("etd,edf->etf", buf, p["wg"])) * h
    y = jnp.einsum("etf,efd->etd", h, p["wo"])
    # each assignment's (expert, slot), back in token order; off-share ones
    # point past the held experts and read 0
    slot_e = jnp.zeros_like(local).at[order].set(sorted_e)
    slot_r = jnp.zeros_like(local).at[order].set(rank)
    picked = y.at[slot_e, slot_r].get(mode="fill", fill_value=0).reshape(n_tok, k, d)
    out = jnp.einsum("tkd,tk->td", picked, weights, preferred_element_type=jnp.float32)
    return out.astype(x.dtype)


def shared_moe(p: Dict, x: jax.Array, *, top_k: int, offset: int,
               norm_topk_prob: bool, scaling: float, act: str = "silu") -> jax.Array:
    """Routed experts (the held share) plus the shared experts, which every
    token passes through in full. ``p``: ``router`` (d, E), ``experts``
    (see ``held_experts``), ``shared`` (a gated MLP whose width is the
    shared experts' together). x (..., d) -> (..., d)."""
    xf = x.reshape(-1, x.shape[-1])
    with jax.named_scope("moe.route"):
        weights, experts = route(xf, p["router"], top_k, norm_topk_prob, scaling)
    with jax.named_scope("moe.experts"):
        y = held_experts(p["experts"], xf, weights, experts, offset, act)
    with jax.named_scope("moe.shared"):
        y = y + L.mlp_apply(p["shared"], xf, act, gated=True)
    return y.reshape(x.shape)


# ---------------------------------------------------------------------------
# forward / loss / serving
# ---------------------------------------------------------------------------


def _block(cfg, lp, x, positions, prefix_len, sharder):
    a, kv = _attn_block(cfg, lp["attn"], _apply_norm(cfg, lp["ln1"], x), positions,
                        prefix_len, sharder)
    x = x + a
    x = sharder(x, ("batch", "seq", "embed"))
    m, aux = moe_apply(cfg, lp["mlp"], _apply_norm(cfg, lp["ln2"], x), sharder)
    m = sharder(m, ("batch", "seq", "embed"))
    return x + m, kv, aux


def forward(cfg, params, x, positions, prefix_len=None,
            sharder: Sharder = _id_sharder, collect_kv: bool = False):
    def body(carry, lp):
        h, aux_sum = carry
        out, kv, aux = _block(cfg, lp, h, positions, prefix_len, sharder)
        return (out, aux_sum + aux), kv if collect_kv else None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    (h, aux), kvs = jax.lax.scan(body_fn, (x, jnp.float32(0.0)), params["layers"])
    h = _apply_norm(cfg, params["final_norm"], h)
    return h, aux / cfg.n_layers, kvs


def loss_fn(cfg: MoEConfig, params, batch, sharder: Sharder = _id_sharder):
    tokens = batch["tokens"]
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = embed_tokens(cfg, params, tokens)
    x = sharder(x, ("batch", "seq", "embed"))
    h, aux, _ = forward(cfg, params, x, positions, sharder=sharder)
    logits = logits_from_hidden(cfg, params, h[:, :-1])
    return L.softmax_xent(logits, tokens[:, 1:], batch.get("loss_mask")) + (
        cfg.aux_loss_weight * aux
    )


def prefill(cfg, params, batch, cache, sharder: Sharder = _id_sharder):
    tokens = batch["tokens"]
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = embed_tokens(cfg, params, tokens)
    h, _aux, kvs = forward(cfg, params, x, positions, sharder=sharder, collect_kv=True)
    k, v = kvs
    cache = {
        "k": jax.lax.dynamic_update_slice(cache["k"], k.astype(cfg.dtype), (0, 0, 0, 0, 0)),
        "v": jax.lax.dynamic_update_slice(cache["v"], v.astype(cfg.dtype), (0, 0, 0, 0, 0)),
        "length": jnp.full((b,), s, jnp.int32),
    }
    return logits_from_hidden(cfg, params, h[:, -1:]), cache


def decode_step(cfg, params, cache, tokens, sharder: Sharder = _id_sharder):
    b = tokens.shape[0]
    lengths = cache["length"]
    x = embed_tokens(cfg, params, tokens[:, None])
    positions = lengths[:, None]

    def body(h, scanned):
        lp, kc, vc = scanned
        xin = _apply_norm(cfg, lp["ln1"], h)
        hh, kv_, dh = cfg.n_heads, cfg.n_kv, cfg.dh
        q = jnp.einsum("bsd,dh->bsh", xin, lp["attn"]["wq"]).reshape(b, 1, hh, dh)
        kk = jnp.einsum("bsd,dh->bsh", xin, lp["attn"]["wk"]).reshape(b, 1, kv_, dh)
        vv = jnp.einsum("bsd,dh->bsh", xin, lp["attn"]["wv"]).reshape(b, 1, kv_, dh)
        q = L.apply_rope(q, positions, cfg.rope_theta)
        kk = L.apply_rope(kk, positions, cfg.rope_theta)
        kc = _write_token(kc, kk.astype(kc.dtype), lengths)
        vc = _write_token(vc, vv.astype(vc.dtype), lengths)
        o = L.decode_attention_dense(q, kc, vc, lengths + 1, window=cfg.window)
        h = h + jnp.einsum("bsh,hd->bsd", o.reshape(b, 1, hh * dh), lp["attn"]["wo"])
        m, _aux = moe_apply(cfg, lp["mlp"], _apply_norm(cfg, lp["ln2"], h), _id_sharder)
        return h + m, (kc, vc)

    x, (new_k, new_v) = jax.lax.scan(body, x, (params["layers"], cache["k"], cache["v"]))
    h = _apply_norm(cfg, params["final_norm"], x)
    logits = logits_from_hidden(cfg, params, h)
    return logits[:, 0], {"k": new_k, "v": new_v, "length": lengths + 1}
