"""Expert-parallel MoE dispatch with LOCAL routing + all-to-all (shard_map).

The pjit auto-partitioned dispatch routes over the GLOBAL token set: the
scatter into the (E, C, d) buffer and the gather back both carry global
indices, which the SPMD partitioner can only honor by all-reducing
buffer-sized partials — measured 25-37 TB/step on grok-1-314b train_4k
(EXPERIMENTS.md §Perf). Production MoE systems route LOCALLY and exchange
token blocks with one all-to-all over the expert axis. This module is that
design:

  per device (data-rank r, model-rank m):
    1. local top-k routing over the device's T_loc tokens (no comm)
    2. local dispatch buffer (Ev, C_loc, d), C_loc = cf * T_loc * k / E
    3. all-to-all over 'model': device m receives every rank's slot for
       virtual expert m -> (1, Ev * C_loc, d)
    4. [ZeRO] all-gather this layer's expert weights over 'data' (~200 MB)
    5. local expert FFN (MXU matmuls)
    6. reverse all-to-all; virtual-shard partial sums; local weighted combine

Comm per layer: 2 all-to-alls of the dispatch buffer (~top_k * activation
bytes) + the optional weight gather — O(activations), not O(buffer * world).
Differentiable end-to-end (shard_map transposes the collectives).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from . import layers as L


def _local_dispatch(cfg, xf, router_w):
    """Local routing of xf (T_loc, d). Returns buf, combine metadata."""
    t_loc, d = xf.shape
    e, k = cfg.n_experts, cfg.top_k
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)

    capacity = max(int(cfg.capacity_factor * t_loc * k / e), min(t_loc, 16))
    flat_e = topi.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    rank = jnp.arange(t_loc * k) - jnp.searchsorted(sorted_e, sorted_e, side="left")
    tok = order // k
    buf = jnp.zeros((e, capacity, d), xf.dtype)
    buf = buf.at[sorted_e, rank].set(xf[tok], mode="drop")
    meta = (sorted_e, rank, tok, topv.reshape(-1)[order], probs, topi, capacity)
    return buf, meta


def _local_combine(cfg, y, meta, t_loc, d):
    sorted_e, rank, tok, w, probs, topi, capacity = meta
    gathered = y.at[sorted_e, rank].get(mode="fill", fill_value=0)
    out = jnp.zeros((t_loc, d), y.dtype).at[tok].add(
        gathered * w.astype(y.dtype)[:, None]
    )
    e = cfg.n_experts
    dispatch_frac = jnp.mean(jax.nn.one_hot(topi, e, dtype=jnp.float32).sum(1), 0)
    aux = e * jnp.sum(dispatch_frac / cfg.top_k * jnp.mean(probs, 0))
    return out, aux


def moe_apply_a2a(
    cfg,
    p: Dict,
    x: jax.Array,  # (B, S, d)
    mesh: Mesh,
    *,
    batch_axes=("pod", "data"),
    seq_axis: Optional[str] = "model",
    expert_axis: str = "model",
    zero_axis: Optional[str] = None,  # weights additionally sharded here
):
    """shard_map MoE FFN. Returns (out (B, S, d), aux scalar)."""
    b, s, d = x.shape
    names = set(mesh.axis_names)
    batch_axes = tuple(a for a in batch_axes if a in names)
    seq_axis = seq_axis if (seq_axis in names and s % mesh.shape[seq_axis] == 0) else None
    ev = cfg.n_virtual
    n_exp_shards = mesh.shape[expert_axis]
    assert ev % n_exp_shards == 0, (ev, n_exp_shards)

    wspec_tail = {"wi": (None, zero_axis), "wg": (None, zero_axis),
                  "wo": (zero_axis, None)}

    def local(xl, router_w, wi, wo, wg):
        bl, sl, _ = xl.shape
        t_loc = bl * sl
        xf = xl.reshape(t_loc, d)
        buf, meta = _local_dispatch(cfg, xf, router_w)  # (E, C_loc, d)
        if cfg.expert_shards > 1:
            buf = jnp.repeat(buf, cfg.expert_shards, axis=0)  # (Ev, C_loc, d)
        # all-to-all: split virtual experts across the expert axis, gather
        # every rank's slots for the local expert(s)
        buf = jax.lax.all_to_all(
            buf, expert_axis, split_axis=0, concat_axis=1, tiled=True
        )  # (Ev/n_shards, n_shards*C_loc, d)
        if zero_axis is not None:
            wi = jax.lax.all_gather(wi, zero_axis, axis=2, tiled=True)
            wo = jax.lax.all_gather(wo, zero_axis, axis=1, tiled=True)
            if cfg.gated:
                wg = jax.lax.all_gather(wg, zero_axis, axis=2, tiled=True)
        h = jnp.einsum("ecd,edf->ecf", buf, wi)
        if cfg.gated:
            h = L.ACTIVATIONS[cfg.act](jnp.einsum("ecd,edf->ecf", buf, wg)) * h
        else:
            h = L.ACTIVATIONS[cfg.act](h)
        y = jnp.einsum("ecf,efd->ecd", h, wo)
        y = jax.lax.all_to_all(
            y, expert_axis, split_axis=1, concat_axis=0, tiled=True
        )  # (Ev, C_loc, d)
        if cfg.expert_shards > 1:
            y = y.reshape(cfg.n_experts, cfg.expert_shards, -1, d).sum(1)
        out, aux = _local_combine(cfg, y, meta, t_loc, d)
        aux = jax.lax.pmean(aux, tuple(mesh.axis_names))  # replicated scalar
        return out.reshape(bl, sl, d), aux

    x_spec = P(batch_axes or None, seq_axis, None)
    w_specs = {
        k: P(expert_axis, *wspec_tail[k]) for k in ("wi", "wg", "wo")
    }
    wg = p.get("wg")
    fn = shard_map(
        local,
        mesh=mesh,
        in_specs=(x_spec, P(), w_specs["wi"], w_specs["wo"],
                  w_specs["wg"] if wg is not None else P()),
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    out, aux = fn(x, p["router"].astype(jnp.float32), p["wi"], p["wo"],
                  wg if wg is not None else jnp.zeros((), cfg.dtype))
    return out, aux
