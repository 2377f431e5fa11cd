"""Uniform model API: every family exposes the same six entry points.

The launcher, trainer, server and dry-run all go through ``family_of(cfg)``
so adding an architecture is: write the module, register the family.

The registry's serving entry points, ``prefill`` and ``decode_step``, are
jitted once here, with the config (a frozen, hashable dataclass) and the
``sharder`` static. A caller that runs them eagerly, as ``ServeEngine``
does once per step, then gets one program per input shape instead of a
fresh lowering of the layer scan on every call. The module functions
(``transformer.prefill`` and the rest) stay plain: the consistency tests
compare against them, and ``train.step.make_serve_steps`` and
``launch/dryrun.py`` trace the registry's entry points inside their own
``jit``, where the inner one is inlined.

Each family also declares its KV layout (``kv_layout``): how many
allocation streams a layer keeps per sequence and how many elements one
token's row of each holds. ``ServeEngine`` sizes the stitched KV arena
from it.
"""

from __future__ import annotations

from dataclasses import dataclass, replace
from typing import Callable, Dict, Tuple

import jax

from . import deepseek_v2, moe, paligemma, rwkv6, transformer, whisper, zamba2


@dataclass(frozen=True)
class KVLayout:
    """Per layer, one allocation per stream per sequence, each holding
    ``n_kv * head_dim`` elements a token."""
    n_layers: int
    n_kv: int
    head_dim: int
    streams: Tuple[str, ...] = ("k", "v")


def heads_kv_layout(cfg) -> KVLayout:
    """K and V of ``n_kv x dh`` per layer."""
    return KVLayout(getattr(cfg, "n_layers", 1), getattr(cfg, "n_kv", 1),
                    getattr(cfg, "dh", 64))


def latent_kv_layout(cfg: deepseek_v2.DeepseekV2Config) -> KVLayout:
    """MLA: one row of latent plus rotary key per layer."""
    return KVLayout(cfg.n_layers, 1, cfg.latent_dim, ("latent",))


@dataclass(frozen=True)
class Family:
    name: str
    init_params: Callable
    param_axes: Callable
    loss_fn: Callable
    prefill: Callable
    decode_step: Callable
    init_cache: Callable
    cache_axes: Callable
    kv_layout: Callable = heads_kv_layout


def _jit_serving(fam: Family) -> Family:
    """``fam`` with ``prefill`` and ``decode_step`` jitted, config static."""
    def serving(fn):
        return jax.jit(fn, static_argnums=0, static_argnames="sharder")

    return replace(fam, prefill=serving(fam.prefill),
                   decode_step=serving(fam.decode_step))


_PLAIN: Dict[str, Family] = {
    "dense": Family(
        "dense", transformer.init_params, transformer.param_axes, transformer.loss_fn,
        transformer.prefill, transformer.decode_step, transformer.init_cache,
        transformer.cache_axes,
    ),
    "moe": Family(
        "moe", moe.init_params, moe.param_axes, moe.loss_fn,
        moe.prefill, moe.decode_step, moe.init_cache, transformer.cache_axes,
    ),
    "hybrid": Family(
        "hybrid", zamba2.init_params, zamba2.param_axes, zamba2.loss_fn,
        zamba2.prefill, zamba2.decode_step, zamba2.init_cache, zamba2.cache_axes,
    ),
    "ssm": Family(
        "ssm", rwkv6.init_params, rwkv6.param_axes, rwkv6.loss_fn,
        rwkv6.prefill, rwkv6.decode_step, rwkv6.init_cache, rwkv6.cache_axes,
    ),
    "audio": Family(
        "audio", whisper.init_params, whisper.param_axes, whisper.loss_fn,
        whisper.prefill, whisper.decode_step, whisper.init_cache, whisper.cache_axes,
    ),
    "vlm": Family(
        "vlm", paligemma.init_params, paligemma.param_axes, paligemma.loss_fn,
        paligemma.prefill, paligemma.decode_step, paligemma.init_cache,
        paligemma.cache_axes,
    ),
    "mla_moe": Family(
        "mla_moe", deepseek_v2.init_params, deepseek_v2.param_axes, deepseek_v2.loss_fn,
        deepseek_v2.prefill, deepseek_v2.decode_step, deepseek_v2.init_cache,
        deepseek_v2.cache_axes, latent_kv_layout,
    ),
}

FAMILIES: Dict[str, Family] = {k: _jit_serving(f) for k, f in _PLAIN.items()}


def family_of(cfg) -> Family:
    if isinstance(cfg, paligemma.PaliGemmaConfig):
        return FAMILIES["vlm"]
    if isinstance(cfg, moe.MoEConfig):
        return FAMILIES["moe"]
    if isinstance(cfg, transformer.TransformerConfig):
        return FAMILIES["dense"]
    if isinstance(cfg, zamba2.Zamba2Config):
        return FAMILIES["hybrid"]
    if isinstance(cfg, rwkv6.RWKV6Config):
        return FAMILIES["ssm"]
    if isinstance(cfg, whisper.WhisperConfig):
        return FAMILIES["audio"]
    if isinstance(cfg, deepseek_v2.DeepseekV2Config):
        return FAMILIES["mla_moe"]
    raise TypeError(f"unknown model config type {type(cfg)}")
