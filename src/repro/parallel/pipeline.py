"""Pipeline parallelism: GPipe microbatch schedule over a mesh axis.

The multi-pod mesh's ``pod`` axis can act as the stage axis: layers are
partitioned into ``n_stages`` contiguous groups; microbatches flow through
stages with ``ppermute`` boundary transfers inside ``shard_map``. The
schedule below is the classic GPipe flush (bubble = (S-1)/(M+S-1)); it is
expressed as a dense loop over ``M + S - 1`` ticks where every stage
computes every tick (idle ticks operate on garbage and are masked), which
keeps the program SPMD — no per-stage control flow.

This module is deliberately self-contained (used by the pipeline example
and tests; the main train path uses DP/TP/SP — PP composes when configured
via ``launch.train --pipeline``).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, List

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map


def pipeline_forward(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,  # pytree with leading (n_stages, ...) axis
    x_microbatches: jax.Array,  # (M, mb, S, d) input microbatches
    mesh: Mesh,
    stage_axis: str = "pod",
) -> jax.Array:
    """Run x through n_stages sequential stages; returns (M, mb, S, d)."""
    n_stages = mesh.shape[stage_axis]
    m = x_microbatches.shape[0]
    ticks = m + n_stages - 1

    def per_stage(params, xs):
        # params: this stage's slice; xs: full (M, mb, S, d) (only stage 0
        # reads it). Runs identically on every stage member.
        # shard_map keeps the sharded leading axis as size 1 — drop it so
        # stage_fn sees (L/S, ...) layer stacks
        params = jax.tree.map(lambda p: p[0], params)
        stage = jax.lax.axis_index(stage_axis)
        mb_shape = xs.shape[1:]
        buf = jnp.zeros(mb_shape, xs.dtype)  # current in-flight microbatch
        outs = jnp.zeros_like(xs)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 injects microbatch t (if any) — others take the
            # boundary value permuted from the previous stage
            inject = jnp.where(t < m, t, 0)
            x0 = jax.lax.dynamic_index_in_dim(xs, inject, 0, keepdims=False)
            cur = jnp.where(stage == 0, x0, buf)
            y = stage_fn(params, cur)
            # the last stage retires microbatch t - (S-1)
            retire = t - (n_stages - 1)
            valid = (retire >= 0) & (retire < m)
            idx = jnp.clip(retire, 0, m - 1)
            upd = jnp.where(
                valid & (stage == n_stages - 1),
                y,
                jax.lax.dynamic_index_in_dim(outs, idx, 0, keepdims=False),
            )
            outs = jax.lax.dynamic_update_index_in_dim(outs, upd, idx, 0)
            # boundary transfer stage i -> i+1
            perm = [(i, i + 1) for i in range(n_stages - 1)]
            buf = jax.lax.ppermute(y, stage_axis, perm)
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(tick, (buf, outs), jnp.arange(ticks))
        # only the last stage holds real outputs; broadcast them back
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
            stage_axis,
        )
        return outs

    spec_params = jax.tree.map(lambda _: P(stage_axis), stage_params)
    fn = shard_map(
        per_stage, mesh=mesh,
        in_specs=(spec_params, P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, x_microbatches)


def split_stages(stacked_params: Any, n_stages: int) -> Any:
    """(L, ...) stacked layer params -> (n_stages, L/n_stages, ...)."""

    def re(x):
        l = x.shape[0]
        assert l % n_stages == 0, f"{l} layers not divisible by {n_stages} stages"
        return x.reshape(n_stages, l // n_stages, *x.shape[1:])

    return jax.tree.map(re, stacked_params)
