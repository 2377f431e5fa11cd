"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        --steps 200 --batch 8 --seq 256 --smoke

Runs the full production stack on the local devices (all of them, or the
first ``--devices``): sharded train step, deterministic data pipeline, async
checkpointing, fault-tolerant supervisor, optional offload arena. ``--smoke``
selects the reduced config so a ~100M-class model trains for a few hundred
steps on one host.
"""

from __future__ import annotations

import argparse
import json
import logging
import time
from pathlib import Path

import jax
import jax.numpy as jnp

from ..ckpt.checkpoint import CheckpointManager
from ..configs import get_arch
from ..data.pipeline import DataConfig, SyntheticTokens
from ..ft.supervisor import Supervisor, SupervisorConfig
from ..models.api import family_of
from ..parallel.sharding import make_rules, make_sharder, tree_shardings
from ..train import optimizer as opt
from ..train.step import TrainState, init_state, make_train_step, state_axes
from ..utils.device import enable_compile_cache
from .mesh import make_host_mesh

log = logging.getLogger("repro.train")


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", help="use the reduced config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="artifacts/ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--devices", type=int, default=None,
                    help="use the first N local devices (default: all)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    entry = get_arch(args.arch)
    cfg = entry.smoke if args.smoke else entry.full
    fam = family_of(cfg)

    mesh = make_host_mesh(model=args.model_parallel, n_devices=args.devices)
    rules = make_rules(mesh, kind="train", seq_parallel=False)
    sharder = make_sharder(mesh, rules)
    adamw = opt.AdamWConfig(lr=args.lr)

    key = jax.random.PRNGKey(args.seed)
    with mesh:
        state = init_state(cfg, adamw, key)
        axes = state_axes(cfg)
        state_sh = tree_shardings(
            jax.eval_shape(lambda: state), axes, rules, mesh, zero=entry.zero
        )
        state = jax.device_put(state, state_sh)
        step_fn = jax.jit(
            make_train_step(cfg, adamw, sharder, microbatches=args.microbatches),
            donate_argnums=(0,),
        )

        data = SyntheticTokens(DataConfig(
            vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch,
            seed=args.seed,
            patch_dim=cfg.d_model if fam.name == "vlm" else None,
            frame_dim=cfg.d_model if fam.name == "audio" else None,
        ))

        ckpt = CheckpointManager(args.ckpt_dir)
        sup = Supervisor(
            step_fn, data.batch_at, ckpt,
            SupervisorConfig(checkpoint_every=args.ckpt_every),
            state_shardings=state_sh,
        )
        t0 = time.time()
        state, history = sup.run(state, start_step=0, n_steps=args.steps)
        wall = time.time() - t0

    losses = [h["loss"] for h in history]
    result = {
        "arch": cfg.name,
        "devices": int(mesh.devices.size),
        "steps": len(history),
        "losses": losses,
        "first_loss": losses[0],
        "last_loss": losses[-1],
        "min_loss": min(losses),
        "wall_s": round(wall, 1),
        "steps_per_s": round(len(history) / wall, 3),
        "events": sup.events,
    }
    for h in history[:: max(1, args.log_every)]:
        log.info("step %5d loss %.4f", h["step"], h["loss"])
    print(json.dumps({k: v for k, v in result.items()
                      if k not in ("events", "losses")}, indent=2))
    return result


if __name__ == "__main__":
    logging.basicConfig(level=logging.INFO)
    enable_compile_cache()
    main()
