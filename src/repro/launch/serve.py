"""Serving driver: continuous batching with the stitched KV arena.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --smoke \
        --requests 24 --max-new 16
    PYTHONPATH=src python -m repro.launch.serve --arch h2o-danube-3-4b \
        --requests 8 --max-new 8 --prompt-lens 16,48     # full width, one chip

Submits a stream of variable-length prompts, decodes with continuous
batching, and reports both throughput and the arena's memory behaviour
(utilization, BestFit state mix) plus a replay comparison of the recorded
trace under the caching vs GMLake allocators. Each distinct prompt length
compiles its own prefill, so a full-width run keeps to a few lengths.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from ..configs import get_arch
from ..core import GB, run_workload
from ..models.api import family_of
from ..serve.engine import EngineConfig, ServeEngine
from ..utils.device import enable_compile_cache


def init_params(cfg, seed: int):
    """Random weights from ``seed``, drawn under ``jit`` so the f32 draw and
    the cast to the model dtype fuse: no f32 copy of a weight is ever
    resident (a full-width 4B model would not fit one chip beside it)."""
    return jax.jit(family_of(cfg).init_params, static_argnums=0)(
        cfg, jax.random.PRNGKey(seed)
    )


def serve(cfg, params, *, requests: int, max_new: int, max_batch: int,
          prompt_lens, seed: int):
    """Submit ``requests`` seeded prompts and drain the engine.

    Returns ``(engine, decode_steps, wall_seconds)``.
    """
    rng = np.random.default_rng(seed)
    eng = ServeEngine(cfg, params, EngineConfig(max_batch=max_batch))
    for _ in range(requests):
        plen = int(rng.choice(prompt_lens))
        eng.submit(rng.integers(0, cfg.vocab, size=plen), max_new=max_new)

    t0 = time.perf_counter()
    steps = 0
    while eng.waiting or eng.running:
        eng.step()
        steps += 1
        if steps > 10_000:
            raise RuntimeError("engine did not drain")
    return eng, steps, time.perf_counter() - t0


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--prompt-lens", default=list(range(8, 64)),
                    type=lambda s: [int(x) for x in s.split(",")],
                    help="comma list of prompt lengths to draw from "
                         "(default 8..63)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    entry = get_arch(args.arch)
    cfg = entry.smoke if args.smoke else entry.full
    fam = family_of(cfg)
    if fam.name not in ("dense", "moe", "vlm"):
        raise SystemExit(f"serve driver supports decoder-only families, got {fam.name}")

    params = init_params(cfg, args.seed)
    eng, steps, wall = serve(
        cfg, params, requests=args.requests, max_new=args.max_new,
        max_batch=args.max_batch, prompt_lens=args.prompt_lens, seed=args.seed,
    )

    report = eng.memory_report()
    # replay the engine's real allocation trace through both allocators
    replay = {}
    for name in ("caching", "gmlake"):
        r = run_workload(eng.recorder.trace, name, capacity_bytes=1 * GB)
        replay[name] = {
            "utilization": round(r.utilization, 4),
            "peak_reserved_mb": round(r.stats.peak_reserved / 2**20, 1),
            "oom": r.oom,
        }
    tokens = sum(len(r.generated) for r in eng.finished)
    out = {
        "arch": cfg.name,
        "device": jax.devices()[0].device_kind,
        "requests": args.requests,
        "answered": len(eng.finished),
        "tokens_generated": tokens,
        "decode_steps": steps,
        "wall_s": wall,
        "tokens_per_s": tokens / wall,
        "arena": {k: (round(v, 4) if isinstance(v, float) else v)
                  for k, v in report.items()},
        "trace_replay": replay,
    }
    print(json.dumps(out, indent=2, default=str))
    return out


if __name__ == "__main__":
    enable_compile_cache()
    main()
