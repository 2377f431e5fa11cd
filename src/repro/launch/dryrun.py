"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Inputs are ShapeDtypeStructs (no allocation); the run prints memory/cost
analysis and records roofline inputs (FLOPs, bytes, collective traffic) as
JSON under artifacts/dryrun/. Run as a program, it appends
``--xla_force_host_platform_device_count=512`` to ``XLA_FLAGS`` so the CPU
backend can stand in for the production meshes; importing the module
changes nothing. Usage:

    PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-135m --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod]
"""

import argparse
import json
import os
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import ARCHS, get_arch
from ..configs.shapes import (
    SHAPES,
    cache_specs,
    decode_token_specs,
    supports_long_context,
    token_batch_specs,
)
from ..models.api import family_of
from ..parallel.sharding import (
    batch_shardings,
    make_rules,
    make_sharder,
    tree_shardings,
)
from ..train import optimizer as opt
from ..train.step import TrainState, init_state, make_serve_steps, make_train_step, state_axes
from ..utils import hlo as hlo_utils
from ..utils.roofline import RooflineReport, model_flops
from .mesh import make_production_mesh

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def _adamw_for(entry) -> opt.AdamWConfig:
    dt = jnp.bfloat16 if entry.opt_dtype == "bfloat16" else jnp.float32
    return opt.AdamWConfig(moment_dtype=dt)


def lower_train(entry, cfg, shape, mesh):
    rules = make_rules(mesh, kind="train", seq_parallel=entry.seq_parallel,
                       pure_dp=entry.pure_dp)
    sharder = make_sharder(mesh, rules, zero_params=entry.zero_params)
    adamw = _adamw_for(entry)
    step_fn = make_train_step(cfg, adamw, sharder, microbatches=entry.microbatches)

    state_shapes = jax.eval_shape(lambda: init_state(cfg, adamw, jax.random.PRNGKey(0)))
    axes = state_axes(cfg)
    repl = NamedSharding(mesh, P())
    state_sh = TrainState(
        params=tree_shardings(state_shapes.params, axes.params, rules, mesh,
                              zero=entry.zero_params),
        opt=opt.OptState(
            mu=tree_shardings(state_shapes.opt.mu, axes.opt.mu, rules, mesh,
                              zero=entry.zero),
            nu=tree_shardings(state_shapes.opt.nu, axes.opt.nu, rules, mesh,
                              zero=entry.zero),
            count=repl,
        ),
        step=repl,
    )
    batch_specs = token_batch_specs(cfg, shape)
    batch_sh = batch_shardings(batch_specs, rules, mesh)
    jitted = jax.jit(
        step_fn,
        in_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, None),
        donate_argnums=(0,),
    )
    return jitted.lower(state_shapes, batch_specs)


def lower_prefill(entry, cfg, shape, mesh):
    rules = make_rules(mesh, kind="prefill", seq_parallel=entry.seq_parallel,
                       pure_dp=entry.pure_dp)
    sharder = make_sharder(mesh, rules, zero_params=entry.zero_params)
    fam = family_of(cfg)
    prefill_fn, _ = make_serve_steps(cfg, sharder)

    param_shapes = jax.eval_shape(lambda: fam.init_params(cfg, jax.random.PRNGKey(0)))
    param_sh = tree_shardings(param_shapes, fam.param_axes(cfg), rules, mesh,
                              zero=entry.zero_params)
    batch_specs = token_batch_specs(cfg, shape)
    batch_sh = batch_shardings(batch_specs, rules, mesh)
    cache_sp = cache_specs(cfg, shape)
    dec_rules = make_rules(mesh, kind="decode",
                           long_context=shape.name == "long_500k")
    cache_sh = tree_shardings(cache_sp, fam.cache_axes(cfg), dec_rules, mesh)
    jitted = jax.jit(
        prefill_fn,
        in_shardings=(param_sh, batch_sh, cache_sh),
        out_shardings=(None, cache_sh),
        donate_argnums=(2,),
    )
    return jitted.lower(param_shapes, batch_specs, cache_sp)


def lower_decode(entry, cfg, shape, mesh):
    rules = make_rules(mesh, kind="decode", long_context=shape.name == "long_500k",
                       pure_dp=entry.pure_dp)
    sharder = make_sharder(mesh, rules, zero_params=entry.zero_params)
    fam = family_of(cfg)
    _, decode_fn = make_serve_steps(cfg, sharder)

    param_shapes = jax.eval_shape(lambda: fam.init_params(cfg, jax.random.PRNGKey(0)))
    param_sh = tree_shardings(param_shapes, fam.param_axes(cfg), rules, mesh,
                              zero=entry.zero_params)
    cache_sp = cache_specs(cfg, shape)
    cache_sh = tree_shardings(cache_sp, fam.cache_axes(cfg), rules, mesh)
    tok_sp = decode_token_specs(shape)
    tok_sh = batch_shardings({"t": tok_sp}, rules, mesh)["t"]
    jitted = jax.jit(
        decode_fn,
        in_shardings=(param_sh, cache_sh, tok_sh),
        out_shardings=(None, cache_sh),
        donate_argnums=(1,),
    )
    return jitted.lower(param_shapes, cache_sp, tok_sp)


LOWER = {"train": lower_train, "prefill": lower_prefill, "decode": lower_decode}


def run_cell(arch_id: str, shape_name: str, multi_pod: bool, out_dir: Path) -> dict:
    entry = get_arch(arch_id)
    cfg = entry.full
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    record = {
        "arch": arch_id, "shape": shape_name, "mesh": mesh_name,
        "kind": shape.kind, "status": "ok",
    }

    if shape_name == "long_500k" and not supports_long_context(cfg):
        record["status"] = "skip"
        record["reason"] = "pure full-attention arch; long_500k needs sub-quadratic attention"
        return record

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    t0 = time.time()
    with mesh:
        lowered = LOWER[shape.kind](entry, cfg, shape, mesh)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    # --- analyses ---------------------------------------------------------
    try:
        mem = compiled.memory_analysis()
        record["memory_analysis"] = {
            k: int(getattr(mem, k))
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, k)
        }
        args_b = record["memory_analysis"].get("argument_size_in_bytes", 0)
        temp_b = record["memory_analysis"].get("temp_size_in_bytes", 0)
        record["peak_memory_per_device"] = args_b + temp_b
    except Exception as e:  # pragma: no cover - backend-dependent
        record["memory_analysis_error"] = str(e)

    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    # raw XLA numbers (NOTE: while/scan bodies counted once — see utils/hlo.py)
    record["xla_cost_analysis"] = {
        "flops": float(cost.get("flops", 0.0)),
        "bytes_accessed": float(
            cost.get("bytes accessed", cost.get("bytes accessed0{}", 0.0))
        ),
    }

    # scan-aware walk of the optimized per-device HLO
    hlo_text = compiled.as_text()
    stats = hlo_utils.analyze(hlo_text)
    record["flops_per_device"] = stats.flops
    record["bytes_per_device"] = stats.traffic_bytes
    record["collectives"] = stats.collectives
    record["collective_bytes_per_device"] = stats.collective_bytes
    record["hlo_bytes"] = len(hlo_text)
    record["model_flops"] = model_flops(cfg, shape.kind, shape.seq_len,
                                        shape.global_batch)
    record["n_devices"] = int(n_dev)
    record["lower_s"] = round(t_lower, 2)
    record["compile_s"] = round(t_compile, 2)

    rep = RooflineReport(
        arch=arch_id, shape=shape_name, mesh=mesh_name, kind=shape.kind,
        flops_per_device=record["flops_per_device"],
        bytes_per_device=record["bytes_per_device"],
        collective_bytes_per_device=record["collective_bytes_per_device"],
        model_flops=record["model_flops"], n_devices=int(n_dev),
        peak_memory_per_device=record.get("peak_memory_per_device"),
        collectives=record["collectives"],
    )
    record["roofline"] = {
        "t_compute": rep.t_compute, "t_memory": rep.t_memory,
        "t_collective": rep.t_collective, "bottleneck": rep.bottleneck,
        "useful_flops_fraction": rep.useful_flops_fraction,
        "roofline_fraction": rep.roofline_fraction,
    }
    return record


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="architecture id (default: all)")
    ap.add_argument("--shape", default=None, help="shape name (default: all)")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=str(ARTIFACTS))
    args = ap.parse_args()

    out_dir = Path(args.out)
    archs = [args.arch] if args.arch else sorted(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    n_ok = n_skip = n_fail = 0
    for multi_pod in meshes:
        mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
        mdir = out_dir / mesh_name
        mdir.mkdir(parents=True, exist_ok=True)
        for arch_id in archs:
            for shape_name in shapes:
                tag = f"{arch_id} x {shape_name} x {mesh_name}"
                try:
                    rec = run_cell(arch_id, shape_name, multi_pod, mdir)
                except Exception:
                    rec = {
                        "arch": arch_id, "shape": shape_name, "mesh": mesh_name,
                        "status": "fail", "error": traceback.format_exc(),
                    }
                (mdir / f"{arch_id}__{shape_name}.json").write_text(
                    json.dumps(rec, indent=2, default=str)
                )
                if rec["status"] == "ok":
                    n_ok += 1
                    r = rec["roofline"]
                    print(
                        f"OK   {tag}: compile={rec['compile_s']}s "
                        f"flops/dev={rec['flops_per_device']:.3e} "
                        f"coll={rec['collective_bytes_per_device']:.3e}B "
                        f"bottleneck={r['bottleneck']} "
                        f"roofline={r['roofline_fraction']:.3f}",
                        flush=True,
                    )
                elif rec["status"] == "skip":
                    n_skip += 1
                    print(f"SKIP {tag}: {rec['reason']}", flush=True)
                else:
                    n_fail += 1
                    print(f"FAIL {tag}:\n{rec['error']}", flush=True)
    print(f"\ndry-run summary: {n_ok} ok, {n_skip} skip, {n_fail} fail")
    if n_fail:
        raise SystemExit(1)


if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = " ".join(filter(None, [
        os.environ.get("XLA_FLAGS"), "--xla_force_host_platform_device_count=512"
    ]))
    main()
