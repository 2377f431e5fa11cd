"""Chip smoke: run the system's main paths once on a TPU and check them.

    python chip_smoke.py             # one chip: serving + the stitch kernels
    python chip_smoke.py --chips 4   # four chips: data-parallel training only

One chip:
  serve    h2o-danube-3-4b at published widths (24 layers, d_model 3840,
           bf16 weights from a seed) answers 8 seeded requests through
           ``ServeEngine``; every generated token must be (within bf16
           noise) the argmax of a full-sequence forward pass over the same
           tokens, the reference for the prefill + dense-cache decode path.
  kernels  ``Arena.store``/``load`` (stitch scatter/gather) over a
           gmlake-stitched, non-contiguous allocation must round-trip
           exactly, and ``StitchedKVCache.write_tokens`` +
           ``decode_attention`` must match the jnp reference, all at
           h2o-danube-3-4b's KV geometry (8 kv heads x 120, bf16).
Four chips (``--chips 4``):
  train    smollm-135m at published widths trains a few steps on a 4-way
           data mesh and on one device of the same process; the per-step
           losses must agree.

Every phase passes or raises. The last line of stdout is the JSON result;
with no TPU the script exits non-zero before printing it.
"""

from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_arch  # noqa: E402
from repro.core.arena import Arena, ArenaConfig  # noqa: E402
from repro.core.kvcache import KVCacheConfig, StitchedKVCache  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch import serve as serve_mod  # noqa: E402
from repro.launch import train as train_mod  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.utils.device import enable_compile_cache, require_tpu  # noqa: E402

SEED = 0
SERVE_ARCH = "h2o-danube-3-4b"
TRAIN_ARCH = "smollm-135m"
#: a served token may trail the reference argmax by this many standard
#: deviations of the reference logits (bf16 noise between the two paths;
#: a wrong token trails by several)
TOKEN_MARGIN_STD = 0.25
#: decode attention: bf16 operands, f32 accumulation, outputs of order 0.1
ATTN_TOL = 2e-2
#: 4-way data-parallel vs one-device loss, relative (bf16 weights, other
#: reduction order)
LOSS_RTOL = 1e-2


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_bytes(device) -> int:
    return device.memory_stats()["peak_bytes_in_use"]


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# ---------------------------------------------------------------------------
# one chip: serving
# ---------------------------------------------------------------------------


def serve_phase(device) -> None:
    cfg = get_arch(SERVE_ARCH).full
    t0 = time.perf_counter()
    params = serve_mod.init_params(cfg, SEED)
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"[serve] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{n_params} params ({cfg.dtype.__name__}) initialised in "
        f"{time.perf_counter() - t0:.1f} s")

    n_req, max_new = 8, 8
    eng, steps, wall = serve_mod.serve(
        cfg, params, requests=n_req, max_new=max_new, max_batch=8,
        prompt_lens=(16, 48), seed=SEED,
    )
    tokens = sum(len(r.generated) for r in eng.finished)
    log(f"[serve] requests answered {len(eng.finished)}/{n_req}, tokens "
        f"generated {tokens}, decode steps {steps}, wall {wall:.2f} s "
        "(compilation included)")
    log(f"[serve] memory_report {json.dumps(eng.memory_report(), default=str)}")
    log(f"[serve] device peak_bytes_in_use {peak_bytes(device)}")
    check(len(eng.finished) == n_req, "not every request was answered")
    for r in eng.finished:
        check(len(r.generated) == max_new, f"request {r.req_id} short")
        check(all(0 <= t < cfg.vocab for t in r.generated),
              f"request {r.req_id} produced an out-of-vocab token")
    check(eng.memory_report()["active_bytes"] == 0, "KV arena not drained")

    # reference: a full forward pass over prompt + generated tokens; each
    # served token must be the reference argmax up to bf16 noise
    @jax.jit
    def forward_logits(params, tokens):
        s = tokens.shape[1]
        x = T.embed_tokens(cfg, params, tokens)
        h, _ = T.forward(cfg, params, x, jnp.arange(s)[None, :])
        return T.logits_from_hidden(cfg, params, h)[0].astype(jnp.float32)

    worst = 0.0
    one_per_length = {len(r.prompt): r for r in eng.finished}
    for req in one_per_length.values():
        seq = np.concatenate([req.prompt, np.asarray(req.generated[:-1], np.int32)])
        logits = np.asarray(forward_logits(params, jnp.asarray(seq[None, :])))
        rows = logits[len(req.prompt) - 1:]  # predicts generated[0], ...
        margin = (rows.max(-1) - rows[np.arange(max_new), req.generated]) / rows.std(-1)
        worst = max(worst, float(margin.max()))
        log(f"[serve] request {req.req_id} (prompt {len(req.prompt)}): "
            f"served-token margin below reference argmax, in logit std: "
            f"{np.round(margin, 4).tolist()}")
    check(worst <= TOKEN_MARGIN_STD,
          f"served tokens trail the reference by {worst} std > {TOKEN_MARGIN_STD}")
    log(f"[serve] ok: worst margin {worst:.4f} std <= {TOKEN_MARGIN_STD}")


# ---------------------------------------------------------------------------
# one chip: stitch kernels at danube's KV geometry
# ---------------------------------------------------------------------------


def kernel_phase(device) -> None:
    danube = get_arch(SERVE_ARCH).full
    kv_cfg = KVCacheConfig(n_layers=2, n_kv=danube.n_kv, head_dim=danube.dh,
                           n_chunks=64)
    log(f"[kernels] KV geometry {danube.n_kv}x{danube.dh} bf16: "
        f"{kv_cfg.chunk_tokens} tokens per 2 MiB chunk as "
        f"({kv_cfg.chunk_rows}, {kv_cfg.row_lanes})")
    rng = np.random.default_rng(SEED)

    # stitch scatter/gather: fill a 7-chunk arena, free two non-adjacent
    # allocations, and take 5 chunks, which gmlake can only stitch
    arena = Arena(ArenaConfig(n_chunks=7, chunk_shape=(kv_cfg.chunk_rows,
                                                       kv_cfg.row_lanes)))
    ce = arena.config.chunk_elems
    held = [arena.alloc_elems(n * ce) for n in (2, 1, 3, 1)]
    arena.free(held[0])
    arena.free(held[2])
    big = arena.alloc_elems(5 * ce - 1000)
    extents = [(e.start, e.n) for e in big.block.extents]
    check(len(extents) > 1, f"allocation not stitched: {extents}")
    x = jnp.asarray(rng.standard_normal((5 * ce - 1000,)), jnp.bfloat16)
    arena.store(big, x)
    y = arena.load(big, x.shape)
    cmap = arena.chunk_map(big)
    gathered = ops.gather(arena.buf, cmap)
    store_err = float(jnp.max(jnp.abs(y.astype(jnp.float32) - x.astype(jnp.float32))))
    gather_err = float(jnp.max(jnp.abs(
        gathered.astype(jnp.float32) - ops.gather_ref(arena.buf, cmap).astype(jnp.float32))))
    log(f"[kernels] stitch scatter/gather over extents {extents}: "
        f"store/load max err {store_err}, gather vs jnp max err {gather_err}")
    check(store_err == 0.0 and gather_err == 0.0, "stitch copy is not exact")

    # stitched KV: sequences grow across chunks, so their page tables are
    # stitched; write K/V, then decode with the Pallas kernel
    kv = StitchedKVCache(kv_cfg)
    tc = kv_cfg.chunk_tokens
    lens = {0: tc - 5, 1: 300, 2: 2 * tc + 7, 3: 1}
    for sid, n in lens.items():
        kv.add_sequence(sid, n)
    kv.append_tokens(0, 40)
    lens[0] += 40
    layer = 1
    for sid, n in lens.items():
        for name in ("k", "v"):
            t = jnp.asarray(rng.standard_normal((n, danube.n_kv, danube.dh)), jnp.bfloat16)
            kv.write_tokens(sid, layer, name, 0, t)
    tables = {sid: np.asarray(kv.page_table([sid], layer, "k")[0][0]).tolist()
              for sid in lens}
    log(f"[kernels] K page tables {tables}")
    check(any(np.any(np.diff(t) != 1) for t in tables.values() if len(t) > 1),
          "no page table is stitched")
    seq_ids = list(lens)
    q = jnp.asarray(rng.standard_normal((len(seq_ids), danube.n_heads, danube.dh)),
                    jnp.bfloat16)
    out = kv.decode_attention(seq_ids, layer, q)
    ptk, sl = kv.page_table(seq_ids, layer, "k")
    ptv, _ = kv.page_table(seq_ids, layer, "v", pad_chunks=ptk.shape[1])
    ref = ops.decode_attention_ref(q, kv.arena.buf, kv.arena.buf, ptk, sl, ptv,
                                   n_kv=kv_cfg.n_kv)
    attn_err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))))
    log(f"[kernels] stitched decode attention over lengths {list(lens.values())}: "
        f"max err vs jnp reference {attn_err} (tol {ATTN_TOL}), "
        f"finite {bool(jnp.all(jnp.isfinite(out)))}")
    check(bool(jnp.all(jnp.isfinite(out))) and attn_err <= ATTN_TOL,
          f"decode attention error {attn_err} > {ATTN_TOL}")
    log(f"[kernels] ok; device peak_bytes_in_use {peak_bytes(device)}")


# ---------------------------------------------------------------------------
# four chips: data-parallel training vs one device
# ---------------------------------------------------------------------------


def train_phase(n_devices: int) -> None:
    runs = {}
    for n in (n_devices, 1):
        with tempfile.TemporaryDirectory() as ckpt:
            res = train_mod.main([
                "--arch", TRAIN_ARCH, "--steps", "4", "--batch", "8",
                "--seq", "512", "--devices", str(n), "--ckpt-dir", ckpt,
                "--ckpt-every", "1000", "--seed", str(SEED),
            ])
        check(res["devices"] == n, f"trained on {res['devices']} devices, wanted {n}")
        check(not any(e["kind"] == "restart" for e in res["events"]),
              f"training restarted: {res['events']}")
        runs[n] = np.asarray(res["losses"])
        log(f"[train] {res['arch']} on {n} device(s): losses {runs[n].tolist()}")
    many, one = runs[n_devices], runs[1]
    rel = np.abs(many - one) / np.abs(one)
    log(f"[train] {n_devices}-way data parallel vs 1 device: max relative loss "
        f"difference {float(rel.max())} (tol {LOSS_RTOL})")
    check(bool(np.all(np.isfinite(many))) and float(rel.max()) <= LOSS_RTOL,
          "data-parallel losses disagree with one device")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devices = require_tpu()
    if len(devices) < args.chips:
        raise SystemExit(f"--chips {args.chips} needs {args.chips} devices, "
                         f"JAX sees {len(devices)}")
    log(f"cache dir {enable_compile_cache()}")
    dev = devices[0]
    log(f"device {dev.platform} {dev.device_kind} x{len(devices)}")
    if args.chips == 4:
        train_phase(4)
    else:
        serve_phase(dev)
        kernel_phase(dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}}))


if __name__ == "__main__":
    main()
