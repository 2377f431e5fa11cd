"""Serving window for the latent-attention MoE family (``mla_moe``).

The window is ``serve``'s (see its docstring): set-up draws the weights,
builds the engine and runs one step that obtains every program; closed-loop
clients then step the engine for ``seconds``; after it the peak memory is
read, the arena checked, the engine freed and a seeded sample of finished
requests compared with the configuration's plain reference. What differs
is what is served and accounted: a DeepSeek-V2 program config (built
before any weight is drawn, so a program without the family fails at
once), one latent stream per layer in the KV arena (``kv_lora_rank +
qk_rope_head_dim`` elements a token), and the allocator's ``state_counts``
and ``stitched_mallocs`` read before and after the window.
"""

from __future__ import annotations

import contextlib
import gc
import math
import shutil
import tempfile
import time
from typing import List

import jax
import numpy as np

from .. import cost, cost_mla, spans as sp, stats
from ..traffic import ClosedLoop
from .serve import (
    CHUNK_BYTES, LANE, SUBLANES_BF16, Window, expected_chunks, max_gap, out_token_ctx,
    reduce_trace, reference_module, sample_requests,
)


def program_config(cfg: dict):
    """The program's model config, from a configuration file."""
    import jax.numpy as jnp
    from repro.models.deepseek_v2 import DeepseekV2Config

    if cfg["family"] != "mla_moe":
        raise ValueError(f"serve_mla driver runs mla_moe models, not {cfg['family']!r}")
    if cfg["rms_norm_eps"] != 1e-6:  # the program's RMSNorm has it fixed
        raise ValueError("the program's RMSNorm uses eps 1e-6")
    fixed = {"hidden_act": "silu", "q_lora_rank": None, "scoring_func": "softmax",
             "topk_method": "greedy", "moe_layer_freq": 1, "n_group": 1, "topk_group": 1}
    for key, want in fixed.items():
        if cfg[key] != want:
            raise ValueError(f"the program has {key} {want!r} only, not {cfg[key]!r}")
    rs = cfg["rope_scaling"]
    if rs["type"] != "yarn":
        raise ValueError("the program's rope scaling is YaRN's")
    return DeepseekV2Config(
        name=cfg["name"], n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], vocab=cfg["vocab_size"],
        d_ff=cfg["intermediate_size"], moe_d_ff=cfg["moe_intermediate_size"],
        n_experts_routed=cfg["n_routed_experts_published"],
        experts_held=cfg["n_routed_experts"], top_k=cfg["num_experts_per_tok"],
        expert_offset=cfg["expert_offset"], n_shared_experts=cfg["n_shared_experts"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        kv_lora_rank=cfg["kv_lora_rank"], qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        rope_theta=float(cfg["rope_theta"]), rope_factor=float(rs["factor"]),
        rope_original_max=rs["original_max_position_embeddings"],
        beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
        mscale=float(rs["mscale"]), mscale_all_dim=float(rs["mscale_all_dim"]),
        tie_embeddings=cfg["tie_word_embeddings"],
        dtype=jnp.bfloat16 if cfg["torch_dtype"] == "bfloat16" else jnp.float32,
    )


def chunk_tokens(cfg: dict) -> int:
    """Tokens of one layer's latent rows in a 2 MiB chunk: rows packed
    lane-dense, ``128 / gcd(row, 128)`` to a lane row, whole 16-row tiles."""
    row = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    per_lane_row = LANE // math.gcd(row, LANE)
    rows = CHUNK_BYTES // (per_lane_row * row * cost.itemsize(cfg))
    return rows // SUBLANES_BF16 * SUBLANES_BF16 * per_lane_row


def alloc_counts(alloc) -> dict:
    """The allocator's malloc tallies (None where the backend keeps none)."""
    counts = getattr(alloc, "state_counts", None)
    return {"state_counts": dict(counts) if counts is not None else None,
            "stitched_mallocs": getattr(alloc, "stitched_mallocs", None)}


def run(ctx) -> dict:
    """One run of an ``mla_moe`` serving cell; returns the harness's parts."""
    from repro.serve.engine import EngineConfig, ServeEngine

    cfg, mix, seed = ctx.config, ctx.traffic, ctx.seed
    eng_cfg = cfg["serve"]
    model_cfg = program_config(cfg)
    ref = reference_module(cfg)
    params = jax.block_until_ready(ref.make_weights(cfg, seed))
    eng = ServeEngine(model_cfg, params, EngineConfig(
        max_batch=eng_cfg["max_batch"], max_len=eng_cfg["max_len"],
        n_chunks=eng_cfg["n_chunks"], allocator=eng_cfg["allocator"],
        interpret=ctx.interpret,
    ))
    loop = ClosedLoop(mix, cfg["vocab_size"], seed)
    if loop.clients > eng_cfg["max_batch"]:
        raise ValueError("more clients than batch slots")
    win = Window()
    for c in range(loop.clients):
        win.submit(eng, *loop.first(c))
    eng.step()  # every prompt bucket, every slot and one decode
    win.observe(eng, time.perf_counter())

    spans = sp.Spans()
    counter = sp.CompileCounter.get()
    trace_dir = None
    if ctx.trace:
        sp.wrap_engine(eng, spans)
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    # clients whose first request ended in set-up submit now
    for _ in range(loop.clients - len(win.seen)):
        win.submit(eng, *loop.next())

    live_share: List[float] = []
    decode_lengths: List[List[int]] = []
    token_bytes = cost_mla.latent_token_bytes(cfg)
    alloc = eng.kv.arena.allocator
    before = alloc_counts(alloc)
    counter.count, counter.on = 0, True
    t0 = time.perf_counter()
    submitted_before = set(win.reqs)
    steps = 0
    with spans.span(sp.WINDOW) if ctx.trace else contextlib.nullcontext():
        while True:
            eng.step()
            steps += 1
            t = time.perf_counter()
            grew, done = win.observe(eng, t)
            if ctx.trace:
                decode_lengths.append([
                    len(eng._requests[r].prompt) + len(eng._requests[r].generated) - 1
                    for r in grew
                ])
                live = sum(s.length for s in eng.kv.seqs.values()) * token_bytes
                live_share.append(live / max(alloc.reserved_bytes, 1))
            if t - t0 >= ctx.seconds:
                break
            for _ in done:
                win.submit(eng, *loop.next())
        t1 = time.perf_counter()
    counter.on = False
    after = alloc_counts(alloc)
    if ctx.trace:
        jax.profiler.stop_trace()
    memory_peak = ctx.memory_peak()

    e2e = stats.serve_end_to_end(list(win.reqs.values()), t0, t1)
    attempted = len(set(win.reqs) - submitted_before)
    checks = {"arena_mismatch_bytes": arena_mismatch(eng, cfg)}

    finished = [eng._requests[rid] for rid in sorted(eng._requests)
                if eng._requests[rid].done]
    bad = [r for r in finished if len(r.generated) != r.max_new
           or not all(0 <= tok < cfg["vocab_size"] for tok in r.generated)]
    checks["bad_requests"] = len(bad)
    served = [(np.asarray(r.prompt), list(r.generated)) for r in finished]
    del eng, finished, alloc  # the cache and the arena leave the device
    gc.collect()

    t_check = time.perf_counter()
    sample = sample_requests(served, ctx.checks["sample"], seed)
    checks["max_logit_gap"] = max_gap(ref, cfg, params, sample)
    checks["sampled_tokens"] = sum(len(g) for _, g in sample)

    record = {
        "kind": "serve", "cfg": cfg, "peaks": ctx.peaks, "steps": steps,
        "window_s": t1 - t0, "compiles": counter.count,
        "spans": [s for s in spans.records if s[0] != sp.WINDOW],
        "kv_live_share": live_share, "decode_lengths": decode_lengths,
        "out_token_ctx": out_token_ctx(win, t0, t1),
        "alloc_window": {"before": before, "after": after},
        "memory_peak_bytes": memory_peak, "trace": None,
    }
    if trace_dir:
        record["trace"] = reduce_trace(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    return {
        "end_to_end": e2e, "record": record, "checks": checks,
        "attempted": attempted, "failed": len(bad),
        "memory_peak_bytes": memory_peak, "t0": t0,
        "timing": {"window_s": t1 - t0, "check_s": time.perf_counter() - t_check},
    }


def arena_mismatch(eng, cfg) -> int:
    """Bytes by which the arena's accounting departs from the live
    sequences (0 when it agrees), as ``serve.arena_mismatch`` reckons them
    with one latent allocation per layer in place of K and V."""
    kv = eng.kv
    ct = chunk_tokens(cfg)
    per_chunk = cfg["num_hidden_layers"] * CHUNK_BYTES  # one stream, every layer
    expected = requested = held = wrong = 0
    live = set(eng.running)
    for rid in live | set(kv.seqs):
        r = eng._requests[rid]
        length = len(r.prompt) + len(r.generated) - 1
        want = expected_chunks(len(r.prompt), length, ct, kv.config.growth) * per_chunk
        expected += want
        state = kv.seqs.get(rid)
        if rid not in live or state is None or state.length != length:
            wrong += want
        if state is not None:
            allocs = [a for group in state.allocs.values() for a in group]
            requested += sum(a.req_size for a in allocs)
            held += sum(a.block_size for a in allocs)
    active = kv.arena.allocator.stats.active_bytes
    return abs(requested - expected) + abs(active - held) + wrong
