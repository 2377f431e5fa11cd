"""The latent-attention MoE serving driver (``serve_mla``) end to end at a
tiny size on the CPU, through the harness's internal entry
(``bench.run.run_cell``): a sound run is correct, reads the allocator's
window counts, and a token altered under the timed path is not correct."""

import dataclasses
import sys
from pathlib import Path

import jax.numpy as jnp

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run as harness  # noqa: E402
from bench.peaks import PEAKS  # noqa: E402

CELL = "dsv2lite.longdoc"
TINY = dict(num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
            vocab_size=512, intermediate_size=96, moe_intermediate_size=32,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16)
MIX = {"driver": "serve_mla", "clients": 4, "pool": 8,
       "prompt_len": {"dist": "choice", "values": [6, 12]},
       "output_len": {"dist": "uniform", "low": 6, "high": 10}}


def tiny_context(seed=2**33 + 20260117, trace=False):
    man = harness.manifest()
    ctx = harness.context_for(CELL, seed, 4.0, trace, PEAKS["TPU v5 lite"],
                              lambda: None, man)
    serve = dict(ctx.config["serve"], max_batch=4, max_len=64, n_chunks=16)
    rope = dict(ctx.config["rope_scaling"], original_max_position_embeddings=64)
    ctx.config = dict(ctx.config, **TINY, rope_scaling=rope, serve=serve)
    ctx.traffic = MIX
    ctx.interpret = True
    return ctx, man


def test_sound_run_is_correct():
    ctx, man = tiny_context()
    assert ctx.config["n_routed_experts"] < ctx.config["n_routed_experts_published"]
    res = harness.run_cell(ctx, man)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"out_tokens_per_s", "setup_s"}
    assert res["metrics"]["out_tokens_per_s"]["value"] > 0


def test_traced_run_reads_the_cells_host_metrics():
    ctx, man = tiny_context(trace=True)
    res = harness.run_cell(ctx, man)
    assert res["correct"], res["checks"]
    m = res["metrics"]
    # the CPU trace has no TPU plane: device metrics are left out, not 0
    assert "mla_decode_step_roofline" not in m and "idle_share.serve" not in m
    assert m["compiles_in_window.serve"]["value"] == 0
    assert m["serve_mfu.mla"]["value"] > 0
    assert 0 < m["kv_live_share"]["value"] < 100
    # every request fits one chunk, freed and taken again: no sBlock
    assert m["stitched_malloc_share"]["value"] == 0


def test_token_altered_under_the_timed_path_is_not_correct(monkeypatch):
    import repro.serve.engine as engine_mod

    real = engine_mod.family_of

    def family_of(cfg):
        fam = real(cfg)
        decode = fam.decode_step

        def step(cfg, params, cache, tokens, **kw):
            logits, cache = decode(cfg, params, cache, tokens, **kw)
            return jnp.roll(logits, 1, axis=-1), cache

        return dataclasses.replace(fam, decode_step=step)

    monkeypatch.setattr(engine_mod, "family_of", family_of)
    ctx, man = tiny_context()
    res = harness.run_cell(ctx, man)
    assert res["correct"] is False, res["checks"]
