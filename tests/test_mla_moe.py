"""The DeepSeek-V2 family (``mla_moe``: latent attention, shared experts,
a held share of the routed ones) against its plain reference, on the CPU at
``SMOKE`` size with float32 weights drawn by the reference
(``bench/references/deepseek_v2.py``)."""

import dataclasses
import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT)]

from bench.drivers.serve_mla import program_config  # noqa: E402
from bench.references import deepseek_v2 as ref  # noqa: E402

from repro.configs import deepseek_v2_lite  # noqa: E402
from repro.models import deepseek_v2, moe  # noqa: E402
from repro.models.api import FAMILIES, family_of  # noqa: E402
from repro.models.layers import mlp_apply  # noqa: E402
from repro.serve.engine import EngineConfig, ServeEngine  # noqa: E402

SMOKE = deepseek_v2_lite.SMOKE
KEY = jax.random.PRNGKey(0)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def hf_config(cfg) -> dict:
    """A configuration file's dict (Hugging Face keys) for a program config."""
    return {
        "name": cfg.name, "family": "mla_moe", "reference": "deepseek_v2",
        "num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.n_heads, "vocab_size": cfg.vocab,
        "intermediate_size": cfg.d_ff, "moe_intermediate_size": cfg.moe_d_ff,
        "n_routed_experts": cfg.experts_held,
        "n_routed_experts_published": cfg.n_experts_routed,
        "expert_offset": cfg.expert_offset,
        "num_experts_per_tok": cfg.top_k, "n_shared_experts": cfg.n_shared_experts,
        "first_k_dense_replace": cfg.first_k_dense_replace,
        "kv_lora_rank": cfg.kv_lora_rank, "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim, "v_head_dim": cfg.v_head_dim,
        "norm_topk_prob": cfg.norm_topk_prob,
        "routed_scaling_factor": cfg.routed_scaling_factor, "rope_theta": cfg.rope_theta,
        "rope_scaling": {"type": "yarn", "factor": cfg.rope_factor,
                         "original_max_position_embeddings": cfg.rope_original_max,
                         "beta_fast": cfg.beta_fast, "beta_slow": cfg.beta_slow,
                         "mscale": cfg.mscale, "mscale_all_dim": cfg.mscale_all_dim},
        "rms_norm_eps": 1e-6, "tie_word_embeddings": cfg.tie_embeddings,
        "hidden_act": "silu", "q_lora_rank": None, "scoring_func": "softmax",
        "topk_method": "greedy", "moe_layer_freq": 1, "n_group": 1, "topk_group": 1,
        "torch_dtype": "float32",
    }


@pytest.fixture(scope="module")
def weights():
    hf = hf_config(SMOKE)
    return hf, ref.make_weights(hf, 7)


def test_config_file_keys_give_the_program_config():
    cfg = program_config(hf_config(SMOKE))
    assert cfg == dataclasses.replace(SMOKE, remat=True)
    assert family_of(cfg) is FAMILIES["mla_moe"]


def test_yarn_frequencies_and_softmax_scale_follow_the_published_formula():
    cfg = deepseek_v2_lite.FULL
    inv = deepseek_v2.yarn_inv_freq(cfg)
    # dim 64, base 1e4, 4096 original positions: the correction range is
    # floor(64 ln(4096 / (32 * 2 pi)) / (2 ln 1e4)) = 10 to
    # ceil(64 ln(4096 / (1 * 2 pi)) / (2 ln 1e4)) = 23
    base = lambda i: 1e4 ** (-2 * i / 64)  # noqa: E731
    ramp = (16 - 10) / (23 - 10)
    expect = {0: 1.0, 5: base(5), 10: base(10),
              16: base(16) / 40 * ramp + base(16) * (1 - ramp),
              23: base(23) / 40, 31: base(31) / 40}
    for i, want in expect.items():
        assert inv[i] == pytest.approx(want, rel=1e-6), i
    np.testing.assert_allclose(inv, ref.yarn_inv_freq(hf_config(cfg)), rtol=1e-6)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * mscale ** 2)


def test_absorbed_decode_attention_equals_the_decompressed_one(weights):
    _, params = weights
    cfg = SMOKE
    p = jax.tree.map(lambda w: w[0], params["moe"]["attn"])
    s = 12
    x = jax.random.normal(KEY, (2, s, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(s), (2, s))
    full, (c, k_pe) = deepseek_v2._attn_prefill(cfg, p, x, pos)
    # the cache holds rows 0..s-2 of layer 1; decode writes row s-1 and
    # attends over all s
    ckv = jnp.zeros((cfg.n_layers, 2, 16, cfg.kv_lora_rank)).at[1, :, :s - 1].set(c[:, :-1])
    kpe = jnp.zeros((cfg.n_layers, 2, 16, cfg.qk_rope_head_dim)).at[1, :, :s - 1].set(
        k_pe[:, :-1])
    lengths = jnp.full((2,), s - 1, jnp.int32)
    out, ckv2, kpe2 = deepseek_v2._attn_decode(cfg, p, x[:, -1:], ckv, kpe, 1, lengths)
    # float32 on both paths; they differ by the order of the sums only
    np.testing.assert_allclose(out[:, 0], full[:, -1], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(ckv2[1, :, :s], c, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(kpe2[1, :, :s], k_pe, rtol=1e-5, atol=1e-6)


def _dense_routed(p, x, weights, experts, offset):
    """Per token, the weighted sum over its chosen experts held in ``p``."""
    out = np.zeros(x.shape, np.float64)
    held = p["wi"].shape[0]
    for t in range(x.shape[0]):
        for w, e in zip(np.asarray(weights[t]), np.asarray(experts[t])):
            if offset <= e < offset + held:
                j = e - offset
                h = jax.nn.silu(x[t] @ p["wg"][j]) * (x[t] @ p["wi"][j])
                out[t] += w * np.asarray(h @ p["wo"][j])
    return out


def test_expert_shares_add_up_to_the_uncut_layer():
    cfg = dataclasses.replace(SMOKE, experts_held=SMOKE.n_experts_routed)
    params = ref.make_weights(hf_config(cfg), 3)
    lp = jax.tree.map(lambda w: w[0], params["moe"])
    x = jax.random.normal(KEY, (40, cfg.d_model))
    kw = dict(top_k=cfg.top_k, norm_topk_prob=False, scaling=1.0)
    uncut = moe.shared_moe(lp, x, offset=0, **kw)
    shared = mlp_apply(lp["shared"], x, "silu", gated=True)
    weights, experts = moe.route(x, lp["router"], cfg.top_k, False)
    parts = []
    for offset in range(0, 64, 8):  # the deployment's 8 chips, 8 experts each
        share = jax.tree.map(lambda w, o=offset: w[o:o + 8], lp["experts"])
        parts.append(moe.held_experts(share, x, weights, experts, offset))
        np.testing.assert_allclose(parts[-1], _dense_routed(share, x, weights, experts,
                                                            offset), rtol=1e-4, atol=1e-5)
    # float32; the shares add in another order than the uncut layer's sum
    np.testing.assert_allclose(sum(parts) + shared, uncut, rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(shared - uncut).max()) > 1e-2  # the routed part counts


def test_every_token_routed_to_one_expert_is_computed():
    cfg = SMOKE
    params = ref.make_weights(hf_config(cfg), 4)
    lp = jax.tree.map(lambda w: w[0], params["moe"])
    n_tok = 300  # many times any capacity a factor of the mean load would give
    x = jnp.abs(jax.random.normal(KEY, (n_tok, cfg.d_model)))
    router = jnp.zeros_like(lp["router"]).at[:, 3].set(10.0)  # expert 3 first for all
    weights, experts = moe.route(x, router, cfg.top_k, False)
    assert bool(jnp.all(experts[:, 0] == 3))
    out = moe.held_experts(lp["experts"], x, weights, experts, 0)
    want = _dense_routed(lp["experts"], x, weights, experts, 0)
    assert np.all(np.abs(want).sum(-1) > 0)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)


def test_route_weights_are_unnormalised_softmax_probabilities():
    x = jax.random.normal(KEY, (5, 16))
    router = jax.random.normal(jax.random.fold_in(KEY, 1), (16, 64))
    w, e = moe.route(x, router, 6, norm_topk_prob=False, scaling=2.0)
    probs = jax.nn.softmax(x @ router, -1)
    np.testing.assert_allclose(w, 2.0 * jnp.take_along_axis(probs, e, -1), rtol=1e-5)
    assert float(w.sum(-1).max()) < 2.0  # six of 64 probabilities, not renormalised
    wn, _ = moe.route(x, router, 6, norm_topk_prob=True)
    np.testing.assert_allclose(wn.sum(-1), 1.0, rtol=1e-5)


def test_engine_prefill_and_decode_match_the_reference(weights):
    hf, params = weights
    eng = ServeEngine(SMOKE, params, EngineConfig(max_batch=3, max_len=48, n_chunks=16))
    seen = {}  # request -> logits rows the engine sampled from

    prefill, decode = eng.fam.prefill, eng.fam.decode_step

    def traced_prefill(cfg, params, batch, cache, **kw):
        logits, cache = prefill(cfg, params, batch, cache, **kw)
        seen.setdefault(len(seen), []).append(np.asarray(logits[0, -1]))
        return logits, cache

    def traced_decode(cfg, params, cache, tokens, **kw):
        logits, cache = decode(cfg, params, cache, tokens, **kw)
        for rid, slot in eng._slot_of.items():
            seen[rid].append(np.asarray(logits[slot]))
        return logits, cache

    eng.fam = dataclasses.replace(eng.fam, prefill=traced_prefill, decode_step=traced_decode)
    rng = np.random.default_rng(0)
    for n, new in ((9, 6), (17, 4), (5, 8), (12, 5)):  # the fourth waits for a slot
        eng.submit(rng.integers(0, SMOKE.vocab, size=n), max_new=new)
    done = eng.run_to_completion()
    assert len(done) == 4
    for r in done:
        seq = np.concatenate([r.prompt, np.asarray(r.generated[:-1], np.int32)])
        want = np.asarray(ref.logits(hf, params, seq, pad_to=64))
        got = np.stack(seen[r.req_id][:len(r.generated)])
        # float32 program against the float32 reference: the flash blocks,
        # the absorbed decode and the expert dispatch sum in other orders
        np.testing.assert_allclose(got, want[len(r.prompt) - 1:len(seq)], rtol=1e-4,
                                   atol=2e-4)


def test_entry_points_compile_once_per_shape_and_carry_the_scopes(weights):
    _, params = weights
    cfg = dataclasses.replace(SMOKE, name="deepseek-v2-smoke-compile-once")
    fam = family_of(cfg)
    assert fam.name == "mla_moe" and fam is FAMILIES["mla_moe"]
    tokens = jax.random.randint(KEY, (2, 8), 0, cfg.vocab)
    cache = fam.init_cache(cfg, 2, 16)
    counts = []
    for _ in range(2):
        names = []

        def listen(event, duration, fun_name="", **_):
            if event == BACKEND_COMPILE_EVENT:
                names.append(fun_name)

        jax.monitoring.register_event_duration_secs_listener(listen)
        try:
            logits, out = fam.prefill(cfg, params, {"tokens": tokens}, cache)
            step, _ = fam.decode_step(cfg, params, out, tokens[:, 0])
            jax.block_until_ready((logits, step))
        finally:
            jax.monitoring.unregister_event_duration_listener(listen)
        counts.append((names.count("jit(prefill)"), names.count("jit(decode_step)")))
    assert counts == [(1, 1), (0, 0)]
    text = fam.decode_step.lower(cfg, params, out, tokens[:, 0]).as_text(debug_info=True)
    for scope in ("mla.attention", "moe.route", "moe.experts", "moe.shared"):
        assert scope in text, scope
