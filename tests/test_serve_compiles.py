"""The registry's serving entry points are compiled once per input shape.

``FAMILIES`` jits each family's ``prefill`` and ``decode_step`` with the
config static, so an engine that calls them step after step obtains no new
executable once every shape it uses has been seen; the jitted entry points
compute what the plain module functions compute; and a wrapper put over
``eng.fam`` after the engine is built still sees every call.
"""

import dataclasses
from contextlib import contextmanager

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import rwkv6, transformer, whisper
from repro.models.api import FAMILIES, family_of
from repro.serve.engine import EngineConfig, ServeEngine

KEY = jax.random.PRNGKey(0)
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

#: one reduced architecture per family
FAMILY_ARCHS = {
    "dense": "smollm-135m", "moe": "dbrx-132b", "hybrid": "zamba2-1.2b",
    "ssm": "rwkv6-7b", "audio": "whisper-medium", "vlm": "paligemma-3b",
}


@contextmanager
def compiles():
    """Yields a list that collects the ``fun_name`` of every executable JAX
    obtains inside the block."""
    names = []

    def listen(event, duration, fun_name="", **_):
        if event == BACKEND_COMPILE_EVENT:
            names.append(fun_name)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield names
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_arch("smollm-135m").smoke
    return cfg, family_of(cfg).init_params(cfg, KEY)


def make_engine(tiny_model, max_batch):
    cfg, params = tiny_model
    return ServeEngine(cfg, params, EngineConfig(max_batch=max_batch, max_len=64,
                                                 n_chunks=64))


def test_engine_steps_after_warmup_obtain_no_executable(tiny_model):
    cfg, _ = tiny_model
    lengths = (5, 9, 12, 16)
    rng = np.random.default_rng(0)
    eng = make_engine(tiny_model, max_batch=len(lengths))
    # one warm-up step prefills every prompt length, fills every slot and
    # decodes once
    for n in lengths:
        eng.submit(rng.integers(0, cfg.vocab, size=n), max_new=3)
    eng.step()
    assert len(eng.running) == len(lengths)
    for i in range(80):
        eng.submit(rng.integers(0, cfg.vocab, size=lengths[i % len(lengths)]),
                   max_new=int(rng.integers(2, 5)))
    with compiles() as names:
        for _ in range(20):
            eng.step()
    assert len(eng.finished) > len(lengths)  # admissions and retirements
    assert eng.waiting  # still admitting at the end
    assert names == []


def _family_inputs(cfg):
    b, s = 2, 8
    tokens = jax.random.randint(KEY, (b, s), 0, cfg.vocab)
    fam = family_of(cfg)
    if isinstance(cfg, whisper.WhisperConfig):
        frames = jax.random.normal(KEY, (b, s, cfg.d_model))
        return {"frames": frames, "tokens": tokens}, fam.init_cache(cfg, b, 2 * s, s)
    if isinstance(cfg, rwkv6.RWKV6Config):
        return {"tokens": tokens}, fam.init_cache(cfg, b)
    batch = {"tokens": tokens}
    if fam.name == "vlm":
        batch["patch_embeds"] = jax.random.normal(KEY, (b, cfg.n_patches, cfg.d_model))
        return batch, fam.init_cache(cfg, b, cfg.n_patches + 2 * s)
    return batch, fam.init_cache(cfg, b, 2 * s)


@pytest.mark.parametrize("family", sorted(FAMILY_ARCHS))
def test_family_entry_points_compile_once_per_shape(family):
    # a config no other test uses, so the first call must compile
    cfg = get_arch(FAMILY_ARCHS[family]).smoke
    cfg = dataclasses.replace(cfg, name=f"{cfg.name}-compile-once")
    fam = family_of(cfg)
    assert fam is FAMILIES[family]
    params = fam.init_params(cfg, KEY)
    batch, cache = _family_inputs(cfg)
    tokens = batch["tokens"][:, 0]

    counts = []
    for _ in range(2):
        with compiles() as names:
            logits, out_cache = fam.prefill(cfg, params, batch, cache)
            step_logits, _ = fam.decode_step(cfg, params, out_cache, tokens)
            jax.block_until_ready((logits, step_logits))
        counts.append((names.count("jit(prefill)"), names.count("jit(decode_step)")))
    assert counts == [(1, 1), (0, 0)]
    assert np.all(np.isfinite(np.asarray(step_logits, np.float32)))


def test_jitted_dense_entry_points_match_the_module_functions():
    cfg = transformer.TransformerConfig(name="t", n_layers=3, d_model=64, n_heads=4,
                                        n_kv=2, d_ff=128, vocab=257,
                                        dtype=jnp.float32, remat=False)
    fam = family_of(cfg)
    params = transformer.init_params(cfg, KEY)
    toks = jax.random.randint(KEY, (2, 32), 0, 257)
    batch = {"tokens": toks}
    cache = transformer.init_cache(cfg, 2, 64)

    def close(a, b):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4)

    lj, cj = fam.prefill(cfg, params, batch, cache)
    lp, cp = transformer.prefill(cfg, params, batch, cache)
    close(lj, lp)
    jax.tree.map(close, cj, cp)
    nxt = jnp.argmax(lp[:, -1], -1)
    for _ in range(4):
        lj, cj = fam.decode_step(cfg, params, cj, nxt)
        lp, cp = transformer.decode_step(cfg, params, cp, nxt)
        close(lj, lp)
        jax.tree.map(close, cj, cp)
        nxt = jnp.argmax(lp, -1)
    np.testing.assert_array_equal(np.asarray(cj["length"]), [36, 36])


def test_wrapper_over_eng_fam_sees_every_call(tiny_model):
    cfg, _ = tiny_model
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab, size=n) for n in (5, 9, 7, 12, 5)]

    def run(wrap):
        eng = make_engine(tiny_model, max_batch=2)
        calls = {"prefill": 0, "decode_step": 0}
        if wrap:
            def counted(name, fn):
                def call(*args, **kwargs):
                    calls[name] += 1
                    return jax.block_until_ready(fn(*args, **kwargs))
                return call

            eng.fam = dataclasses.replace(
                eng.fam, prefill=counted("prefill", eng.fam.prefill),
                decode_step=counted("decode_step", eng.fam.decode_step),
            )
        for p in prompts:
            eng.submit(p, max_new=3)
        decodes = 0
        while eng.waiting or eng.running:
            eng._admit()
            decodes += bool(eng.running)
            eng.step()
        return eng, calls, decodes

    eng, calls, decodes = run(wrap=True)
    assert calls == {"prefill": len(prompts), "decode_step": decodes}
    plain, _, _ = run(wrap=False)
    assert ([r.generated for r in eng.finished]
            == [r.generated for r in plain.finished])
