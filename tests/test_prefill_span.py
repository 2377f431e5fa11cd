"""The engine's ``serve.prefill`` span carries the prompt's token count
(``tokens``) beside the request id, when the program's tracer is on."""

import jax
import numpy as np

from repro import obs
from repro.configs import get_arch
from repro.models.api import family_of
from repro.serve.engine import EngineConfig, ServeEngine


def test_prefill_span_carries_the_prompt_tokens():
    cfg = get_arch("deepseek-v2-lite").smoke
    params = family_of(cfg).init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, EngineConfig(max_batch=2, max_len=32, n_chunks=16))
    rng = np.random.default_rng(0)
    lengths = (5, 9, 6)
    for n in lengths:
        eng.submit(rng.integers(0, cfg.vocab, size=n), max_new=2)
    obs.enable()
    try:
        eng.run_to_completion()
    finally:
        out = obs.drain()
    prefills = [s for s in out["spans"] if s[0] == "serve.prefill"]
    assert [s[4] for s in prefills] == [{"req": i, "tokens": n}
                                        for i, n in enumerate(lengths)]
