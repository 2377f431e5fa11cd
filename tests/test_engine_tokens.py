"""Each request served by ``ServeEngine`` gets the greedy token of its own
row: the first from the last position of its prefill logits, every later
one from its slot's row of the decode logits, while slots are freed and
re-used mid-run."""

import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models.api import family_of
from repro.serve.engine import EngineConfig, ServeEngine

LENGTHS = (5, 11, 7, 16, 4, 9, 13)
MAX_NEW = (6, 2, 4, 3, 5, 2, 6)


@pytest.mark.parametrize("arch", ["smollm-135m", "deepseek-v2-lite"])
def test_every_token_is_the_argmax_of_its_own_row(arch):
    cfg = get_arch(arch).smoke
    params = family_of(cfg).init_params(cfg, jax.random.PRNGKey(3))
    eng = ServeEngine(cfg, params, EngineConfig(max_batch=3, max_len=48, n_chunks=64))
    prefills, decodes = [], []  # (prompt, logits); (slot by request, logits)
    eng_prefill, eng_decode = eng.fam.prefill, eng.fam.decode_step

    def prefill(*args, **kwargs):
        logits, cache = eng_prefill(*args, **kwargs)
        prefills.append((np.asarray(args[2]["tokens"])[0], np.asarray(logits)))
        return logits, cache

    def decode_step(*args, **kwargs):
        logits, cache = eng_decode(*args, **kwargs)
        decodes.append((dict(eng._slot_of), np.asarray(logits)))
        return logits, cache

    eng.fam = dataclasses.replace(eng.fam, prefill=prefill, decode_step=decode_step)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab, size=n) for n in LENGTHS]
    rids = [eng.submit(p, max_new=m) for p, m in zip(prompts, MAX_NEW)]
    eng.run_to_completion()

    assert len(eng.finished) == len(rids)
    # admission is FIFO here, so the i-th prefill is request i's
    assert len(prefills) == len(rids)
    for rid, prompt, (seen, logits) in zip(rids, prompts, prefills):
        req = eng._requests[rid]
        np.testing.assert_array_equal(seen, prompt)
        assert len(req.generated) == req.max_new
        assert req.generated[0] == int(np.argmax(logits[0, -1]))
        rows = [(slots[rid], out) for slots, out in decodes if rid in slots]
        assert len(rows) == req.max_new - 1
        assert req.generated[1:] == [int(np.argmax(out[s])) for s, out in rows]
    # a freed slot was re-used out of order, so a batch held its requests
    # out of slot order and a row picked by position would have been caught
    assert any(list(slots.values()) != sorted(slots.values()) for slots, _ in decodes)
