"""The program's tracer (``repro.obs``) and the spans and counters the
serving path writes with it: nothing while off, nesting and attributes
while on, compile events by function and span, the engine's phases and
host syncs, and GMLake's count of stitched mallocs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.alloc.chunks import CHUNK_SIZE, VMMDevice
from repro.alloc.gmlake import GMLakeAllocator, SBlock
from repro.configs import get_arch
from repro.models.api import family_of
from repro.serve.engine import EngineConfig, ServeEngine


@pytest.fixture
def tracer():
    """Leaves the tracer off and empty, whatever the test did."""
    yield obs
    if obs.ON:
        obs.drain()


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_arch("smollm-135m").smoke
    return cfg, family_of(cfg).init_params(cfg, jax.random.PRNGKey(0))


def make_engine(tiny_model, max_batch=2):
    cfg, params = tiny_model
    return ServeEngine(cfg, params, EngineConfig(max_batch=max_batch, max_len=64,
                                                 n_chunks=64))


def prompts(cfg, lengths):
    rng = np.random.default_rng(0)
    return [rng.integers(0, cfg.vocab, size=n) for n in lengths]


def test_off_records_nothing_and_calls_no_profiler(tracer, tiny_model, monkeypatch):
    tracer.enable()
    tracer.drain()  # the compile listener is registered, recording is off

    def boom(*args, **kwargs):
        raise AssertionError("tracer touched while off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    monkeypatch.setattr(obs, "span", boom)
    monkeypatch.setattr(obs, "count", boom)
    eng = make_engine(tiny_model)
    for p in prompts(tiny_model[0], [5, 7, 6]):
        eng.submit(p, max_new=3)
    eng.run_to_completion()
    jax.jit(lambda x: x * 7 - 2)(jnp.arange(3.0))  # a compile while off
    assert len(eng.finished) == 3
    assert not obs.ON
    assert obs._spans == [] and obs._counters == {} and obs._compiles == []


def test_spans_nest_with_parents_and_attributes(tracer, monkeypatch):
    names = []
    real = jax.profiler.TraceAnnotation

    def annotation(name, **kwargs):
        names.append((name, kwargs))
        return real(name)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", annotation)
    tracer.enable()
    with obs.span("outer", step=4):
        with obs.span("inner", req=7):
            obs.count("hits")
        obs.count("hits", 2)
    with obs.span("after"):
        pass
    out = tracer.drain()
    assert not obs.ON
    assert [(n, p, a) for n, _, _, p, a in out["spans"]] == [
        ("outer", None, {"step": 4}), ("inner", 0, {"req": 7}), ("after", None, {})]
    (_, o0, o1, _, _), (_, i0, i1, _, _), (_, a0, a1, _, _) = out["spans"]
    assert o0 <= i0 <= i1 <= o1 <= a0 <= a1
    assert out["counters"] == {"hits": 3}
    # the bare name: metadata would change the trace event's name
    assert names == [("outer", {}), ("inner", {}), ("after", {})]
    assert obs.drain() == {"spans": [], "counters": {}, "compiles": []}


def test_drain_inside_an_open_span_is_refused(tracer):
    tracer.enable()
    with pytest.raises(RuntimeError):
        with obs.span("open"):
            obs.drain()


def test_compile_events_name_the_function_and_span(tracer):
    def fresh_fn_for_obs(x):
        return jnp.sin(x) * 3 + 1

    tracer.enable()
    with obs.span("outer"):
        with obs.span("phase"):
            jax.jit(fresh_fn_for_obs)(jnp.arange(5.0))
    out = tracer.drain()
    mine = [c for c in out["compiles"] if "fresh_fn_for_obs" in c[3]]
    assert {c[0] for c in mine} == set(obs.COMPILE_EVENTS)
    (_, s0, s1, _, _), (_, p0, p1, _, _) = out["spans"]
    for _, t0, t1, _, span in mine:
        assert span == 1  # the innermost open span: "phase"
        assert p0 <= t0 <= t1 <= p1


def test_engine_phases_in_order_and_host_syncs(tracer, tiny_model):
    cfg = tiny_model[0]
    eng = make_engine(tiny_model, max_batch=2)
    for p in prompts(cfg, [5, 9, 6]):
        eng.submit(p, max_new=3)
    eng.step()  # compile outside the record
    tracer.enable()
    expected = 0
    while eng.waiting or eng.running:
        admits = min(len(eng.waiting), eng.ecfg.max_batch - len(eng.running))
        # one read for the step's admissions, one for its decode
        expected += bool(admits) + bool(len(eng.running) + admits)
        eng.step()
    out = tracer.drain()
    spans = out["spans"]
    assert out["counters"]["serve.host_syncs"] == expected

    def children(i):
        return [(j, s) for j, s in enumerate(spans) if s[3] == i]

    steps = [(i, s) for i, s in enumerate(spans) if s[0] == "serve.step"]
    assert [s[4]["step"] for _, s in steps] == list(range(1, 1 + len(steps)))
    assert all(s[3] is None for _, s in steps)
    frees = admitting = decoding = 0
    for i, _ in steps:
        kids = [s[0] for _, s in children(i)]
        admits = [j for j, s in children(i) if s[0] == "serve.admit"]
        assert kids[:len(admits)] == ["serve.admit"] * len(admits)
        rest = kids[len(admits):]
        if admits:  # the admitted batch's first tokens, read at once
            assert rest[0] == "serve.sample"
            rest = rest[1:]
            admitting += 1
        assert rest[:2] == ["serve.decode", "serve.sample"]
        decoding += 1
        assert set(rest[2:]) <= {"kv.free", "kv.grow"}
        frees += rest.count("kv.free")
        for j in admits:
            rid = spans[j][4]["req"]
            tokens = len(eng._requests[rid].prompt)
            assert [(s[0], s[4]) for _, s in children(j)] == [
                ("kv.grow", {"req": rid}), ("serve.prefill", {"req": rid, "tokens": tokens}),
                ("serve.merge", {"req": rid})]
    assert frees == 3
    assert admitting > 0
    # at most two blocking reads a step, however many tokens it serves
    assert out["counters"]["serve.host_syncs"] == admitting + decoding
    assert [s[0] for s in spans].count("serve.sample") == admitting + decoding


@pytest.mark.parametrize("frag_limit", [CHUNK_SIZE, GMLakeAllocator.TUNED_FRAG_LIMIT],
                         ids=["sources-over-limit", "retry-ignoring-limit"])
def test_stitched_malloc_counts_sblock_handouts(frag_limit):
    # two freed 2-chunk blocks fill the device; a 4-chunk request stitches
    # them (S3: directly, or through S4's retry when they are under the limit)
    alloc = GMLakeAllocator(VMMDevice(4 * CHUNK_SIZE), frag_limit=frag_limit)
    a, b = alloc.malloc(2 * CHUNK_SIZE), alloc.malloc(2 * CHUNK_SIZE)
    assert alloc.stitched_mallocs == 0
    alloc.free(a)
    alloc.free(b)
    big = alloc.malloc(4 * CHUNK_SIZE)
    assert isinstance(big.block, SBlock)
    assert alloc.state_counts["S3"] == 1
    assert alloc.stitched_mallocs == 1
    alloc.free(big)
    again = alloc.malloc(4 * CHUNK_SIZE)  # S1: the sBlock handed out again
    assert isinstance(again.block, SBlock)
    assert alloc.state_counts["S1"] == 1
    assert alloc.stitched_mallocs == 2
