"""The program's own spans (``repro.obs``) as the benchmark's trace tools
see them: the benchmark's runs leave the tracer off, the spans reach the
profiler's trace under their bare names, and the trace reduction labels
device idle time by the innermost program span."""

import sys
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src"), str(Path(__file__).parent)]

from bench import run as harness  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402
from test_bench_serve_cpu import tiny_context  # noqa: E402

from repro import obs  # noqa: E402

PROGRAM_SPANS = ("serve.step", "serve.admit", "serve.prefill", "serve.merge",
                 "serve.decode", "serve.sample", "kv.grow", "kv.free")


@pytest.mark.parametrize("trace", [False, True], ids=["plain", "traced"])
def test_benchmark_runs_leave_the_program_tracer_off(trace):
    # the benchmark measures the program with its tracer off, traced or not
    ctx, man = tiny_context(trace=trace)
    ctx.seconds = 2.0
    res = harness.run_cell(ctx, man)
    assert res["correct"], res["checks"]
    assert res["metrics"]["out_tokens_per_s" if not trace
                          else "engine_self_ms_per_step"]["value"] > 0
    assert not obs.ON
    assert obs._spans == [] and obs._counters == {} and obs._compiles == []


def test_program_spans_reach_the_trace_under_their_names(tmp_path):
    from repro.configs import get_arch
    from repro.models.api import family_of
    from repro.serve.engine import EngineConfig, ServeEngine

    cfg = get_arch("smollm-135m").smoke
    params = family_of(cfg).init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, EngineConfig(max_batch=2, max_len=64, n_chunks=64))
    rng = np.random.default_rng(0)
    for n in (5, 9, 6):
        eng.submit(rng.integers(0, cfg.vocab, size=n), max_new=3)
    jax.profiler.start_trace(str(tmp_path))
    obs.enable()
    try:
        eng.run_to_completion()
    finally:
        out = obs.drain()
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    host = tr.load_xplane(str(path), PROGRAM_SPANS).host_spans
    in_trace = sorted(n for n, _, _ in host if n != "compile")
    assert in_trace == sorted(s[0] for s in out["spans"])
    assert set(in_trace) == set(PROGRAM_SPANS)


def test_idle_time_goes_to_the_innermost_program_span():
    # window 0..100 ns on one device, busy 10-20 (decode), 50-55 (prefill)
    # and 80-85 (the step itself: the merge has ended); idle 0-10 (compile
    # inside admit), 20-50 (sample), 55-80 (merge), 85-100 (the step)
    ops = {"/device:TPU:0": [("fusion.1", 10, 20), ("dot.2", 50, 55),
                             ("copy.3", 80, 85)]}
    spans = [("bench.window", 0, 100), ("serve.step", 0, 100),
             ("serve.admit", 0, 10), ("compile", 1, 9), ("serve.decode", 8, 20),
             ("serve.sample", 20, 50), ("serve.admit", 50, 80),
             ("serve.prefill", 50, 55), ("serve.merge", 55, 80)]
    red = tr.reduce_events(ops, spans, (0, 100))
    assert red.idle_by_label() == [
        ("serve.sample", pytest.approx(30e-9)), ("serve.merge", pytest.approx(25e-9)),
        ("serve.step", pytest.approx(15e-9)), ("compile", pytest.approx(10e-9))]
    assert red.span_device_s == {"serve.decode": pytest.approx(10e-9),
                                 "serve.prefill": pytest.approx(5e-9),
                                 "serve.step": pytest.approx(5e-9)}
