"""Integration tests: a2a MoE dispatch, serving engine, trace properties,
HLO analyzer, end-to-end training."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import GB, PAPER_MODELS, run_workload, training_trace
from repro.core.trace import ALLOC, FREE, inference_trace
from repro.utils.hlo import HloModule, analyze

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


# ---------------------------------------------------------------------------
# a2a MoE dispatch == global dispatch (multi-device)
# ---------------------------------------------------------------------------


def test_moe_a2a_matches_global_dispatch():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_auto_mesh
        from repro.models import moe as M
        from repro.parallel.sharding import make_rules, make_sharder
        mesh = make_auto_mesh((2, 2, 2), ("pod", "data", "model"))
        mk = lambda a2a, gated: M.MoEConfig(
            name="t", n_layers=2, d_model=64, n_heads=4, n_kv=2, d_ff=96,
            vocab=211, n_experts=4, top_k=2, capacity_factor=8.0,
            dtype=jnp.float32, gated=gated, act="silu", remat=False,
            a2a_dispatch=a2a)
        key = jax.random.PRNGKey(1)
        toks = jax.random.randint(key, (4, 32), 0, 211)
        for gated in (True, False):
            params = M.init_params(mk(False, gated), key)
            l_ref = M.loss_fn(mk(False, gated), params, {"tokens": toks})
            with mesh:
                rules = make_rules(mesh, kind="train", seq_parallel=True)
                sharder = make_sharder(mesh, rules)
                l_a2a = jax.jit(lambda p, b: M.loss_fn(mk(True, gated), p, b,
                                                       sharder=sharder))(
                    params, {"tokens": toks})
            # aux-loss statistics are per-shard means under a2a: tiny delta
            np.testing.assert_allclose(float(l_ref), float(l_a2a), rtol=5e-4)
        print("OK")
    """)
    assert "OK" in out


def test_moe_virtual_experts_equivalence():
    """expert_shards=2 with re-laid-out weights == expert_shards=1."""
    from repro.models import moe as M

    mk = lambda es: M.MoEConfig(name="t", n_layers=2, d_model=64, n_heads=4,
                                n_kv=2, d_ff=96, vocab=211, n_experts=4,
                                top_k=2, capacity_factor=8.0, dtype=jnp.float32,
                                gated=True, act="silu", remat=False,
                                expert_shards=es)
    key = jax.random.PRNGKey(1)
    p1 = M.init_params(mk(1), key)
    p2 = jax.tree.map(lambda x: x, p1)
    for k in ("wi", "wg"):
        w = p1["layers"]["mlp"][k]
        l, e, d, f = w.shape
        p2["layers"]["mlp"][k] = (
            w.reshape(l, e, d, 2, f // 2).transpose(0, 1, 3, 2, 4)
            .reshape(l, e * 2, d, f // 2)
        )
    wo = p1["layers"]["mlp"]["wo"]
    l, e, f, d = wo.shape
    p2["layers"]["mlp"]["wo"] = wo.reshape(l, e, 2, f // 2, d).reshape(
        l, e * 2, f // 2, d)
    toks = jax.random.randint(key, (2, 32), 0, 211)
    l1 = M.loss_fn(mk(1), p1, {"tokens": toks})
    l2 = M.loss_fn(mk(2), p2, {"tokens": toks})
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)


# ---------------------------------------------------------------------------
# serving engine
# ---------------------------------------------------------------------------


def test_serve_engine_drains_and_reuses_arena():
    from repro.configs import get_arch
    from repro.models.api import family_of
    from repro.serve.engine import EngineConfig, ServeEngine

    cfg = get_arch("smollm-135m").smoke
    fam = family_of(cfg)
    params = fam.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(cfg, params, EngineConfig(max_batch=4, max_len=128,
                                                n_chunks=128))
    rng = np.random.default_rng(0)
    for _ in range(6):
        eng.submit(rng.integers(0, cfg.vocab, size=int(rng.integers(4, 24))),
                   max_new=5)
    steps = 0
    while eng.waiting or eng.running:
        eng.step()
        steps += 1
        assert steps < 200
    rep = eng.memory_report()
    assert rep["active_bytes"] == 0  # all sequences retired
    assert rep["utilization"] > 0.5
    assert rep["state_counts"]["S1"] > 0  # chunk reuse happened
    assert rep["n_trace_events"] > 0


# ---------------------------------------------------------------------------
# trace generators: structural properties
# ---------------------------------------------------------------------------


@given(st.sampled_from(["", "R", "LR", "RO", "LRO"]),
       st.sampled_from([1, 2, 4]), st.integers(0, 3))
@settings(max_examples=10, deadline=None)
def test_training_trace_is_leak_free(strat, world, seed):
    tr = training_trace(PAPER_MODELS["opt-1.3b"], strategies=strat,
                        world=world, batch=2, seq=256, iters=2, seed=seed)
    live = set()
    for ev in tr.events:
        if ev.op == ALLOC:
            assert ev.tid not in live and ev.size > 0
            live.add(ev.tid)
        elif ev.op == FREE:
            live.discard(ev.tid)
    # persistent state (params/opt) stays live; everything transient freed
    persistent = [e for e in tr.events
                  if e.op == ALLOC and e.tid in live]
    assert all(("param" in e.label) or ("opt" in e.label) or ("embed" in e.label)
               for e in persistent)


def test_inference_trace_retires_everything():
    tr = inference_trace(PAPER_MODELS["opt-13b"], n_requests=32)
    live = set()
    for ev in tr.events:
        if ev.op == ALLOC:
            live.add(ev.tid)
        elif ev.op == FREE:
            live.remove(ev.tid)
    assert not live


def test_gmlake_dominates_caching_across_matrix():
    """On every irregular workload, GMLake reserves no more than caching."""
    for strat in ("LR", "LRO"):
        tr = training_trace(PAPER_MODELS["vicuna-13b"], strategies=strat,
                            world=4, batch=8, seq=2048, iters=6)
        rc = run_workload(tr, "caching", capacity_bytes=80 * GB)
        rg = run_workload(tr, "gmlake", capacity_bytes=80 * GB)
        assert rg.stats.peak_reserved <= rc.stats.peak_reserved
        assert rg.utilization >= rc.utilization


# ---------------------------------------------------------------------------
# scan-aware HLO analyzer
# ---------------------------------------------------------------------------

SYNTH_HLO = """
HloModule test

%body.1 (arg: (s32[], f32[8,16])) -> (s32[], f32[8,16]) {
  %arg = (s32[], f32[8,16]) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = f32[8,16]{1,0} get-tuple-element(%arg), index=1
  %w = f32[16,16]{1,0} constant({...})
  %dot.1 = f32[8,16]{1,0} dot(%x, %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,16]{1,0} all-reduce(%dot.1), replica_groups={}
  %one = s32[] constant(1)
  %ip = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,16]) tuple(%ip, %ar)
}

%cond.1 (arg.2: (s32[], f32[8,16])) -> pred[] {
  %arg.2 = (s32[], f32[8,16]) parameter(0)
  %i.2 = s32[] get-tuple-element(%arg.2), index=0
  %n = s32[] constant(10)
  ROOT %lt = pred[] compare(%i.2, %n), direction=LT
}

ENTRY %main (p0: f32[8,16]) -> f32[8,16] {
  %p0 = f32[8,16]{1,0} parameter(0)
  %z = s32[] constant(0)
  %tup = (s32[], f32[8,16]) tuple(%z, %p0)
  %w2 = (s32[], f32[8,16]) while(%tup), condition=%cond.1, body=%body.1, backend_config={"known_trip_count":{"n":"10"}}
  ROOT %out = f32[8,16]{1,0} get-tuple-element(%w2), index=1
}
"""


def test_hlo_analyzer_multiplies_loop_bodies():
    stats = analyze(SYNTH_HLO)
    # dot: 2*8*16*16 = 4096 flops, x10 trips (+10 adds of 1 elem)
    assert stats.flops == pytest.approx(4096 * 10 + 10, rel=0.01)
    # all-reduce: 8*16*4 bytes = 512, x10
    assert stats.collective_bytes == 512 * 10
    assert stats.collectives["all-reduce"]["count"] == 10


def test_hlo_analyzer_on_real_module():
    """Scan flops must exceed XLA's body-counted-once estimate ~L-fold."""
    def f(x, ws):
        def body(h, w):
            return jnp.tanh(h @ w), None
        h, _ = jax.lax.scan(body, x, ws)
        return h

    x = jnp.ones((8, 32))
    ws = jnp.ones((12, 32, 32))
    compiled = jax.jit(f).lower(x, ws).compile()
    stats = analyze(compiled.as_text())
    per_layer = 2 * 8 * 32 * 32
    assert stats.flops >= 12 * per_layer  # all 12 trips counted
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    xla_flops = float(cost.get("flops", 0))
    assert stats.flops > 5 * xla_flops  # and XLA indeed undercounts


# ---------------------------------------------------------------------------
# end-to-end training through the supervisor
# ---------------------------------------------------------------------------


def test_end_to_end_training_loss_decreases(tmp_path):
    from repro.launch.train import main as train_main

    result = train_main([
        "--arch", "smollm-135m", "--smoke", "--steps", "40",
        "--batch", "4", "--seq", "64", "--ckpt-dir", str(tmp_path),
    ])
    assert result["steps"] == 40
    assert result["last_loss"] < result["first_loss"]


# ---------------------------------------------------------------------------
# launcher device setup
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(monkeypatch, tmp_path, from_env):
    """The compile cache goes where JAX_COMPILATION_CACHE_DIR says (and the
    helper sets nothing), else to the fixed .jax_cache in the checkout."""
    from repro.utils import device

    before = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        got = device.enable_compile_cache()
        if from_env:
            assert got == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            assert got == os.path.join(REPO, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
