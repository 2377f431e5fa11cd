"""Fault-tolerance layer: checkpoint atomicity, restart-on-failure,
straggler detection (injectable clock), resumable data pipeline."""

import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt.checkpoint import CheckpointManager
from repro.data.pipeline import DataConfig, SyntheticTokens
from repro.ft.supervisor import StragglerDetector, Supervisor, SupervisorConfig


def tiny_state():
    return {"w": jnp.arange(12.0).reshape(3, 4), "step": jnp.int32(7)}


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrip(tmp_path):
    mgr = CheckpointManager(tmp_path)
    state = tiny_state()
    mgr.save(3, state)
    assert mgr.latest_step() == 3
    back = mgr.restore(jax.tree.map(jnp.zeros_like, state))
    np.testing.assert_array_equal(np.asarray(back["w"]), np.asarray(state["w"]))
    assert int(back["step"]) == 7


def test_checkpoint_uncommitted_is_invisible(tmp_path):
    mgr = CheckpointManager(tmp_path)
    mgr.save(1, tiny_state())
    torn = mgr.step_dir(5)
    torn.mkdir()
    (torn / "meta.json").write_text("{}")  # no COMMIT marker
    assert mgr.latest_step() == 1


def test_checkpoint_async_and_retention(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=2)
    for s in (1, 2, 3, 4):
        mgr.save_async(s, tiny_state())
    mgr.wait()
    steps = sorted(int(p.name.split("_")[1]) for p in tmp_path.glob("step_*"))
    assert steps == [3, 4]


def test_checkpoint_elastic_restore_new_sharding(tmp_path):
    """Restore re-shards to the current mesh (sharding != save-time)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_auto_mesh

    mgr = CheckpointManager(tmp_path)
    state = {"w": jnp.arange(16.0).reshape(4, 4)}
    mgr.save(1, state)
    mesh = make_auto_mesh((1,), ("data",))
    sh = {"w": NamedSharding(mesh, P("data"))}
    back = mgr.restore(state, shardings=sh)
    assert back["w"].sharding == sh["w"]
    np.testing.assert_array_equal(np.asarray(back["w"]), np.asarray(state["w"]))


# ---------------------------------------------------------------------------
# straggler detector
# ---------------------------------------------------------------------------


def test_straggler_detector_fires_on_slow_step():
    t = [0.0]

    def clock():
        return t[0]

    det = StragglerDetector(factor=3.0, warmup=3, clock=clock)
    for i in range(5):
        det.start()
        t[0] += 1.0  # steady 1s steps
        assert det.stop(i) is None
    det.start()
    t[0] += 10.0  # 10x slower
    ev = det.stop(5)
    assert ev is not None and ev.elapsed == 10.0 and ev.median == 1.0


# ---------------------------------------------------------------------------
# supervisor: crash -> restore -> identical result
# ---------------------------------------------------------------------------


def make_step():
    def step(state, batch):
        w = state["w"] + jnp.sum(batch["tokens"])
        return {"w": w}, {"loss": jnp.sum(w)}

    return step


def test_supervisor_restart_recovers_and_is_deterministic(tmp_path):
    data = SyntheticTokens(DataConfig(vocab=97, seq_len=16, global_batch=2))
    state0 = {"w": jnp.float32(0.0)}

    # clean run
    mgr1 = CheckpointManager(tmp_path / "a")
    sup1 = Supervisor(make_step(), data.batch_at, mgr1,
                      SupervisorConfig(checkpoint_every=5))
    clean, hist1 = sup1.run(state0, 0, 20)

    # faulty run: crash at steps 7 and 13
    crashes = {7, 13}

    def injector(step):
        if step in crashes:
            crashes.discard(step)
            raise RuntimeError(f"injected failure at {step}")

    mgr2 = CheckpointManager(tmp_path / "b")
    sup2 = Supervisor(make_step(), data.batch_at, mgr2,
                      SupervisorConfig(checkpoint_every=5))
    faulty, hist2 = sup2.run(state0, 0, 20, fail_injector=injector)

    np.testing.assert_allclose(float(clean["w"]), float(faulty["w"]))
    assert len([e for e in sup2.events if e["kind"] == "restart"]) == 2


def test_supervisor_restart_budget(tmp_path):
    data = SyntheticTokens(DataConfig(vocab=97, seq_len=8, global_batch=2))

    def injector(step):
        raise RuntimeError("always broken")

    mgr = CheckpointManager(tmp_path)
    sup = Supervisor(make_step(), data.batch_at, mgr,
                     SupervisorConfig(max_restarts=2))
    with pytest.raises(RuntimeError, match="restart budget"):
        sup.run({"w": jnp.float32(0.0)}, 0, 5, fail_injector=injector)


def test_supervisor_config_is_per_instance(tmp_path):
    """The default config must be built per Supervisor — a shared mutable
    default would leak tweaks (e.g. a bumped restart budget) across every
    supervisor in the process."""
    data = SyntheticTokens(DataConfig(vocab=97, seq_len=8, global_batch=2))
    mgr = CheckpointManager(tmp_path)
    a = Supervisor(make_step(), data.batch_at, mgr)
    b = Supervisor(make_step(), data.batch_at, mgr)
    assert a.config is not b.config
    a.config.max_restarts = 99
    assert b.config.max_restarts == SupervisorConfig().max_restarts


def test_straggler_window_and_warmup_plumbed_from_config(tmp_path):
    data = SyntheticTokens(DataConfig(vocab=97, seq_len=8, global_batch=2))
    mgr = CheckpointManager(tmp_path)
    sup = Supervisor(
        make_step(), data.batch_at, mgr,
        SupervisorConfig(straggler_factor=2.5, straggler_window=5,
                         straggler_warmup=2),
    )
    assert sup.detector.factor == 2.5
    assert sup.detector.window == 5
    assert sup.detector.warmup == 2


def test_straggler_window_bounds_the_median(tmp_path):
    """Old samples age out of the rolling window: after `window` fast
    steps the earlier slow regime no longer drags the median up."""
    t = [0.0]
    det = StragglerDetector(factor=3.0, window=4, warmup=2,
                            clock=lambda: t[0])
    for i, dt in enumerate([8.0, 8.0, 8.0, 1.0, 1.0, 1.0, 1.0]):
        det.start()
        t[0] += dt
        det.stop(i)
    assert det.times == [1.0, 1.0, 1.0, 1.0]
    det.start()
    t[0] += 4.0  # 4x the current median of 1.0 -> fires
    assert det.stop(99) is not None


def test_restart_history_has_strictly_increasing_steps(tmp_path):
    """After restore the rolled-back history entries are dropped, so the
    returned history never contains duplicated or out-of-order steps."""
    data = SyntheticTokens(DataConfig(vocab=97, seq_len=8, global_batch=2))
    crashes = {8, 13}

    def injector(step):
        if step in crashes:
            crashes.discard(step)
            raise RuntimeError(f"boom at {step}")

    mgr = CheckpointManager(tmp_path)
    sup = Supervisor(make_step(), data.batch_at, mgr,
                     SupervisorConfig(checkpoint_every=5))
    _, history = sup.run({"w": jnp.float32(0.0)}, 0, 20,
                         fail_injector=injector)
    steps = [h["step"] for h in history]
    assert steps == list(range(20))  # no duplicates from the replays


def test_restart_budget_resets_after_clean_streak(tmp_path):
    """Spaced transient failures must not accumulate against the budget:
    with ``restart_reset_after`` set, a long run survives one failure per
    epoch; without it the same pattern exhausts ``max_restarts``."""
    data = SyntheticTokens(DataConfig(vocab=97, seq_len=8, global_batch=2))

    def make_injector():
        crashes = {5, 15}

        def injector(step):
            if step in crashes:
                crashes.discard(step)
                raise RuntimeError(f"flake at {step}")

        return injector

    cfg = SupervisorConfig(checkpoint_every=2, max_restarts=1,
                           restart_reset_after=3)
    sup = Supervisor(make_step(), data.batch_at,
                     CheckpointManager(tmp_path / "reset"), cfg)
    _, history = sup.run({"w": jnp.float32(0.0)}, 0, 20,
                         fail_injector=make_injector())
    assert [h["step"] for h in history] == list(range(20))
    assert any(e["kind"] == "budget_reset" for e in sup.events)

    legacy = SupervisorConfig(checkpoint_every=2, max_restarts=1,
                              restart_reset_after=None)
    sup2 = Supervisor(make_step(), data.batch_at,
                      CheckpointManager(tmp_path / "legacy"), legacy)
    with pytest.raises(RuntimeError, match="restart budget"):
        sup2.run({"w": jnp.float32(0.0)}, 0, 20,
                 fail_injector=make_injector())


# ---------------------------------------------------------------------------
# data pipeline determinism / sharding
# ---------------------------------------------------------------------------


def test_data_deterministic_and_host_sharded():
    cfg = DataConfig(vocab=1000, seq_len=32, global_batch=8, seed=3)
    d = SyntheticTokens(cfg)
    a = d.batch_at(5)
    b = d.batch_at(5)
    np.testing.assert_array_equal(np.asarray(a["tokens"]), np.asarray(b["tokens"]))
    c = d.batch_at(6)
    assert not np.array_equal(np.asarray(a["tokens"]), np.asarray(c["tokens"]))
    # host shards are disjoint slices of the same global stream seed-wise
    h0 = d.batch_at(5, host_id=0, n_hosts=2)
    h1 = d.batch_at(5, host_id=1, n_hosts=2)
    assert h0["tokens"].shape == (4, 32)
    assert not np.array_equal(np.asarray(h0["tokens"]), np.asarray(h1["tokens"]))


def test_data_length_buckets_cycle():
    cfg = DataConfig(vocab=10, seq_len=64, global_batch=2, buckets=(1.0, 0.5))
    d = SyntheticTokens(cfg)
    assert d.batch_at(0)["tokens"].shape[1] == 64
    assert d.batch_at(1)["tokens"].shape[1] == 32
    assert d.batch_at(2)["tokens"].shape[1] == 64
