"""Round-5 slot-table core: interleavings, policy knob, counters.

The slot-table core keeps reconciled activity counts in one
slot-indexed numpy array (``sb_active``), freezes each plan's
refcounts as a ``(slot ids, counts)`` array pair, and recycles
destroyed slots only after a quarantine. Its replay digests on fixed
traces are pinned in ``tests/test_golden_equivalence.py``; the
randomized take/free/split/destroy interleavings here hold it to its
own invariant checker, including under forced dead-log compaction (the
quarantine-recycling edge).

The ``va_budget`` policy knob is the deliberate *non*-bit-identical
tier: a looser StitchFree VA budget trades address-space headroom for
fewer destroy/remap cycles. Its trade-off is pinned by the
load-independent ``model_cost_per_event`` signal (never wall time):
cost(speed) < cost(paper) <= cost(tight), peak stitched VA strictly the
other way around.
"""

import random

import pytest

from repro.alloc.caching_allocator import AllocatorOOM
from repro.alloc.chunks import VMMDevice
from repro.alloc.gmlake import VA_BUDGET_TIERS, GMLakeAllocator
from repro.core import GB, MB, PAPER_MODELS, inference_trace, replay

from hypothesis import given, settings, strategies as st


def _run_interleaving(alloc: GMLakeAllocator, seed: int, n_ops: int, every: int):
    """Random mallocs (odd sizes force splits, the spread forces
    multi-size stitches) and frees; an out-of-memory request must surface
    as ``AllocatorOOM``, and the invariant checker runs every ``every``
    ops and after the drain."""
    rng = random.Random(seed)
    live = []
    for i in range(n_ops):
        if live and rng.random() < 0.45:
            alloc.free(live.pop(rng.randrange(len(live))))
        else:
            try:
                live.append(alloc.malloc(rng.randrange(2 * MB, 320 * MB)))
            except AllocatorOOM:
                pass
        if i % every == 0:
            alloc.check_invariants()
    while live:
        alloc.free(live.pop())
    alloc.check_invariants()
    assert alloc.stats.active_bytes == 0


# ---------------------------------------------------------------------------
# randomized interleavings (takes, frees, splits via odd sizes, destroys
# via a tight VA budget)
# ---------------------------------------------------------------------------


@given(st.integers(min_value=0, max_value=2**31 - 1))
@settings(max_examples=60, deadline=None)
def test_lockstep_interleaving_parity(seed):
    alloc = GMLakeAllocator(VMMDevice(2 * GB), va_budget=700 * MB)
    _run_interleaving(alloc, seed, n_ops=70, every=9)


def test_interleaving_exercises_vectorized_machinery():
    """The interleaving must actually drive the slot machinery: StitchFree
    destroys whose shells are recycled and (with a shrunken dead log)
    quarantined-slot compaction, checked by the invariant checker on both
    sides of each compaction — otherwise the checks above are vacuous.
    (The cached-plan purge is driven deterministically by
    ``tests/test_plan_identity.py::test_destroy_purges_cached_plan_refs``.)
    """
    alloc = GMLakeAllocator(VMMDevice(2 * GB), va_budget=700 * MB)
    alloc.DEAD_LOG_LIMIT = 8
    _run_interleaving(alloc, 123, n_ops=220, every=31)
    assert alloc.hotspots["shell_reuse"] > 0, "no destroyed shell recycled"
    assert alloc.vec_counters["dead_compactions"] > 0, (
        "quarantine recycling never ran"
    )


# ---------------------------------------------------------------------------
# va_budget knob: resolution + modeled-cost-refereed trade-off
# ---------------------------------------------------------------------------


def test_va_budget_resolution():
    cap = 2 * GB
    mk = lambda **kw: GMLakeAllocator(VMMDevice(cap), **kw)
    assert mk().sblock_va_budget == 4 * cap  # default == "paper"
    assert mk(va_budget="paper").sblock_va_budget == 4 * cap
    assert mk(va_budget="tight").sblock_va_budget == cap
    assert mk(va_budget="speed").sblock_va_budget == float("inf")
    assert mk(va_budget=2.5).sblock_va_budget == int(2.5 * cap)
    assert mk(va_budget=700 * MB).sblock_va_budget == 700 * MB
    with pytest.raises(ValueError) as ei:
        mk(va_budget="warp")
    for tier in VA_BUDGET_TIERS:
        assert tier in str(ei.value)  # the error names the valid tiers


def test_va_budget_tradeoff_pinned_by_model_cost():
    """The fast tier is refereed by the load-independent modeled cost, not
    wall time: a looser budget must strictly cut modeled cost/event on the
    destroy-churn serving trace, and must strictly pay for it in peak
    stitched address space."""
    trace = inference_trace(PAPER_MODELS["vicuna-13b"], n_requests=1200, seed=5)
    cost = {}
    peak_va = {}
    for budget in ("tight", "paper", "speed"):
        a = GMLakeAllocator(VMMDevice(80 * GB), va_budget=budget)
        res, _ = replay(trace, a)
        cost[budget] = res.model_cost / (res.stats.n_alloc + res.stats.n_free)
        peak_va[budget] = a.peak_sblock_va
    assert cost["speed"] < cost["paper"] <= cost["tight"]
    assert peak_va["tight"] < peak_va["paper"] < peak_va["speed"]


# ---------------------------------------------------------------------------
# counters surfaced through the standard channels (no side channels)
# ---------------------------------------------------------------------------


def test_vec_counters_surfaced_in_replay_result():
    trace = inference_trace(PAPER_MODELS["vicuna-13b"], n_requests=400, seed=0)
    res, _ = replay(trace, "gmlake")
    assert set(res.vec_counters) == {"ref_purges", "slot_grows", "dead_compactions"}
    # non-gmlake backends have no slot tables and surface None
    res_c, _ = replay(trace, "caching")
    assert res_c.vec_counters is None


def test_vec_counters_surfaced_in_memory_report():
    import jax

    from repro.configs import get_arch
    from repro.models.api import family_of
    from repro.serve.engine import EngineConfig, ServeEngine

    cfg = get_arch("smollm-135m").smoke
    fam = family_of(cfg)
    params = fam.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServeEngine(
        cfg, params, EngineConfig(max_batch=2, max_len=64, n_chunks=64)
    )
    rep = eng.memory_report()
    assert "vec_counters" in rep
    alloc = eng.kv.arena.allocator
    if getattr(alloc, "vec_counters", None) is not None:
        assert rep["vec_counters"] == alloc.vec_counters
