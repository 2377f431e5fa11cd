"""Golden-digest equivalence tests, parametrized over the backend registry.

Two jobs:

1. The indexed-pool / LRU-heap / cached-extent / compact-sid-array rewrites
   of the gmlake and caching cores are pure mechanical-sympathy
   optimizations: for any trace they must produce the exact S1-S5 state
   counts, peak active/reserved bytes, and OOM points of the original
   (seed) implementation. Those digests were recorded by replaying the
   fixed-seed traces through the seed implementation (commit 97c6e93); any
   drift means a data-structure rewrite changed allocation policy.

2. Every backend in ``repro.alloc.registry`` must have pinned digests here
   (``test_registry_is_fully_pinned`` enforces it), so registering a new
   allocator forces recording its behaviour on the shared trace suite.
   The native and stalloc digests were recorded when each backend landed
   (stalloc: PR 3, this file).

The parametrization resolves backends through the registry-key replay path
(``replay(trace, "name", capacity_bytes=...)``), so string resolution,
device construction, and planning-backend ``prepare`` are covered too.
"""

from pathlib import Path

import pytest

from repro.alloc import registry
from repro.core import (
    GB,
    PAPER_MODELS,
    VMMDevice,
    inference_trace,
    replay,
    replay_batched,
    training_trace,
)
from repro.core.gmlake import GMLakeAllocator
from repro.core.trace import load_trace

#: Real ServeEngine-recorded stream (fixed seed; see
#: examples/record_engine_trace.py). All KV allocations are single-chunk
#: (2 MB) grows, so gmlake's path mix is S1-dominant — the
#: free-then-retake-at-the-same-size-class pattern the round-4
#: plan-identity fast path targets.
ENGINE_TRACE_PATH = (
    Path(__file__).parent / "data" / "serve_engine_smollm.trace.json"
)

#: Kill/recover scenario recording (examples/kill_recover_serving.py): a
#: fault-injected engine run with a supervisor restore mid-trace, so the
#: stream carries the restore's free/re-alloc churn and engine.restore
#: marks. Replayed here fault-free: digests pin that the *trace shape*
#: (and every backend's handling of it) stays put.
KILLRECOVER_TRACE_PATH = (
    Path(__file__).parent / "data" / "serve_engine_killrecover.trace.json"
)

#: Multi-tenant loadgen-driven engine recording (examples/
#: record_engine_trace.py --scenario multitenant): widened KV geometry
#: mixes single-chunk interactive churn with >=16 MB batch-class prompt
#: allocations, and every event carries tenant/SLO columns. This is the
#: trace where ellm's elastic arena earns its keep: best-fit spans pack
#: the large cohort tighter than either caching's split-block reuse or
#: pure stitching, so its pinned peak sits below both.
MULTITENANT_TRACE_PATH = (
    Path(__file__).parent / "data" / "serve_engine_multitenant.trace.json"
)

# (trace key, allocator backend, capacity GB) -> pinned digest.
# state_counts is None for backends without Algorithm-1 state tracking.
GOLDEN = {
    ("train_opt13b_LRO", "caching", 80): dict(
        state_counts=None, peak_active=20049543168, peak_reserved=29087498240,
        oom=False, oom_at_event=None, n_alloc=8201, n_free=8032,
    ),
    ("train_opt13b_LRO", "gmlake", 80): dict(
        state_counts={"S1": 5193, "S2": 108, "S3": 121, "S4": 219, "S5": 0},
        peak_active=20113784832, peak_reserved=20185088000,
        oom=False, oom_at_event=None, n_alloc=8201, n_free=8032,
    ),
    # 20 GB device: the splitting allocator strands capacity and OOMs at
    # event 12746; GMLake completes the same trace (the paper's core claim).
    ("train_opt13b_LRO", "caching", 20): dict(
        state_counts=None, peak_active=19430883328, peak_reserved=21422407680,
        oom=True, oom_at_event=12746, n_alloc=6474, n_free=6265,
    ),
    ("train_opt13b_LRO", "gmlake", 20): dict(
        state_counts={"S1": 5193, "S2": 108, "S3": 121, "S4": 219, "S5": 0},
        peak_active=20113784832, peak_reserved=20185088000,
        oom=False, oom_at_event=None, n_alloc=8201, n_free=8032,
    ),
    ("train_opt1.3b_LR", "caching", 80): dict(
        state_counts=None, peak_active=7304380416, peak_reserved=11026825216,
        oom=False, oom_at_event=None, n_alloc=4273, n_free=4072,
    ),
    ("train_opt1.3b_LR", "gmlake", 80): dict(
        state_counts={"S1": 3143, "S2": 117, "S3": 12, "S4": 137, "S5": 0},
        peak_active=7304380416, peak_reserved=7350517760,
        oom=False, oom_at_event=None, n_alloc=4273, n_free=4072,
    ),
    ("serve_vicuna", "caching", 80): dict(
        state_counts=None, peak_active=24018124800, peak_reserved=64181239808,
        oom=False, oom_at_event=None, n_alloc=2000, n_free=2000,
    ),
    ("serve_vicuna", "gmlake", 80): dict(
        state_counts={"S1": 16, "S2": 103, "S3": 1869, "S4": 12, "S5": 0},
        peak_active=24027070464, peak_reserved=24672993280,
        oom=False, oom_at_event=None, n_alloc=2000, n_free=2000,
    ),
    # 16 GB device: both allocators OOM at the same event with the same peaks
    ("serve_vicuna", "caching", 16): dict(
        state_counts=None, peak_active=15974301696, peak_reserved=15980298240,
        oom=True, oom_at_event=7, n_alloc=7, n_free=0,
    ),
    ("serve_vicuna", "gmlake", 16): dict(
        state_counts={"S1": 0, "S2": 0, "S3": 0, "S4": 8, "S5": 1},
        peak_active=15980298240, peak_reserved=15980298240,
        oom=True, oom_at_event=7, n_alloc=7, n_free=0,
    ),
    # -- native: reserved == active by construction (no pooling) ----------
    ("train_opt13b_LRO", "native", 80): dict(
        state_counts=None, peak_active=20028047360, peak_reserved=20028047360,
        oom=False, oom_at_event=None, n_alloc=8201, n_free=8032,
    ),
    ("train_opt1.3b_LR", "native", 80): dict(
        state_counts=None, peak_active=7302905856, peak_reserved=7302905856,
        oom=False, oom_at_event=None, n_alloc=4273, n_free=4072,
    ),
    ("serve_vicuna", "native", 80): dict(
        state_counts=None, peak_active=24018124800, peak_reserved=24018124800,
        oom=False, oom_at_event=None, n_alloc=2000, n_free=2000,
    ),
    ("serve_vicuna", "native", 16): dict(
        state_counts=None, peak_active=15973580800, peak_reserved=15973580800,
        oom=True, oom_at_event=7, n_alloc=7, n_free=0,
    ),
    # -- stalloc: planned peak beats caching on every trace; reserved is
    # the plan's single upfront arena *at device chunk granularity* (the
    # chaos sentinel's drain agreement caught the arena being published
    # un-rounded while cu_malloc holds the 2 MB-rounded size — the
    # planned peaks below carry that sub-chunk correction). Round-4
    # size-ordered offset assignment (place large intervals first) cut
    # planned fragmentation to train 0.7% / 0.7% / serve 14.5% (was
    # 7.4 / 3.9 / 14.9; caching: 31 / 34 / 63%) — see BENCHMARKS.md §5.1
    ("train_opt13b_LRO", "stalloc", 80): dict(
        state_counts=None, peak_active=20028047360, peak_reserved=20166213632,
        oom=False, oom_at_event=None, n_alloc=8201, n_free=8032,
    ),
    # 20 GB device: the round-3 arrival-order plan needed 21.6 GB and
    # failed fast here; the size-ordered plan fits in 18.8 GB, so the
    # planner now completes the trace a 20 GB device (like gmlake, and
    # unlike caching which strands its way to an OOM at event 12746)
    ("train_opt13b_LRO", "stalloc", 20): dict(
        state_counts=None, peak_active=20028047360, peak_reserved=20166213632,
        oom=False, oom_at_event=None, n_alloc=8201, n_free=8032,
    ),
    ("train_opt1.3b_LR", "stalloc", 80): dict(
        state_counts=None, peak_active=7302905856, peak_reserved=7358906368,
        oom=False, oom_at_event=None, n_alloc=4273, n_free=4072,
    ),
    ("serve_vicuna", "stalloc", 80): dict(
        state_counts=None, peak_active=24018124800, peak_reserved=28093448192,
        oom=False, oom_at_event=None, n_alloc=2000, n_free=2000,
    ),
    ("serve_vicuna", "stalloc", 16): dict(
        state_counts=None, peak_active=0, peak_reserved=0,
        oom=True, oom_at_event=0, n_alloc=0, n_free=0,
    ),
    # -- ellm: elastic weight arena + stitching core. Weight-class
    # requests land slab-quantized (peak reserved sits between gmlake's
    # stitched-tight peak and caching's stranded one); KV-sized requests
    # route to the embedded gmlake core, so the chunk-grow engine traces
    # reproduce gmlake's digests exactly ---------------------------------
    ("train_opt1.3b_LR", "ellm", 80): dict(
        state_counts={"S1": 0, "S2": 0, "S3": 0, "S4": 24, "S5": 0},
        peak_active=7304380416, peak_reserved=7600078848,
        oom=False, oom_at_event=None, n_alloc=4273, n_free=4072,
    ),
    ("serve_vicuna", "ellm", 80): dict(
        state_counts={"S1": 0, "S2": 0, "S3": 0, "S4": 0, "S5": 0},
        peak_active=24027070464, peak_reserved=30433869824,
        oom=False, oom_at_event=None, n_alloc=2000, n_free=2000,
    ),
    ("serve_engine_smollm", "ellm", 2): dict(
        state_counts={"S1": 240, "S2": 0, "S3": 0, "S4": 48, "S5": 0},
        peak_active=100663296, peak_reserved=100663296,
        oom=False, oom_at_event=None, n_alloc=288, n_free=288,
    ),
    ("serve_engine_killrecover", "ellm", 1): dict(
        state_counts={"S1": 54, "S2": 0, "S3": 0, "S4": 36, "S5": 0},
        peak_active=75497472, peak_reserved=75497472,
        oom=False, oom_at_event=None, n_alloc=90, n_free=90,
    ),
    # -- real engine-recorded serving trace (uniform 2 MB KV grows):
    # gmlake converges to S1 re-holds of previously-freed stitches --------
    ("serve_engine_smollm", "caching", 2): dict(
        state_counts=None,
        peak_active=100663296, peak_reserved=104857600,
        oom=False, oom_at_event=None, n_alloc=288, n_free=288,
    ),
    ("serve_engine_smollm", "native", 2): dict(
        state_counts=None,
        peak_active=100663296, peak_reserved=100663296,
        oom=False, oom_at_event=None, n_alloc=288, n_free=288,
    ),
    ("serve_engine_smollm", "gmlake", 2): dict(
        state_counts={"S1": 240, "S2": 0, "S3": 0, "S4": 48, "S5": 0},
        peak_active=100663296, peak_reserved=100663296,
        oom=False, oom_at_event=None, n_alloc=288, n_free=288,
    ),
    ("serve_engine_smollm", "stalloc", 2): dict(
        state_counts=None,
        peak_active=100663296, peak_reserved=100663296,
        oom=False, oom_at_event=None, n_alloc=288, n_free=288,
    ),
    # -- kill/recover scenario recording (restore churn mid-trace): all
    # KV grows are single-chunk, so gmlake is S1/S4-only here too --------
    ("serve_engine_killrecover", "caching", 1): dict(
        state_counts=None,
        peak_active=75497472, peak_reserved=83886080,
        oom=False, oom_at_event=None, n_alloc=90, n_free=90,
    ),
    ("serve_engine_killrecover", "native", 1): dict(
        state_counts=None,
        peak_active=75497472, peak_reserved=75497472,
        oom=False, oom_at_event=None, n_alloc=90, n_free=90,
    ),
    ("serve_engine_killrecover", "gmlake", 1): dict(
        state_counts={"S1": 54, "S2": 0, "S3": 0, "S4": 36, "S5": 0},
        peak_active=75497472, peak_reserved=75497472,
        oom=False, oom_at_event=None, n_alloc=90, n_free=90,
    ),
    ("serve_engine_killrecover", "stalloc", 1): dict(
        state_counts=None,
        peak_active=75497472, peak_reserved=75497472,
        oom=False, oom_at_event=None, n_alloc=90, n_free=90,
    ),
    # -- multi-tenant serving recording (mixed 2 MB churn + large batch
    # prompts): the one engine trace with real size diversity. Exact-fit
    # backends (native/stalloc) sit at peak_active; caching strands
    # ~300 MB in split remainders; gmlake's chunk caching holds slightly
    # more; ellm routes the large cohort through its elastic arena and
    # lands below caching — the acceptance ordering this PR pins --------
    ("serve_engine_multitenant", "caching", 2): dict(
        state_counts=None,
        peak_active=1736441856, peak_reserved=2048917504,
        oom=False, oom_at_event=None, n_alloc=648, n_free=648,
    ),
    ("serve_engine_multitenant", "native", 2): dict(
        state_counts=None,
        peak_active=1736441856, peak_reserved=1736441856,
        oom=False, oom_at_event=None, n_alloc=648, n_free=648,
    ),
    ("serve_engine_multitenant", "gmlake", 2): dict(
        state_counts={"S1": 341, "S2": 182, "S3": 15, "S4": 110, "S5": 0},
        peak_active=1736441856, peak_reserved=2099249152,
        oom=False, oom_at_event=None, n_alloc=648, n_free=648,
    ),
    ("serve_engine_multitenant", "stalloc", 2): dict(
        state_counts=None,
        peak_active=1736441856, peak_reserved=1736441856,
        oom=False, oom_at_event=None, n_alloc=648, n_free=648,
    ),
    ("serve_engine_multitenant", "ellm", 2): dict(
        state_counts={"S1": 72, "S2": 0, "S3": 0, "S4": 30, "S5": 0},
        peak_active=1736441856, peak_reserved=1908408320,
        oom=False, oom_at_event=None, n_alloc=648, n_free=648,
    ),
    # -- hybrid: packed-plan statics + embedded gmlake core for the
    # unplanned tail. On these fault-free traces with a full-trace plan
    # every request lands in the plan, so the core stays idle (all state
    # counts zero) and peak_reserved is the packed plan capacity at
    # device chunk granularity: training matches stalloc (polish
    # auto-skips — the FFD plan is already within 5% of the lower bound)
    # while serving drops from stalloc's 28.09 GB arena to 26.95 GB
    # (ruin-and-recreate packing) ---------------------------------------
    ("train_opt13b_LRO", "hybrid", 80): dict(
        state_counts={"S1": 0, "S2": 0, "S3": 0, "S4": 0, "S5": 0},
        peak_active=20028047360, peak_reserved=20166213632,
        oom=False, oom_at_event=None, n_alloc=8201, n_free=8032,
    ),
    ("train_opt1.3b_LR", "hybrid", 80): dict(
        state_counts={"S1": 0, "S2": 0, "S3": 0, "S4": 0, "S5": 0},
        peak_active=7302905856, peak_reserved=7358906368,
        oom=False, oom_at_event=None, n_alloc=4273, n_free=4072,
    ),
    ("serve_vicuna", "hybrid", 80): dict(
        state_counts={"S1": 0, "S2": 0, "S3": 0, "S4": 0, "S5": 0},
        peak_active=24018124800, peak_reserved=26954694656,
        oom=False, oom_at_event=None, n_alloc=2000, n_free=2000,
    ),
    ("serve_engine_smollm", "hybrid", 2): dict(
        state_counts={"S1": 0, "S2": 0, "S3": 0, "S4": 0, "S5": 0},
        peak_active=100663296, peak_reserved=100663296,
        oom=False, oom_at_event=None, n_alloc=288, n_free=288,
    ),
    ("serve_engine_killrecover", "hybrid", 1): dict(
        state_counts={"S1": 0, "S2": 0, "S3": 0, "S4": 0, "S5": 0},
        peak_active=75497472, peak_reserved=75497472,
        oom=False, oom_at_event=None, n_alloc=90, n_free=90,
    ),
    ("serve_engine_multitenant", "hybrid", 2): dict(
        state_counts={"S1": 0, "S2": 0, "S3": 0, "S4": 0, "S5": 0},
        peak_active=1736441856, peak_reserved=1736441856,
        oom=False, oom_at_event=None, n_alloc=648, n_free=648,
    ),
}

def test_registry_is_fully_pinned():
    """Every registered backend must have golden digests on this suite —
    a new backend registration without pinned behaviour fails here."""
    pinned = {case[1] for case in GOLDEN}
    missing = set(registry.names()) - pinned
    assert not missing, f"backends with no golden digests: {sorted(missing)}"


def _trace(key):
    if key == "train_opt13b_LRO":
        return training_trace(
            PAPER_MODELS["opt-13b"], "LRO", world=4, batch=8, seq=2048,
            iters=8, seed=0,
        )
    if key == "train_opt1.3b_LR":
        return training_trace(
            PAPER_MODELS["opt-1.3b"], "LR", world=4, batch=8, seq=2048,
            iters=8, seed=0,
        )
    if key == "serve_vicuna":
        return inference_trace(PAPER_MODELS["vicuna-13b"], n_requests=2000, seed=0)
    if key == "serve_engine_smollm":
        return load_trace(ENGINE_TRACE_PATH)
    if key == "serve_engine_killrecover":
        return load_trace(KILLRECOVER_TRACE_PATH)
    if key == "serve_engine_multitenant":
        return load_trace(MULTITENANT_TRACE_PATH)
    raise KeyError(key)


def _digest(res):
    return dict(
        state_counts=res.state_counts,
        peak_active=res.stats.peak_active,
        peak_reserved=res.stats.peak_reserved,
        oom=res.oom,
        oom_at_event=res.oom_at_event,
        n_alloc=res.stats.n_alloc,
        n_free=res.stats.n_free,
    )


@pytest.fixture(scope="module")
def traces():
    return {k: _trace(k) for k in {case[0] for case in GOLDEN}}


@pytest.mark.parametrize("case", sorted(GOLDEN, key=str))
def test_matches_seed_implementation(case, traces):
    trace_key, alloc_name, cap_gb = case
    res, _ = replay(traces[trace_key], alloc_name, capacity_bytes=cap_gb * GB)
    assert _digest(res) == GOLDEN[case]


@pytest.mark.parametrize("case", sorted(GOLDEN, key=str))
def test_batched_replay_matches_seed(case, traces):
    """replay_batched is a drop-in: identical digests AND identical marks."""
    trace_key, alloc_name, cap_gb = case
    res, marks = replay_batched(
        traces[trace_key], alloc_name, capacity_bytes=cap_gb * GB
    )
    assert _digest(res) == GOLDEN[case]

    _, ref_marks = replay(traces[trace_key], alloc_name, capacity_bytes=cap_gb * GB)
    assert marks == ref_marks


def test_multitenant_trace_carries_tenant_columns(traces):
    """The multi-tenant recording is only useful if the tenant/SLO columns
    actually round-tripped through the v1 JSON format."""
    tr = traces["serve_engine_multitenant"]
    tenants = {e.tenant for e in tr.events if e.tenant}
    slos = {e.slo for e in tr.events if e.slo}
    assert len(tenants) >= 3
    assert slos == {"interactive", "standard", "batch"}


def test_ellm_beats_caching_on_multitenant_trace():
    """The PR's acceptance ordering, read straight off the pinned digests:
    ellm's peak reservation on the multi-tenant serving trace sits below
    both caching's and gmlake's."""
    peak = lambda b: GOLDEN[("serve_engine_multitenant", b, 2)]["peak_reserved"]
    assert peak("ellm") < peak("caching")
    assert peak("ellm") < peak("gmlake")
    # and everyone agrees on what was actually live
    actives = {GOLDEN[("serve_engine_multitenant", b, 2)]["peak_active"]
               for b in registry.names()}
    assert len(actives) == 1


def test_invariants_hold_throughout_golden_traces(traces):
    """Sampled invariant checks over the training golden trace, every backend."""
    for name in registry.names():
        res, _ = replay(
            traces["train_opt1.3b_LR"], name, check_invariants_every=97
        )
        assert not res.oom, name


@pytest.mark.parametrize(
    "trace_key,cadence",
    [
        ("train_opt1.3b_LR", 1),
        ("train_opt1.3b_LR", 7),
        ("train_opt1.3b_LR", 97),
        # the serving trace is the S3-dominant stress case for the deferred
        # path: ~93% of requests free a held stitched block, so pending
        # frees and StitchFree interleave densely with the forced reconciles
        ("serve_vicuna", 3),
        ("serve_vicuna", 101),
    ],
)
def test_reconcile_timing_is_unobservable(trace_key, cadence, traces):
    """Deferred-free reconciliation must not be a behaviour knob.

    ``check_invariants`` reconciles pending sBlock frees, so replaying with
    invariant checks at different cadences forces reconciliation at
    arbitrary points mid-trace. Digests must match the unchecked replay
    exactly — if they ever diverge, the deferred free path leaked timing
    into allocation policy.
    """
    trace = traces[trace_key]
    allocator = GMLakeAllocator(VMMDevice(80 * GB))
    res, _ = replay(trace, allocator, check_invariants_every=cadence)
    assert _digest(res) == GOLDEN[(trace_key, "gmlake", 80)]


# ---------------------------------------------------------------------------
# gmlake digests pinned on the traces and settings that once compared its
# two refcount cores and its plan-identity fast paths against their
# force-disabled twin. Recorded on the last tree that carried those paths,
# where every path replayed to these exact digests; the one core left must
# keep them at every invariant-check cadence (each check forces a
# reconcile and a settle mid-trace).
# ---------------------------------------------------------------------------

# (trace key, va_budget tier) -> digest, including modeled device cost
CORE_PINS = {
    ("serve_vicuna_600_s3", "paper"): dict(
        state_counts={"S1": 11, "S2": 69, "S3": 497, "S4": 23, "S5": 0},
        peak_active=21386756096, peak_reserved=21533556736,
        oom=False, oom_at_event=None,
        n_alloc=600, n_free=600, model_cost=53178.620410156, marks=[],
    ),
    ("serve_vicuna_500_s3", "paper"): dict(
        state_counts={"S1": 10, "S2": 65, "S3": 402, "S4": 23, "S5": 0},
        peak_active=21386756096, peak_reserved=21533556736,
        oom=False, oom_at_event=None,
        n_alloc=500, n_free=500, model_cost=44605.307042969, marks=[],
    ),
    ("train_opt1.3b_LR_i4_s1", "paper"): dict(
        state_counts={"S1": 1507, "S2": 104, "S3": 6, "S4": 136, "S5": 0},
        peak_active=7056916480, peak_reserved=7082082304,
        oom=False, oom_at_event=None,
        n_alloc=2233, n_free=2036, model_cost=3275.18759375,
        marks=[
            ("iter0", {"S1": 0, "S2": 0, "S3": 0, "S4": 97, "S5": 0}),
            ("iter1", {"S1": 369, "S2": 7, "S3": 1, "S4": 134, "S5": 0}),
            ("iter2", {"S1": 747, "S2": 42, "S3": 2, "S4": 134, "S5": 0}),
            ("iter3", {"S1": 1128, "S2": 72, "S3": 3, "S4": 136, "S5": 0}),
            ("end", {"S1": 1507, "S2": 104, "S3": 6, "S4": 136, "S5": 0}),
        ],
    ),
    # every StitchFree policy tier on one trace: the tier changes policy,
    # so each has its own digest
    ("serve_vicuna_600_s7", "tight"): dict(
        state_counts={"S1": 10, "S2": 72, "S3": 496, "S4": 22, "S5": 0},
        peak_active=22443720704, peak_reserved=22470983680,
        oom=False, oom_at_event=None,
        n_alloc=600, n_free=600, model_cost=53137.70778125, marks=[],
    ),
    ("serve_vicuna_600_s7", "paper"): dict(
        state_counts={"S1": 11, "S2": 69, "S3": 498, "S4": 22, "S5": 0},
        peak_active=22443720704, peak_reserved=22468886528,
        oom=False, oom_at_event=None,
        n_alloc=600, n_free=600, model_cost=53024.658851562, marks=[],
    ),
    ("serve_vicuna_600_s7", "speed"): dict(
        state_counts={"S1": 16, "S2": 69, "S3": 493, "S4": 22, "S5": 0},
        peak_active=22443720704, peak_reserved=22468886528,
        oom=False, oom_at_event=None,
        n_alloc=600, n_free=600, model_cost=52745.6513125, marks=[],
    ),
}


def _core_trace(key):
    if key == "train_opt1.3b_LR_i4_s1":
        return training_trace(
            PAPER_MODELS["opt-1.3b"], "LR", world=4, batch=8, seq=2048,
            iters=4, seed=1,
        )
    n_requests, seed = {
        "serve_vicuna_600_s3": (600, 3),
        "serve_vicuna_500_s3": (500, 3),
        "serve_vicuna_600_s7": (600, 7),
    }[key]
    return inference_trace(
        PAPER_MODELS["vicuna-13b"], n_requests=n_requests, seed=seed
    )


@pytest.mark.parametrize(
    "trace_key,budget,cadence",
    [
        ("serve_vicuna_600_s3", "paper", 0),
        ("serve_vicuna_600_s3", "paper", 97),
        ("serve_vicuna_500_s3", "paper", 0),
        ("serve_vicuna_500_s3", "paper", 7),
        ("serve_vicuna_500_s3", "paper", 101),
        ("train_opt1.3b_LR_i4_s1", "paper", 0),
        ("train_opt1.3b_LR_i4_s1", "paper", 53),
        ("serve_vicuna_600_s7", "tight", 61),
        ("serve_vicuna_600_s7", "paper", 61),
        ("serve_vicuna_600_s7", "speed", 61),
    ],
)
def test_gmlake_core_digest_pinned(trace_key, budget, cadence):
    allocator = GMLakeAllocator(VMMDevice(80 * GB), va_budget=budget)
    res, marks = replay(
        _core_trace(trace_key), allocator, check_invariants_every=cadence
    )
    digest = dict(
        _digest(res), model_cost=round(res.model_cost, 9), marks=marks
    )
    assert digest == CORE_PINS[(trace_key, budget)]
