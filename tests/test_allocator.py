"""Unit + property tests for the GMLake core allocator (paper §3-§4)."""

import random

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    CHUNK_SIZE,
    GB,
    MB,
    AllocatorOOM,
    CachingAllocator,
    GMLakeAllocator,
    NativeAllocator,
    PAPER_MODELS,
    PBlock,
    SBlock,
    VMMDevice,
    pack_extents,
    replay,
    round_up,
    run_workload,
    training_trace,
    unpack_extents,
)


def make_gmlake(capacity=4 * GB, **kw):
    return GMLakeAllocator(VMMDevice(capacity), **kw)


# ---------------------------------------------------------------------------
# extents
# ---------------------------------------------------------------------------


def test_pack_extents_roundtrip():
    ids = [0, 1, 2, 7, 8, 3, 10]
    ext = pack_extents(ids)
    assert [(e.start, e.n) for e in ext] == [(0, 3), (7, 2), (3, 1), (10, 1)]
    assert unpack_extents(ext) == ids


@given(st.lists(st.integers(0, 100), unique=True, max_size=64))
def test_pack_extents_property(ids):
    assert unpack_extents(pack_extents(ids)) == ids


# ---------------------------------------------------------------------------
# BestFit states (Algorithm 1)
# ---------------------------------------------------------------------------


def test_s4_cold_alloc_then_s1_exact_match():
    a = make_gmlake()
    x = a.malloc(64 * MB)
    assert a.state_counts["S4"] == 1 and isinstance(x.block, PBlock)
    a.free(x)
    y = a.malloc(64 * MB)
    assert a.state_counts["S1"] == 1 and y.block is x.block


def test_s2_split_single_larger_block():
    a = make_gmlake()
    x = a.malloc(128 * MB)
    a.free(x)
    y = a.malloc(32 * MB)  # split of the 128 MB pBlock
    assert a.state_counts["S2"] == 1
    assert y.block.size == 32 * MB
    # the opportunistic stitch preserved the original size in the tape:
    # freeing y and asking for 128 MB again must be an exact (S1) hit.
    a.free(y)
    z = a.malloc(128 * MB)
    assert a.state_counts["S1"] == 1 and isinstance(z.block, SBlock)
    assert a.reserved_bytes == 128 * MB  # no new physical memory
    a.check_invariants()


def test_s3_stitch_multiple_blocks():
    a = make_gmlake()
    xs = [a.malloc(32 * MB) for _ in range(4)]
    for x in xs:
        a.free(x)
    big = a.malloc(100 * MB)  # needs 4 x 32 MB stitched (with a split)
    assert a.state_counts["S3"] == 1
    assert isinstance(big.block, SBlock)
    assert a.reserved_bytes == 128 * MB  # reuses existing chunks only
    a.check_invariants()


def test_s4_partial_stitch_with_new_alloc():
    a = make_gmlake()
    x = a.malloc(32 * MB)
    a.free(x)
    y = a.malloc(96 * MB)  # 32 MB inactive + 64 MB fresh
    assert a.state_counts["S4"] == 2  # cold alloc + this one
    assert isinstance(y.block, SBlock)
    assert a.reserved_bytes == 96 * MB
    a.check_invariants()


def test_s5_oom_raises():
    a = make_gmlake(capacity=64 * MB)
    with pytest.raises(AllocatorOOM):
        a.malloc(128 * MB)
    assert a.state_counts["S5"] == 1


def test_oom_only_when_truly_out_of_memory():
    """The paper's effectiveness claim (§4.2.1): at a new peak, all inactive
    bytes are usable — GMLake only OOMs when active+request > capacity."""
    a = make_gmlake(capacity=128 * MB)
    xs = [a.malloc(2 * MB) for _ in range(64)]  # fill completely
    for x in xs[::2]:
        a.free(x)  # free every other block: maximally fragmented
    y = a.malloc(64 * MB)  # succeeds by stitching 32 scattered 2MB blocks
    assert y.block.size == 64 * MB
    a.check_invariants()


def test_frag_limit_blocks_are_not_stitched():
    a = make_gmlake(frag_limit=64 * MB)
    xs = [a.malloc(32 * MB) for _ in range(4)]
    for x in xs:
        a.free(x)
    y = a.malloc(128 * MB)
    # 32 MB blocks are below the limit: a fresh Alloc (S4) must happen
    assert a.state_counts["S4"] == 5  # 4 cold + 1 fresh
    assert a.reserved_bytes == 256 * MB
    assert y.block.size == 128 * MB


def test_small_allocs_use_splitting_pool():
    a = make_gmlake()
    x = a.malloc(1000)  # < 2 MB
    assert not isinstance(x.block, (PBlock, SBlock))
    assert a.reserved_bytes == 2 * MB  # one small segment
    a.free(x)


def test_stitchfree_lru_eviction():
    a = make_gmlake(va_budget=256 * MB)
    for sz in (96, 80, 112):
        xs = [a.malloc(16 * MB) for _ in range(sz // 16)]
        for x in xs:
            a.free(x)
        y = a.malloc(sz * MB)
        a.free(y)
    # VA budget forces LRU eviction of old sBlocks
    assert a._sblock_va_bytes <= 256 * MB
    a.check_invariants()


def test_update_keeps_physical_memory():
    a = make_gmlake()
    x = a.malloc(64 * MB)
    reserved = a.reserved_bytes
    a.free(x)
    assert a.reserved_bytes == reserved  # free() never releases chunks


def test_active_state_propagation():
    """An sBlock is active iff any member pBlock is active (paper §3.2)."""
    a = make_gmlake()
    x1, x2 = a.malloc(32 * MB), a.malloc(32 * MB)
    a.free(x1), a.free(x2)
    s = a.malloc(64 * MB)  # stitches both
    assert isinstance(s.block, SBlock) and s.block.active
    a.free(s)
    assert not s.block.active
    # grabbing one member pBlock directly re-activates the sBlock
    y = a.malloc(32 * MB)
    assert s.block.active
    a.free(y)
    a.check_invariants()


# ---------------------------------------------------------------------------
# caching allocator (baseline) behaviour
# ---------------------------------------------------------------------------


def test_caching_splits_and_coalesces():
    dev = VMMDevice(1 * GB)
    a = CachingAllocator(dev)
    x = a.malloc(8 * MB)  # 20 MB segment, split 8/12
    y = a.malloc(8 * MB)  # fits the 12 MB remainder, split 8/4
    assert a.reserved_bytes == 20 * MB
    a.free(x)
    a.free(y)
    z = a.malloc(18 * MB)  # only fits if the three free blocks coalesced
    assert a.reserved_bytes == 20 * MB
    a.check_invariants()
    a.free(z)


def test_caching_fragmentation_oom_where_gmlake_survives():
    """The paper's Figure 1 scenario: splitting strands capacity that
    stitching recovers."""
    cap = 128 * MB
    for name, expect_oom in (("caching", True), ("gmlake", False)):
        dev = VMMDevice(cap)
        alloc = CachingAllocator(dev) if name == "caching" else GMLakeAllocator(dev)
        # 9 MB allocs pack two per 20 MB segment in the caching allocator;
        # freeing every other one leaves a live neighbour in every segment,
        # so no segment can be released — capacity is stranded in holes.
        xs = [alloc.malloc(9 * MB) for _ in range(12)]
        for x in xs[::2]:
            alloc.free(x)
        if expect_oom:
            with pytest.raises(AllocatorOOM):
                alloc.malloc(48 * MB)
        else:
            y = alloc.malloc(48 * MB)
            assert y.block.size == 48 * MB


def test_native_allocator_costs_dominate():
    tr = training_trace(PAPER_MODELS["opt-1.3b"], "", world=1, batch=2, seq=512, iters=4)
    rn = run_workload(tr, "native", capacity_bytes=80 * GB)
    rc = run_workload(tr, "caching", capacity_bytes=80 * GB)
    assert rn.model_cost > 8 * rc.model_cost  # paper: ~10x


# ---------------------------------------------------------------------------
# property-based: random traces never violate invariants; GMLake never
# reserves more than the caching allocator needs for the same trace + never
# OOMs earlier.
# ---------------------------------------------------------------------------


@st.composite
def random_trace(draw):
    n_ops = draw(st.integers(10, 120))
    rng = random.Random(draw(st.integers(0, 2**31)))
    events = []
    live = []
    tid = 0
    for _ in range(n_ops):
        if live and rng.random() < 0.45:
            i = rng.randrange(len(live))
            events.append(("free", live.pop(i), 0))
        else:
            size = rng.choice([rng.randint(1, 4 * MB), rng.randint(4 * MB, 96 * MB)])
            events.append(("alloc", tid, size))
            live.append(tid)
            tid += 1
    return events


@given(random_trace())
@settings(max_examples=60, deadline=None)
def test_gmlake_invariants_on_random_traces(events):
    a = make_gmlake(capacity=8 * GB)
    live = {}
    for op, tid, size in events:
        if op == "alloc":
            live[tid] = a.malloc(size)
        else:
            a.free(live.pop(tid))
        a.check_invariants()
        # active never exceeds reserved
        assert a.stats.active_bytes <= a.reserved_bytes
    for alloc in live.values():
        a.free(alloc)
    a.check_invariants()


@given(random_trace())
@settings(max_examples=30, deadline=None)
def test_gmlake_never_ooms_before_true_capacity(events):
    """Every allocation must succeed while active-bytes + request (rounded
    to chunks, plus the small pool's segments) fits in device capacity."""
    cap = 2 * GB
    a = make_gmlake(capacity=cap)
    live = {}
    for op, tid, size in events:
        if op == "free":
            a.free(live.pop(tid))
            continue
        demand = a.stats.active_bytes + round_up(max(size, 1), CHUNK_SIZE) + 64 * MB
        try:
            live[tid] = a.malloc(size)
        except AllocatorOOM:
            assert demand > cap, (
                f"GMLake OOM with active={a.stats.active_bytes} req={size} cap={cap}"
            )
            break


def test_replay_caching_vs_gmlake_on_paper_workload():
    m = PAPER_MODELS["opt-13b"]
    tr = training_trace(m, strategies="LRO", world=4, batch=8, seq=2048, iters=8)
    rc = run_workload(tr, "caching", capacity_bytes=80 * GB)
    rg = run_workload(tr, "gmlake", capacity_bytes=80 * GB)
    assert not rg.oom
    assert rg.utilization > 0.9, rg.utilization  # paper: ~90-95 %+
    assert rg.utilization > rc.utilization + 0.1  # >=10 pt fragmentation win
    assert rg.stats.peak_reserved < rc.stats.peak_reserved


def test_gmlake_converges_to_exact_match():
    """Paper Fig. 14: after a few iterations allocation is ~all S1."""
    m = PAPER_MODELS["opt-1.3b"]
    tr = training_trace(m, strategies="LR", world=4, batch=8, seq=2048, iters=8)
    dev = VMMDevice(80 * GB)
    a = GMLakeAllocator(dev)
    _res, marks = replay(tr, a)
    iters = [c for lbl, c in marks if lbl.startswith("iter") or lbl == "end"]
    last_delta = {k: iters[-1][k] - iters[-2][k] for k in iters[-1]}
    tot = sum(last_delta.values())
    assert last_delta["S1"] / tot > 0.9
    assert last_delta["S4"] <= 2  # physical allocation has stopped
