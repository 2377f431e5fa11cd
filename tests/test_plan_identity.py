"""Round-4 plan-identity correctness: frozen plans must be bit-inert.

The round-4 fast paths (frozen plan segments, cached plan refcounts,
plan-generation stamps, the destroyed-slot purge, sBlock shell
recycling) are pure mechanical sympathy; their replay digests are pinned
in ``tests/test_golden_equivalence.py``. These tests pin that the fast
path actually fires on the free-then-retake-at-the-same-size pattern it
targets, and that a *stale* cached plan — one whose slices were settled,
split, cherry-picked, or touched by a StitchFree destroy since the
freeze — is never re-activated wholesale (the slow re-hold runs
instead), with the invariant checker holding throughout.
"""

import random

import pytest

from repro.alloc.caching_allocator import AllocatorOOM
from repro.alloc.chunks import CHUNK_SIZE, ChunkRun, VMMDevice
from repro.alloc.gmlake import GMLakeAllocator
from repro.core import GB, MB

from hypothesis import given, settings, strategies as st

# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


class _Driver:
    """One allocator behind integer handles: ``malloc`` returns a handle,
    or -1 when the request surfaces as ``AllocatorOOM``; ``check`` runs
    the invariant checker."""

    def __init__(self, capacity=2 * GB, **kw):
        self.alloc = GMLakeAllocator(VMMDevice(capacity), **kw)
        self.live = {}
        self._next = 0

    def malloc(self, size) -> int:
        try:
            a = self.alloc.malloc(size)
        except AllocatorOOM:
            return -1
        tid = self._next
        self._next += 1
        self.live[tid] = a
        return tid

    def free(self, tid) -> None:
        self.alloc.free(self.live.pop(tid))

    def check(self) -> None:
        self.alloc.check_invariants()


# ---------------------------------------------------------------------------
# the fast path fires where it should...
# ---------------------------------------------------------------------------


def test_plan_identity_reactivates_frozen_plan():
    """free -> retake at the same size class is the targeted pattern: after
    the first stitched handout, every further cycle re-activates the cached
    plan wholesale (S1 + hold_fast), with no recount and no walk."""
    pair = _Driver()
    a, b = pair.malloc(256 * MB), pair.malloc(256 * MB)
    pair.free(a)
    pair.free(b)
    cycles = 6
    for _ in range(cycles):
        m = pair.malloc(512 * MB)  # S3 once, then S1 re-holds
        pair.free(m)
    assert pair.alloc.state_counts["S3"] == 1
    assert pair.alloc.state_counts["S1"] == cycles - 1
    assert pair.alloc.hotspots["hold_fast"] == cycles - 1
    assert pair.alloc.hotspots["hold_slow"] == 0
    pair.check()


def test_invariant_check_settles_and_downgrades_to_slow_path():
    """check_invariants reconciles + settles, which kills frozen segments;
    the next re-hold must notice (generation mismatch) and take the slow
    path, which rebuilds the plan."""
    pair = _Driver()
    a, b = pair.malloc(256 * MB), pair.malloc(256 * MB)
    pair.free(a)
    pair.free(b)
    m = pair.malloc(512 * MB)
    pair.free(m)
    pair.check()  # settles the pool: the cached plan's slices are broken up
    m = pair.malloc(512 * MB)
    assert pair.alloc.hotspots["hold_fast"] == 0
    assert pair.alloc.hotspots["hold_slow"] >= 1
    pair.free(m)
    # the slow re-hold rebuilt fresh frozen segments: fast again from here
    m = pair.malloc(512 * MB)
    assert pair.alloc.hotspots["hold_fast"] == 1
    pair.free(m)
    pair.check()


# ---------------------------------------------------------------------------
# ...and never where it must not: stale plans are not re-activated
# ---------------------------------------------------------------------------


def test_member_cherry_pick_invalidates_cached_plan():
    """Taking one member of a reconciled plan directly (S1 pBlock exact)
    settles its bucket; when it comes back, the cached plan must NOT be
    re-activated wholesale (the slice was broken up)."""
    pair = _Driver()
    a, b = pair.malloc(256 * MB), pair.malloc(254 * MB)
    pair.free(a)
    pair.free(b)
    m = pair.malloc(510 * MB)  # stitches both
    pair.free(m)
    # cherry-pick one member size out of the pooled plan...
    c = pair.malloc(256 * MB)
    assert pair.alloc.state_counts["S1"] == 1  # exact pBlock hit
    pair.free(c)
    # ...then retake the stitched size: the plan survived in *content* but
    # its slices were settled/cherry-picked — wholesale reuse is unsound
    m = pair.malloc(510 * MB)
    assert pair.alloc.hotspots["hold_fast"] == 0
    assert pair.alloc.hotspots["hold_slow"] >= 1
    pair.free(m)
    pair.check()


def test_split_of_pooled_member_invalidates_cached_plan():
    """A split of a pooled plan member (S2 on a larger request than any
    single block) changes the membership; the stale plan must not be
    re-activated."""
    pair = _Driver()
    a, b = pair.malloc(256 * MB), pair.malloc(256 * MB)
    pair.free(a)
    pair.free(b)
    m = pair.malloc(512 * MB)
    pair.free(m)
    # S2: splits one pooled 256 MB member (frag limit is 8 MB)
    c = pair.malloc(100 * MB)
    assert pair.alloc.state_counts["S2"] == 1
    pair.free(c)
    m = pair.malloc(512 * MB)  # S1 on the (now 3-member) stitched block
    assert pair.alloc.hotspots["hold_fast"] == 0
    assert pair.alloc.hotspots["hold_slow"] >= 1
    pair.free(m)
    pair.check()


def test_destroy_purges_cached_plan_refs():
    """StitchFree destroys between a free and a retake: the cached plan's
    frozen refcounts name the destroyed block's slot (they shared members)
    and must be purged against the slot table's aliveness before the plan
    is re-activated — a frozen plan must never resurrect a destroyed
    sBlock."""
    pair = _Driver(capacity=2 * GB, va_budget=700 * MB)
    a, b = pair.malloc(256 * MB), pair.malloc(256 * MB)
    pair.free(a)
    pair.free(b)
    m1 = pair.malloc(512 * MB)  # stitch #1 (va 512 MB, under budget)
    dead_slot = pair.live[m1].block.slot
    pair.free(m1)
    m2 = pair.malloc(510 * MB)  # stitch #2 over the same members (+ split)
    pair.free(m2)  # va > budget -> StitchFree destroys stitch #1
    assert not pair.alloc._vec_core.sb_alive[dead_slot], "stitch #1 survived"
    # retake stitch #2's size: its cached plan is intact (the destroy only
    # removed the dead block from the shared members' refs), so the fast
    # path fires — after masking the dead slot out of the cached refs
    m3 = pair.malloc(510 * MB)
    assert pair.alloc.hotspots["hold_fast"] == 1
    assert pair.alloc.vec_counters["ref_purges"] == 1
    refs_slots = pair.live[m3].block._refs[0].tolist()
    assert dead_slot not in refs_slots, "destroyed block resurrected"
    pair.free(m3)
    pair.check()


# ---------------------------------------------------------------------------
# randomized interleaving (property-style; runs seeded and bounded)
# ---------------------------------------------------------------------------


@settings(deadline=None, max_examples=10)
@given(st.integers(min_value=0, max_value=10_000))
def test_randomized_interleaving_is_mode_identical(seed):
    """Random take/free/split/destroy interleavings: an out-of-memory
    request surfaces as ``AllocatorOOM``, and the invariant checker holds
    at sampled points (which also varies settle/reconcile timing)."""
    rng = random.Random(seed)
    # small device + tight VA budget: forces stitching, splits, StitchFree
    # destroys, OOMs — every invalidation source the fast path must survive
    pair = _Driver(capacity=512 * MB, va_budget=600 * MB)
    sizes = [
        2 * MB, 3 * MB, 8 * MB, 16 * MB, 17 * MB, 32 * MB, 64 * MB,
        100 * MB, 128 * MB,
    ]
    tids = []
    for step in range(120):
        op = rng.random()
        if op < 0.55 or not tids:
            tid = pair.malloc(rng.choice(sizes))
            if tid >= 0:
                tids.append(tid)
        else:
            tid = tids.pop(rng.randrange(len(tids)))
            pair.free(tid)
        if step % 17 == 0:
            pair.check()
    pair.check()
    for tid in tids:
        pair.free(tid)
    pair.check()


# ---------------------------------------------------------------------------
# ChunkRun: the O(1) split-slicing view (round 4, chunks.py)
# ---------------------------------------------------------------------------


def test_chunkrun_views_share_storage_and_compare_like_lists():
    base = list(range(10, 30))
    run = ChunkRun(base)
    assert len(run) == 20 and list(run) == base and run == base
    left, right = run[:7], run[7:]
    assert isinstance(left, ChunkRun) and isinstance(right, ChunkRun)
    assert left.base is base and right.base is base  # O(1): no copying
    assert list(left) + list(right) == base
    assert left[0] == 10 and right[-1] == 29 and right[0] == 17
    nested = right[2:5]
    assert nested == base[9:12] and nested.base is base
    with pytest.raises(IndexError):
        left[7]


def test_split_produces_chunk_views_not_copies():
    a = GMLakeAllocator(VMMDevice(1 * GB))
    x = a.malloc(256 * MB)
    a.free(x)
    y = a.malloc(100 * MB)  # S2: splits the pooled 256 MB block
    chunks = y.block.chunks
    assert isinstance(chunks, ChunkRun)
    assert len(chunks) == (100 * MB + CHUNK_SIZE - 1) // CHUNK_SIZE
    a.check_invariants()
    a.free(y)
    a.check_invariants()


def test_dead_log_compaction_bounds_memory_and_stays_identical():
    """Quarantined slots are recycled (and stale plan caches dropped)
    past DEAD_LOG_LIMIT, so memory stays O(live) — with the invariant
    checker holding after the compactions."""
    pair = _Driver(capacity=2 * GB, va_budget=700 * MB)
    pair.alloc.DEAD_LOG_LIMIT = 3  # instance override: compact every 4 destroys
    a, b = pair.malloc(256 * MB), pair.malloc(256 * MB)
    pair.free(a)
    pair.free(b)
    for i in range(12):  # fresh stitch + StitchFree destroy per cycle
        m = pair.malloc((512 - 2 * i) * MB)
        pair.free(m)
    assert pair.alloc._vec_core.quarantined <= 4
    assert pair.alloc.vec_counters["dead_compactions"] >= 2
    pair.check()


def test_shell_generations_never_collide_across_lives():
    """A recycled shell's generation continues monotonically, so a stale
    holder stamp from the previous life can never read as active."""
    a = GMLakeAllocator(VMMDevice(2 * GB), va_budget=700 * MB)
    x, y = a.malloc(256 * MB), a.malloc(256 * MB)
    a.free(x)
    a.free(y)
    # alternating size classes force fresh stitches; the tight VA budget
    # destroys the previous one each cycle, so its shell gets recycled
    for i in range(6):
        m = a.malloc((512 - 2 * i) * MB)
        a.free(m)
    assert a.hotspots["shell_reuse"] >= 1
    held_gens = [s.gen for s in a._sblocks.values()]
    assert all(g >= 1 for g in held_gens)
    a.check_invariants()
