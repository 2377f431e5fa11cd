"""Property layer: seeded alloc/free/shrink/release fuzzing, every backend.

Each example is a deterministic random program (a seed expands to an
op sequence through one ``random.Random``) executed against a fresh
backend on a small device, with the allocator contract checked after
every operation and at drain:

  * reserved never drops below active, and the backend's own
    ``check_invariants`` holds at sampled points;
  * allocation failure surfaces as ``AllocatorOOM`` — a raw ``DeviceOOM``
    escaping a backend is a bug (the fault layer depends on this);
  * draining every live allocation leaves active at zero, and after
    ``release_cached`` + deferred-unmap drain the device agrees with the
    backend about what is still reserved.

Each contract is one module-level ``@given`` property parametrised over
the registry's backends, so every backend runs its own examples (one
property body shared by several test classes trips hypothesis's
``differing_executors`` health check). 200 random and 100 faulted
examples per backend keep within the suite's wall budget because
programs are pure host-side metadata churn.
"""

import random

import pytest

from repro.alloc import (
    GB,
    MB,
    AllocatorOOM,
    FaultInjector,
    FaultSchedule,
    FaultWindow,
    VMMDevice,
    registry,
)
from repro.alloc.chunks import DeviceOOM

from hypothesis import given, settings, strategies as st

CAPACITY = 256 * MB
N_OPS = 60
#: op mix: weights for (alloc_small, alloc_large, free, release, shrink)
_OP_WEIGHTS = (34, 14, 38, 10, 4)
_OPS = ("alloc_small", "alloc_large", "free", "release", "shrink")

#: every registered backend; the coverage tests below fail when a new
#: registration is missing here
BACKENDS = ("caching", "native", "gmlake", "stalloc", "ellm", "hybrid")


def _program(seed: int):
    """Expand ``seed`` into a deterministic op sequence."""
    rng = random.Random(seed)
    ops = []
    for _ in range(N_OPS):
        op = rng.choices(_OPS, weights=_OP_WEIGHTS)[0]
        if op == "alloc_small":
            ops.append(("alloc", rng.randrange(256 * 1024, 4 * MB)))
        elif op == "alloc_large":
            ops.append(("alloc", rng.randrange(4 * MB, 48 * MB)))
        elif op == "free":
            ops.append(("free", rng.random()))
        elif op == "shrink":
            ops.append(("shrink", rng.choice((2 * MB, 4 * MB, 8 * MB))))
        else:
            ops.append(("release", None))
    return ops


def _drain(alloc, live, device):
    for a in live:
        alloc.free(a)
    assert alloc.stats.active_bytes == 0
    alloc.check_invariants()
    alloc.release_cached()
    drain = getattr(alloc, "drain_deferred_unmaps", None)
    if drain is not None:
        drain()
    assert device.used_bytes == alloc.reserved_bytes


def _run_program(backend: str, device, seed: int) -> None:
    """Run ``seed``'s program on a fresh ``backend`` over ``device``,
    checking the contract after every op and at drain."""
    ops = _program(seed)
    alloc = registry.create(backend, device)
    live = []
    for i, (op, arg) in enumerate(ops):
        if op == "alloc":
            try:
                live.append(alloc.malloc(arg))
            except AllocatorOOM:
                pass
            except DeviceOOM as e:
                raise AssertionError(
                    f"raw DeviceOOM escaped {alloc.name}: {e}"
                ) from e
        elif op == "free" and live:
            alloc.free(live.pop(int(arg * len(live)) % len(live)))
        elif op == "shrink":
            device.shrink(arg)
        elif op == "release":
            alloc.release_cached()
        assert alloc.stats.active_bytes <= alloc.reserved_bytes, (
            f"{alloc.name}: active exceeds reserved after op {i} ({op})"
        )
        if i % 7 == 0:
            alloc.check_invariants()
    _drain(alloc, live, device)


@pytest.mark.parametrize("backend", BACKENDS)
@given(st.integers(min_value=0, max_value=2**31 - 1))
@settings(max_examples=200, deadline=None)
def test_random_interleaving_upholds_contract(backend, seed):
    _run_program(backend, VMMDevice(CAPACITY), seed)


def test_every_backend_is_fuzzed():
    """A new backend registration must join the property layer."""
    assert set(BACKENDS) == set(registry.names())


# ---------------------------------------------------------------------------
# fault-aware property layer: the same interleavings under injected faults
# ---------------------------------------------------------------------------


def _fault_schedule(seed: int) -> FaultSchedule:
    """Seed-derived multi-window fault schedule: a low base transient rate
    plus 1-3 windows of elevated create/map/release failure probability,
    landing inside the 60-op program's alloc-call range."""
    rng = random.Random(seed ^ 0xFA17)
    windows = []
    for _ in range(rng.randint(1, 3)):
        windows.append(FaultWindow(
            start_call=rng.randint(1, 60),
            duration=rng.randint(4, 16),
            create_fail_prob=rng.choice((0.0, 0.2, 0.4)),
            map_fail_prob=rng.choice((0.0, 0.2)),
            release_fail_prob=rng.choice((0.0, 0.3)),
        ))
    return FaultSchedule(
        seed=seed & 0xFFFF,
        create_fail_prob=0.02,
        burst=rng.choice((1, 2)),
        windows=tuple(windows),
    )


@pytest.mark.parametrize("backend", BACKENDS)
@given(st.integers(min_value=0, max_value=2**31 - 1))
@settings(max_examples=100, deadline=None)
def test_faulted_interleaving_upholds_contract(backend, seed):
    """The random programs re-run over a fault-injected device.

    Mid-fault ladder contract, asserted after *every* op while windows
    are live: a raw ``DeviceOOM`` (transient or not) never escapes a
    backend, active never exceeds reserved, ``check_invariants`` holds
    at sampled points, and the drain agreement survives absorbed
    release-side faults (frees are fire-and-forget; a release fault must
    stall, never leak).
    """
    device = FaultInjector(VMMDevice(CAPACITY), _fault_schedule(seed))
    _run_program(backend, device, seed)


def test_every_backend_is_fault_fuzzed():
    """A new backend registration must join the fault property layer."""
    assert set(BACKENDS) == set(registry.names())
