"""Deterministic micro-hotspot profile of the serving replay (gmlake).

Wall-clock replay numbers on shared runners drift ~2x with container load
(see BENCHMARKS.md variance note), which makes "did round N+1 actually cut
the hot term?" arguments fragile. This harness runs the S3-dominant
serving replay (the allocator stress case) under **deterministic cProfile**
— every call traced, exact call counts, no sampling — and reports a small
set of **named hotspot terms** keyed by function identity, so future
rounds compare `_take_stitch_candidates`-the-term against itself instead
of eyeballing load-noisy end-to-end walls:

  * call counts (``ncalls``) are bit-deterministic for a fixed-seed trace —
    any drift is a behaviour change, not noise;
  * per-term cumulative/total times still move with load, but ratios of
    terms recorded in one session (e.g. A/B of two checkouts, interleaved)
    are far more stable than absolute walls, and the term decomposition
    shows *where* a regression lives.

Terms are resolved from the live module at run time (code-object identity
for methods like ``SBlock.__init__`` whose bare name is ambiguous), with
graceful absence: a term whose function does not exist in this version
(e.g. ``_split_parts`` before round 4) contributes only its existing
functions. ``named_combined_cum`` sums the four round-4 acceptance terms
(take + split + reconcile + SBlock.__init__).

Emits ``BENCH_profile.json`` (via ``benchmarks.common.emit_json``); CI
runs ``--fast`` mode and uploads the file next to ``BENCH_replay.json``.
``benchmarks/compare_replay.py --profile-baseline/--profile-candidate``
**blocks** on per-term call-count drift — call counts are
load-independent — while the time columns stay informational
(warn-annotate only).
"""

from __future__ import annotations

import cProfile
import gc
import pstats
from typing import Dict, List, Optional, Sequence

from repro.core import GB, PAPER_MODELS, VMMDevice, inference_trace, replay_batched

from .common import Row, emit, emit_json

#: The four terms the round-4 acceptance tracks, plus context terms.
#: Each maps to the attribute paths (resolved on the live module) whose
#: profile rows are summed into the term.
TERM_SPECS: Dict[str, Sequence[str]] = {
    "take_stitch_candidates": ("GMLakeAllocator._take_stitch_candidates",),
    "split": ("GMLakeAllocator._split", "GMLakeAllocator._split_parts"),
    "reconcile": ("GMLakeAllocator._reconcile",),
    "sblock_init": ("SBlock.__init__",),
    # context (not part of the acceptance sum):
    "stitch_plan": ("GMLakeAllocator._stitch_plan", "GMLakeAllocator._stitch"),
    "hold_sblock": ("GMLakeAllocator._hold_sblock",),
    "destroy_sblock": ("GMLakeAllocator._destroy_sblock",),
    "malloc": ("GMLakeAllocator.malloc",),
    "free": ("GMLakeAllocator.free",),
    # round-5 slot-table passes. The take tail's membership count pass is
    # the round-5 "improve >=1.5x like-for-like" floor term.
    "take_count_pass": ("GMLakeAllocator._count_segs_refs",),
    "vec_refcount_apply": (
        "GMLakeAllocator._apply_activation",
        "GMLakeAllocator._refs_decrement",
    ),
    "vec_purge_compact": (
        "GMLakeAllocator._purge_refs",
        "GMLakeAllocator._compact_dead_log",
    ),
}

#: Terms whose cumulative times sum into ``named_combined_cum`` — the
#: round-4 acceptance metric ("combined take + split + reconcile +
#: SBlock.__init__ terms reduced >= 2x vs the round-3 recording").
ACCEPTANCE_TERMS = ("take_stitch_candidates", "split", "reconcile", "sblock_init")

#: Terms whose cumulative times sum into ``floor_terms_cum`` — the round-5
#: acceptance metric ("take count pass + reconcile refcount pair improve
#: >= 1.5x vs the round-4 recording"). These two carry the floor work in
#: every recording since round 3 (the count pass is inside the take term;
#: the refcount decrement pair is inside reconcile), so the ratio is
#: like-for-like across rounds even though the round-5 sub-terms
#: (``take_count_pass``, ``vec_refcount_apply``) are new.
FLOOR_TERMS = ("take_stitch_candidates", "reconcile")


def _resolve_term_keys() -> Dict[str, List[tuple]]:
    """Map term name -> pstats keys (filename, firstlineno, funcname).

    Resolved from the live ``repro.alloc.gmlake`` module so the harness
    keeps working across rounds that rename/add/remove helpers: missing
    attribute paths are skipped, and ambiguous names (``__init__``) are
    pinned by code-object identity.
    """
    from repro.alloc import gmlake as g

    keys: Dict[str, List[tuple]] = {}
    for term, paths in TERM_SPECS.items():
        term_keys = []
        for path in paths:
            obj = g
            try:
                for part in path.split("."):
                    obj = getattr(obj, part)
            except AttributeError:
                continue  # not present in this version of the module
            code = getattr(obj, "__code__", None)
            if code is not None:
                term_keys.append((code.co_filename, code.co_firstlineno, code.co_name))
        keys[term] = term_keys
    return keys


def profile_replay(fast: bool = False, n_requests: Optional[int] = None) -> dict:
    """Profile one gmlake serving replay; returns the JSON payload dict."""
    from repro.alloc import registry

    if n_requests is None:
        n_requests = 1600 if fast else 8000
    trace = inference_trace(
        PAPER_MODELS["vicuna-13b"], n_requests=n_requests, seed=0
    )
    trace.compiled()  # compile outside the profiled window
    allocator = registry.create("gmlake", VMMDevice(80 * GB))
    gc.collect()
    prof = cProfile.Profile()
    prof.enable()
    res, _marks = replay_batched(trace, allocator)
    prof.disable()

    stats = pstats.Stats(prof)
    stats.calc_callees()  # populates total_tt
    term_keys = _resolve_term_keys()
    terms: Dict[str, dict] = {}
    for term, keys in term_keys.items():
        ncalls = tottime = cumtime = 0.0
        for key in keys:
            row = stats.stats.get(key)
            if row is None:
                continue
            cc, nc, tt, ct, _callers = row
            ncalls += nc
            tottime += tt
            cumtime += ct
        terms[term] = {
            "ncalls": int(ncalls),
            "tottime": round(tottime, 6),
            "cumtime": round(cumtime, 6),
        }

    top = []
    for key, (cc, nc, tt, ct, _callers) in sorted(
        stats.stats.items(), key=lambda kv: -kv[1][3]
    )[:20]:
        filename, lineno, funcname = key
        short = filename.rsplit("/", 1)[-1] if "/" in filename else filename
        top.append(
            {
                "function": f"{short}:{lineno}({funcname})",
                "ncalls": nc,
                "tottime": round(tt, 6),
                "cumtime": round(ct, 6),
            }
        )

    combined = round(sum(terms[t]["cumtime"] for t in ACCEPTANCE_TERMS), 6)
    floor = round(sum(terms[t]["cumtime"] for t in FLOOR_TERMS), 6)
    return {
        "benchmark": "profile",
        "fast": fast,
        "allocator": "gmlake",
        "trace": f"serve_vicuna_{len(trace.events) // 1000}k",
        "n_events": len(trace.events),
        "total_seconds": round(stats.total_tt, 6),
        "named_combined_cum": combined,
        "acceptance_terms": list(ACCEPTANCE_TERMS),
        # round-5 floor: the take count pass + reconcile refcount pair,
        # read off the two terms every recording since round 3 carries —
        # compare this single number across rounds' BENCH_profile.json
        "floor_terms_cum": floor,
        "floor_terms": list(FLOOR_TERMS),
        "terms": terms,
        "top": top,
        "state_counts": res.state_counts,
        "hotspot_counters": dict(allocator.hotspots),
        "vec_counters": dict(allocator.vec_counters),
        "unit": {
            "terms": "per-function ncalls (deterministic) + tottime/cumtime "
            "seconds under cProfile (load-sensitive; compare interleaved "
            "recordings, or ratios within one session)",
            "named_combined_cum": "sum of the acceptance terms' cumtime",
        },
    }


def run(fast: bool = False, allocators: Optional[Sequence[str]] = None) -> None:
    # the profile is gmlake-specific (it names gmlake internals); the
    # --allocator flag of the harness is accepted but ignored beyond a note.
    # Full mode records the best of 3 (by the floor-term sum) — call counts
    # are identical across repeats, so min-of-N only de-noises the time
    # columns; fast/CI mode stays single-shot.
    repeats = 1 if fast else 3
    payload = min(
        (profile_replay(fast=fast) for _ in range(repeats)),
        key=lambda p: p["floor_terms_cum"],
    )
    rows = [
        Row(
            f"profile/{term}",
            (t["cumtime"] / t["ncalls"] * 1e6) if t["ncalls"] else 0.0,
            t["cumtime"],
            extra=f"ncalls:{t['ncalls']}",
        )
        for term, t in payload["terms"].items()
    ]
    rows.append(
        Row("profile/NAMED_COMBINED", 0.0, payload["named_combined_cum"],
            extra="+".join(payload["acceptance_terms"]))
    )
    emit(rows, "deterministic serving-replay hotspot profile: term,us/call,cum_s")
    emit_json("profile", payload)


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--fast", action="store_true")
    args = ap.parse_args()
    run(fast=args.fast)
