"""Diff two BENCH_replay.json files and flag replay regressions.

CI calls this with the previous successful run's artifact as the baseline and
the fresh run's output as the candidate:

    python -m benchmarks.compare_replay baseline.json candidate.json \
        [--threshold 0.20] [--model-threshold 0.02] [--annotate-only]

The gate is **two-tier**, modeled cost first, wall time second:

  1. ``model_cost_per_event`` — the VMMCostLedger's modeled device-API cost
     (cuMalloc units). It is a pure function of the allocator's decisions on
     the fixed-seed trace: bit-stable across machines and container load.
     Any drift beyond ``--model-threshold`` (default 2%) means allocation
     *policy* changed — a real finding regardless of how noisy the runner
     is, flagged as ``model`` regressions.
  2. ``us_per_call`` — host wall time, the number users feel, but noisy
     (~±20 % on a loaded runner). Gated at the looser ``--threshold``.

Rows carrying ``hybrid_counters`` (the hybrid backend's planned/spilled
routing split) get an exact-match check *before* both tiers: the split is
a deterministic function of the plan on the fixed-seed trace, so any
drift — in particular a plan that silently stops covering requests and
routes everything to the spill path — **blocks** (subject to
``--annotate-only``), attributed as a ``hybrid`` finding.

A **serving** tier activates when both ``--serving-baseline`` and
``--serving-candidate`` point at BENCH_serving.json files (see
``benchmarks/bench_serving.py``): per-backend, per-SLO-class modeled
TTFT/TPOT percentiles plus modeled cost / peak reserved / deferral counts
are deterministic functions of the seeded schedule, so drift beyond
``--model-threshold`` **blocks** (subject to ``--annotate-only``); wall
time only ever warns. A changed load config skips the tier rather than
comparing incomparables.

A third, **hotspot** tier compares named terms from ``BENCH_profile.json``
files (see ``benchmarks/bench_profile.py``) when both
``--profile-baseline`` and ``--profile-candidate`` are readable. It is
two-speed, mirroring the replay tier: per-term *call counts* are
deterministic for the fixed-seed trace — load cannot move them — so any
drift **blocks** (subject to ``--annotate-only``). Per-term
cumulative-*time* ratios beyond ``--profile-threshold`` (default 1.5x)
stay informational ``::warning`` annotations — term times are
load-sensitive, so they exist to *name* the hot term that moved, not to
block.

A fourth, **chaos** tier compares campaign verdicts from
``BENCH_chaos.json`` files (see ``benchmarks/bench_chaos.py``) when both
``--chaos-baseline`` and ``--chaos-candidate`` are given. Verdicts are
deterministic functions of the seeded scenario set, so the rules are
absolute: a ``scenario/backend/mode`` leg whose verdict was ok must stay
ok, and sentinel-violation / unrecovered-replay-fault counters that were
zero must stay zero, per leg and in aggregate — any flip **blocks**
(subject to ``--annotate-only``). New legs pass freely; disappeared legs
warn.

Exit codes: 0 = no regression (or --annotate-only), 1 = at least one
trace x allocator pair regressed on any blocking tier, or the
candidate file itself is unreadable (a defect in this very run, never
suppressed). A missing or unreadable *baseline* (corrupt artifact, schema
drift in perf history) warns and exits 0 — an absent perf history must
never block the build. Rows present on only one side (renamed traces, new
allocators) are reported but never fail the check. GitHub-flavoured
``::warning``/``::error`` annotations are emitted for every finding so
regressions surface on the PR without digging through logs.
"""

from __future__ import annotations

import argparse
import json
import sys


def _rows(payload: dict) -> dict:
    try:
        return {
            r["name"]: (
                float(r["us_per_call"]),
                r.get("model_cost_per_event"),
                r.get("hybrid_counters"),
            )
            for r in payload["rows"]
        }
    except (KeyError, TypeError) as e:
        raise ValueError(f"not a BENCH_replay.json payload: {e}") from e


def _hybrid_digest(counters) -> str:
    """Routing-split digest of a row's ``hybrid_counters``: which requests
    the plan served vs spilled to the stitching core. Deterministic for
    the fixed-seed trace, so *any* drift is a policy change — in
    particular a plan that silently stops covering anything (everything
    routed to spill) must fail the gate, not slide through as a small
    modeled-cost wobble."""
    return (
        f"planned {counters.get('planned_allocs')} "
        f"({counters.get('planned_bytes')} B) / "
        f"spilled {counters.get('spilled_allocs')} "
        f"({counters.get('spilled_bytes')} B)"
    )


def compare(baseline: dict, candidate: dict, threshold: float, model_threshold: float):
    """Returns (regressions, improvements, missing).

    ``regressions``/``improvements`` map row name -> (signal, old, new,
    ratio) where ``signal`` is ``"hybrid"`` (planned/spilled routing split
    — exact-match, any drift blocks), ``"model"`` (modeled device-API
    cost — the load-independent tier, checked first) or ``"wall"`` (host
    µs/event). A row only reaches the wall tier if its deterministic
    signals are clean, so a policy change is always attributed to the
    deterministic number.
    """
    base = _rows(baseline)
    cand = _rows(candidate)
    regressions, improvements = {}, {}
    for name, (new_us, new_model, new_hc) in cand.items():
        entry = base.get(name)
        if entry is None:
            continue
        old_us, old_model, old_hc = entry
        if old_hc is not None and new_hc is not None and old_hc != new_hc:
            # deterministic routing split changed; this outranks both the
            # modeled and wall tiers for this row
            regressions[name] = (
                "hybrid", _hybrid_digest(old_hc), _hybrid_digest(new_hc), 1.0
            )
            continue
        if old_model and new_model is not None:
            ratio = new_model / old_model
            if ratio > 1.0 + model_threshold:
                regressions[name] = ("model", old_model, new_model, ratio)
                continue  # modeled drift explains (and outranks) any wall drift
            if ratio < 1.0 - model_threshold:
                improvements[name] = ("model", old_model, new_model, ratio)
        if old_us > 0:
            ratio = new_us / old_us
            if ratio > 1.0 + threshold:
                regressions[name] = ("wall", old_us, new_us, ratio)
            elif ratio < 1.0 - threshold and name not in improvements:
                improvements[name] = ("wall", old_us, new_us, ratio)
    missing = sorted(set(base) - set(cand))
    return regressions, improvements, missing


_UNITS = {"model": "model-cost/event", "wall": "us/event"}


def compare_profiles(baseline: dict, candidate: dict, threshold: float):
    """Hotspot-term diff of two BENCH_profile.json payloads.

    Returns a list of (kind, term, old, new) findings where ``kind`` is
    ``"time"`` (cumtime ratio past threshold — load-sensitive, never
    blocks) or ``"ncalls"`` (call-count drift — deterministic, so any
    change is a behaviour change).
    """
    findings = []
    base_terms = baseline.get("terms", {})
    cand_terms = candidate.get("terms", {})
    for term, cand_t in cand_terms.items():
        base_t = base_terms.get(term)
        if base_t is None:
            continue
        if base_t.get("ncalls") != cand_t.get("ncalls"):
            findings.append(("ncalls", term, base_t.get("ncalls"), cand_t.get("ncalls")))
        old_ct, new_ct = base_t.get("cumtime", 0.0), cand_t.get("cumtime", 0.0)
        if old_ct > 0.01 and new_ct / old_ct > threshold:
            findings.append(("time", term, old_ct, new_ct))
    for term, base_t in base_terms.items():
        if term not in cand_terms:
            # a term that vanished (function deleted/renamed) is the
            # largest possible call-count drift, not a clean bill
            findings.append(("ncalls", term, base_t.get("ncalls"), None))
    return findings


def _profile_tier(profile_baseline, profile_candidate, threshold,
                  annotate_only) -> int:
    """Run the hotspot-term tier. Returns the number of blocking findings.

    Call counts are deterministic for the fixed-seed trace — load cannot
    move them — so call-count drift **blocks** (subject to
    ``--annotate-only``). Term *times* stay informational: they are
    load-sensitive, so they only ever warn.
    """
    try:
        with open(profile_baseline) as f:
            base = json.load(f)
        with open(profile_candidate) as f:
            cand = json.load(f)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"::notice::hotspot-term diff skipped (unreadable profile): {e}")
        return 0
    findings = compare_profiles(base, cand, threshold)
    can_block = not annotate_only
    blocking = 0
    for kind, term, old, new in findings:
        if kind == "ncalls":
            level = "error" if can_block else "warning"
            blocking += can_block
            print(f"::{level}::hotspot term {term}: call count changed "
                  f"{old} -> {new} (deterministic: behaviour changed)")
        else:
            print(f"::warning::hotspot term {term}: {old:.3f}s -> {new:.3f}s "
                  f"cumulative ({new / old:.2f}x; informational — profile "
                  f"times are load-sensitive)")
    if not findings:
        n = len(cand.get("terms", {}))
        print(f"hotspot terms: {n} named terms within {threshold:.2f}x of "
              f"baseline, call counts unchanged")
    return blocking


def compare_serving(baseline: dict, candidate: dict, model_threshold: float):
    """Diff two BENCH_serving.json payloads (see bench_serving.multitenant).

    Returns (regressions, warnings) as lists of human-readable findings.
    Modeled per-class TTFT/TPOT percentiles are deterministic functions of
    (schedule, backend policy), so they gate at the tight modeled
    threshold; wall time is load-noise and only ever warns.
    """
    base = {r["allocator"]: r for r in baseline.get("backends", [])}
    regressions, warnings = [], []
    for row in candidate.get("backends", []):
        name = row["allocator"]
        old = base.get(name)
        if old is None:
            warnings.append(f"serving/{name}: no baseline row (new backend?)")
            continue
        for cls in sorted(row.get("per_class", {})):
            new_c = row["per_class"][cls]
            old_c = (old.get("per_class") or {}).get(cls)
            if old_c is None:
                continue
            for metric in ("ttft_ms_p50", "ttft_ms_p95",
                           "tpot_ms_p50", "tpot_ms_p95"):
                ov, nv = old_c.get(metric), new_c.get(metric)
                if not ov or nv is None:
                    continue
                ratio = nv / ov
                if ratio > 1.0 + model_threshold:
                    regressions.append(
                        f"serving/{name}/{cls}/{metric}: "
                        f"{ov:.1f} -> {nv:.1f} modeled ms ({ratio:.3f}x)"
                    )
        for metric in ("model_cost", "peak_reserved", "deferrals"):
            ov, nv = old.get(metric), row.get(metric)
            if ov is None or nv is None:
                continue
            if ov == 0:
                # count metrics can regress from a clean zero baseline,
                # where a ratio is undefined: any appearance blocks
                if nv > 0:
                    regressions.append(
                        f"serving/{name}/{metric}: 0 -> {nv:.0f}"
                    )
                continue
            ratio = nv / ov
            if ratio > 1.0 + model_threshold:
                regressions.append(
                    f"serving/{name}/{metric}: {ov:.0f} -> {nv:.0f} "
                    f"({ratio:.3f}x)"
                )
        ow, nw = old.get("wall_seconds"), row.get("wall_seconds")
        if ow and nw and nw / ow > 1.5:
            warnings.append(
                f"serving/{name}: wall {ow:.2f}s -> {nw:.2f}s "
                f"({nw / ow:.2f}x; informational — wall is load-sensitive)"
            )
    return regressions, warnings


def compare_chaos(baseline: dict, candidate: dict):
    """Diff two BENCH_chaos.json payloads (see bench_chaos).

    Campaign verdicts are deterministic functions of the seeded scenario
    set, so the rules are absolute, not thresholded: a leg whose verdict
    was ok must stay ok, and sentinel-violation / unrecovered-fault
    counters that were zero must stay zero (per leg and in aggregate).
    New legs (new scenarios or backends) pass freely; a leg that
    disappears only warns (a renamed scenario is not a regression)."""
    regressions, warnings = [], []
    base_legs = baseline.get("legs", {}) or {}
    cand_legs = candidate.get("legs", {}) or {}
    for key, old in sorted(base_legs.items()):
        new = cand_legs.get(key)
        if new is None:
            warnings.append(f"chaos/{key}: leg disappeared (scenario set "
                            f"changed?)")
            continue
        if old.get("ok") and not new.get("ok"):
            regressions.append(
                f"chaos/{key}: verdict ok -> FAILED (liveness="
                f"{new.get('liveness')} safety={new.get('safety')} "
                f"quality={new.get('quality')})"
            )
        for metric in ("n_violations", "unrecovered"):
            ov = old.get(metric, 0) or 0
            nv = new.get(metric, 0) or 0
            if ov == 0 and nv > 0:
                regressions.append(f"chaos/{key}/{metric}: 0 -> {nv}")
    for metric in ("sentinel_violations", "unrecovered_faults"):
        ov = baseline.get(metric, 0) or 0
        nv = candidate.get(metric, 0) or 0
        if ov == 0 and nv > 0:
            regressions.append(f"chaos/{metric}: 0 -> {nv}")
    return regressions, warnings


def _chaos_tier(chaos_baseline, chaos_candidate, annotate_only) -> int:
    """Run the chaos campaign-verdict tier. Returns the number of
    blocking regressions (0 under --annotate-only or no usable baseline)."""
    try:
        with open(chaos_baseline) as f:
            base = json.load(f)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"::warning::chaos verdict diff skipped (no usable baseline): {e}")
        return 0
    try:
        with open(chaos_candidate) as f:
            cand = json.load(f)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"::error::chaos verdict candidate unreadable: {e}")
        return 1
    regressions, warns = compare_chaos(base, cand)
    for w in warns:
        print(f"::warning::{w}")
    level = "warning" if annotate_only else "error"
    for r in regressions:
        print(f"::{level}::chaos verdict regression {r}")
    if not regressions:
        print(f"chaos verdicts: {len(cand.get('legs', {}))} legs, no "
              f"ok->FAILED flips, no new sentinel violations or "
              f"unrecovered faults")
    return 0 if annotate_only else len(regressions)


def _serving_tier(serving_baseline, serving_candidate, model_threshold,
                  annotate_only) -> int:
    """Run the serving TTFT/TPOT tier. Returns the number of blocking
    regressions (0 under --annotate-only or with no usable baseline)."""
    try:
        with open(serving_baseline) as f:
            base = json.load(f)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"::warning::serving perf diff skipped (no usable baseline): {e}")
        return 0
    try:
        with open(serving_candidate) as f:
            cand = json.load(f)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"::error::serving perf candidate unreadable: {e}")
        return 1
    if base.get("load") != cand.get("load"):
        # different schedule shapes are incomparable, not a regression
        print("::warning::serving perf diff skipped (load config changed)")
        return 0
    regressions, warns = compare_serving(base, cand, model_threshold)
    for w in warns:
        print(f"::warning::{w}")
    level = "warning" if annotate_only else "error"
    for r in regressions:
        print(f"::{level}::serving modeled regression {r} "
              f"(threshold {1.0 + model_threshold:.2f}x)")
    if not regressions:
        print(f"serving perf: {len(cand.get('backends', []))} backends "
              f"within {model_threshold:.0%} of baseline on modeled "
              f"TTFT/TPOT, cost and peak")
    return 0 if annotate_only else len(regressions)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("baseline", help="previous run's BENCH_replay.json")
    ap.add_argument("candidate", help="this run's BENCH_replay.json")
    ap.add_argument(
        "--threshold", type=float, default=0.20,
        help="fractional us/event increase that counts as a wall regression",
    )
    ap.add_argument(
        "--model-threshold", type=float, default=0.02,
        help="fractional modeled-cost increase that counts as a policy "
        "regression (load-independent, so the default is tight)",
    )
    ap.add_argument(
        "--annotate-only", action="store_true",
        help="emit annotations but always exit 0 (for noisy runners)",
    )
    ap.add_argument(
        "--profile-baseline", default=None,
        help="previous run's BENCH_profile.json (hotspot terms; optional)",
    )
    ap.add_argument(
        "--profile-candidate", default=None,
        help="this run's BENCH_profile.json (hotspot terms; optional)",
    )
    ap.add_argument(
        "--profile-threshold", type=float, default=1.5,
        help="cumtime ratio that warn-annotates a named hotspot term "
        "(times never block; call-count drift in the same tier does)",
    )
    ap.add_argument(
        "--serving-baseline", default=None,
        help="previous run's BENCH_serving.json (modeled TTFT/TPOT tier)",
    )
    ap.add_argument(
        "--serving-candidate", default=None,
        help="this run's BENCH_serving.json (modeled TTFT/TPOT tier)",
    )
    ap.add_argument(
        "--chaos-baseline", default=None,
        help="previous run's BENCH_chaos.json (campaign-verdict tier)",
    )
    ap.add_argument(
        "--chaos-candidate", default=None,
        help="this run's BENCH_chaos.json (campaign-verdict tier)",
    )
    args = ap.parse_args(argv)

    profile_regressions = 0
    if args.profile_baseline and args.profile_candidate:
        profile_regressions = _profile_tier(
            args.profile_baseline, args.profile_candidate,
            args.profile_threshold, args.annotate_only,
        )

    serving_regressions = 0
    if args.serving_baseline and args.serving_candidate:
        serving_regressions = _serving_tier(
            args.serving_baseline, args.serving_candidate,
            args.model_threshold, args.annotate_only,
        )

    chaos_regressions = 0
    if args.chaos_baseline and args.chaos_candidate:
        chaos_regressions = _chaos_tier(
            args.chaos_baseline, args.chaos_candidate, args.annotate_only,
        )

    try:  # a missing/unreadable *baseline* must never block the build
        with open(args.baseline) as f:
            baseline = json.load(f)
        _rows(baseline)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"::warning::replay perf diff skipped (no usable baseline): {e}")
        return 1 if (serving_regressions or profile_regressions
                     or chaos_regressions) else 0
    try:  # an unreadable *candidate* is a real defect in this very run
        with open(args.candidate) as f:
            candidate = json.load(f)
        regressions, improvements, missing = compare(
            baseline, candidate, args.threshold, args.model_threshold
        )
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"::error::replay perf candidate unreadable: {e}")
        return 1

    for name, (sig, old, new, ratio) in sorted(improvements.items()):
        print(f"::notice::replay perf {name}: {old:.2f} -> {new:.2f} "
              f"{_UNITS[sig]} ({ratio:.2f}x, improvement)")
    for name in missing:
        print(f"::warning::replay perf {name}: present in baseline, missing now")
    for name, (sig, old, new, ratio) in sorted(regressions.items()):
        level = "warning" if args.annotate_only else "error"
        if sig == "hybrid":
            print(f"::{level}::replay hybrid routing drift {name}: "
                  f"{old} -> {new} (deterministic planned/spilled split "
                  f"changed: the plan covers different requests)")
            continue
        what = "policy (modeled-cost)" if sig == "model" else "wall-time"
        thresh = args.model_threshold if sig == "model" else args.threshold
        print(f"::{level}::replay {what} regression {name}: "
              f"{old:.2f} -> {new:.2f} {_UNITS[sig]} ({ratio:.2f}x, "
              f"threshold {1.0 + thresh:.2f}x)")
    if not regressions:
        print(f"replay perf: {len(candidate.get('rows', []))} rows within "
              f"thresholds (model {args.model_threshold:.0%}, "
              f"wall {args.threshold:.0%}) of baseline")
    blocking = (
        (regressions and not args.annotate_only)
        or serving_regressions
        or profile_regressions
        or chaos_regressions
    )
    return 1 if blocking else 0


if __name__ == "__main__":
    sys.exit(main())
