"""Readings that set a cell's correctness limit, for any serving cell: the
program's and the control's widest logit gap, seed by seed, in one process.

    python3 bench/calibrate_cell.py --workload <cell> --seeds 11,12,13 --seconds 30

As ``calibrate.py``, but through the driver that the cell's traffic mix
names (``bench/drivers/<driver>.py``), so a cell with a driver of its own
is read the same way. One JSON line per seed on stdout, with the run's
``out_tokens_per_s`` and ``memory_peak_bytes`` beside the readings.
``--program-only`` skips the control, which costs a second reference pass
over the sample, on those seeds.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import run as harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, type=lambda s: [int(x) for x in s.split(",")])
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--program-only", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from bench.peaks import peaks_for

    harness.configure_cache(jax)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit("no TPU found")
    captured = {}
    for seed in args.seeds:
        ctx = harness.context_for(
            args.workload, seed, args.seconds, False, peaks_for(dev.device_kind),
            lambda: dev.memory_stats()["peak_bytes_in_use"])
        driver = importlib.import_module(f"bench.drivers.{ctx.traffic['driver']}")
        reading = driver.max_gap

        def both(ref, cfg, params, sample, lowp=False, _reading=reading):
            t = time.perf_counter()
            captured["program"] = _reading(ref, cfg, params, sample)
            if not args.program_only:
                captured["control"] = _reading(ref, cfg, params, sample, lowp=True)
            captured["check_s"] = time.perf_counter() - t
            return captured["program"]

        driver.max_gap = both
        try:
            out = driver.run(ctx)
        finally:
            driver.max_gap = reading
        print(json.dumps({
            "workload": args.workload, "seed": seed, **captured,
            "sampled_tokens": out["checks"]["sampled_tokens"],
            "arena_mismatch_bytes": out["checks"]["arena_mismatch_bytes"],
            "bad_requests": out["checks"]["bad_requests"],
            "out_tokens_per_s": out["end_to_end"]["out_tokens_per_s"],
            "memory_peak_bytes": out["memory_peak_bytes"],
            "device": dev.device_kind,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
