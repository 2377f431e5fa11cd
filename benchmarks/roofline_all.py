"""Assignment §Roofline: aggregate the dry-run artifacts into the roofline
table (all 40 cells x meshes) and emit EXPERIMENTS.md-ready markdown."""

from __future__ import annotations

import json
from pathlib import Path
from typing import List, Optional

from repro.utils.roofline import DRYRUN_DEVICE_KIND, peaks_for

from .common import Row, emit

ART = Path(__file__).resolve().parent.parent / "artifacts" / "dryrun"


def load_records(mesh: str) -> List[dict]:
    out = []
    for p in sorted((ART / mesh).glob("*.json")):
        out.append(json.loads(p.read_text()))
    return out


def markdown_table(mesh: str = "pod16x16") -> str:
    recs = load_records(mesh)
    pk = peaks_for(DRYRUN_DEVICE_KIND)
    lines = [
        f"### Roofline — {mesh} ({DRYRUN_DEVICE_KIND}: {pk.flops/1e12:.0f} TF/s, "
        f"{pk.hbm_bw/1e9:.0f} GB/s HBM, {pk.ici_bw/1e9:.0f} GB/s ICI)",
        "",
        "| arch | shape | kind | t_compute (s) | t_memory (s) | t_collective (s) "
        "| bottleneck | MODEL/HLO flops | roofline frac | mem/dev (GB) |",
        "|---|---|---|---|---|---|---|---|---|",
    ]
    for r in recs:
        if r["status"] == "skip":
            lines.append(
                f"| {r['arch']} | {r['shape']} | {r['kind']} | — | — | — | "
                f"SKIP | — | — | — |"
            )
            continue
        if r["status"] != "ok":
            lines.append(f"| {r['arch']} | {r['shape']} | {r['kind']} | FAIL |")
            continue
        rf = r["roofline"]
        mem = r.get("peak_memory_per_device")
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['kind']} "
            f"| {rf['t_compute']:.3f} | {rf['t_memory']:.3f} "
            f"| {rf['t_collective']:.3f} | {rf['bottleneck']} "
            f"| {rf['useful_flops_fraction']:.3f} | {rf['roofline_fraction']:.3f} "
            f"| {mem / 1e9:.2f} |" if mem else
            f"| {r['arch']} | {r['shape']} | {r['kind']} "
            f"| {rf['t_compute']:.3f} | {rf['t_memory']:.3f} "
            f"| {rf['t_collective']:.3f} | {rf['bottleneck']} "
            f"| {rf['useful_flops_fraction']:.3f} | {rf['roofline_fraction']:.3f} "
            f"| n/a |"
        )
    return "\n".join(lines)


def run(fast: bool = False) -> None:
    rows = []
    for mesh in ("pod16x16", "pod2x16x16"):
        if not (ART / mesh).exists():
            continue
        for r in load_records(mesh):
            if r["status"] != "ok":
                continue
            rf = r["roofline"]
            rows.append(Row(
                f"roofline/{mesh}/{r['arch']}/{r['shape']}",
                r.get("compile_s", 0) * 1e6,
                rf["roofline_fraction"],
                extra=f"bottleneck={rf['bottleneck']};"
                      f"tc={rf['t_compute']:.3f};tm={rf['t_memory']:.3f};"
                      f"tx={rf['t_collective']:.3f}",
            ))
    emit(rows, "Roofline terms per (arch x shape x mesh) from the dry run")
