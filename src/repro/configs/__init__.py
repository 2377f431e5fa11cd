"""Architecture registry: ``--arch <id>`` resolves here.

Each entry carries the exact assigned FULL config, a reduced SMOKE config of
the same family, and per-arch distribution tuning (ZeRO sharding of
parameters/optimizer over the data axis, sequence parallelism, gradient
accumulation, optimizer dtype) used by the launcher and the dry run.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict

from . import (
    dbrx_132b,
    deepseek_v2_lite,
    grok1_314b,
    h2o_danube3_4b,
    internlm2_20b,
    paligemma_3b,
    rwkv6_7b,
    smollm_135m,
    starcoder2_15b,
    whisper_medium,
    zamba2_1p2b,
)
from .shapes import SHAPES, ShapeSpec, supports_long_context


@dataclass(frozen=True)
class ArchEntry:
    arch_id: str
    full: Any
    smoke: Any
    #: ZeRO-1: shard optimizer state over 'data' (in addition to TP axes)
    zero: bool = False
    #: ZeRO-3: ALSO shard parameters over 'data' (per-layer gathers); only
    #: needed when TP-sharded params exceed per-chip HBM (dbrx, grok)
    zero_params: bool = False
    #: Megatron-style sequence parallelism for the residual stream
    seq_parallel: bool = True
    #: gradient-accumulation microbatches for train_4k
    microbatches: int = 1
    #: adam moment dtype ("float32" | "bfloat16")
    opt_dtype: str = "float32"
    #: pure data-parallel mapping (batch over every axis, no TP) — for
    #: models too small / head-indivisible for the 16-way model axis
    pure_dp: bool = False


ARCHS: Dict[str, ArchEntry] = {
    e.arch_id: e
    for e in [
        ArchEntry("starcoder2-15b", starcoder2_15b.FULL, starcoder2_15b.SMOKE,
                  zero=True, microbatches=2),
        ArchEntry("h2o-danube-3-4b", h2o_danube3_4b.FULL, h2o_danube3_4b.SMOKE,
                  zero=True),
        ArchEntry("internlm2-20b", internlm2_20b.FULL, internlm2_20b.SMOKE,
                  zero=True, microbatches=2),
        ArchEntry("smollm-135m", smollm_135m.FULL, smollm_135m.SMOKE,
                  zero=False, seq_parallel=False, pure_dp=True),
        ArchEntry("zamba2-1.2b", zamba2_1p2b.FULL, zamba2_1p2b.SMOKE, zero=True),
        ArchEntry("paligemma-3b", paligemma_3b.FULL, paligemma_3b.SMOKE, zero=True),
        ArchEntry("rwkv6-7b", rwkv6_7b.FULL, rwkv6_7b.SMOKE, zero=True),
        ArchEntry("dbrx-132b", dbrx_132b.FULL, dbrx_132b.SMOKE,
                  zero=True, zero_params=True, microbatches=4,
                  opt_dtype="bfloat16"),
        ArchEntry("grok-1-314b", grok1_314b.FULL, grok1_314b.SMOKE,
                  zero=True, zero_params=True, microbatches=4,
                  opt_dtype="bfloat16"),
        ArchEntry("whisper-medium", whisper_medium.FULL, whisper_medium.SMOKE,
                  zero=False),
        ArchEntry("deepseek-v2-lite", deepseek_v2_lite.FULL, deepseek_v2_lite.SMOKE,
                  zero=True),
    ]
}


def get_arch(arch_id: str) -> ArchEntry:
    if arch_id not in ARCHS:
        raise KeyError(f"unknown arch {arch_id!r}; known: {sorted(ARCHS)}")
    return ARCHS[arch_id]


def cells():
    """All (arch x shape) dry-run cells, with SKIP reasons where applicable."""
    from ..models import whisper
    out = []
    for aid, entry in ARCHS.items():
        for sname, shape in SHAPES.items():
            skip = None
            if sname == "long_500k" and not supports_long_context(entry.full):
                skip = "pure full attention (quadratic) — assignment says skip"
            out.append((aid, sname, skip))
    return out


__all__ = ["ARCHS", "ArchEntry", "SHAPES", "ShapeSpec", "get_arch", "cells"]
