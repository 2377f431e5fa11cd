"""Paper Fig. 4 + Fig. 11: fragmentation vs GPU scale-out (1 -> 16).

LR strategy, DeepSpeed-style ZeRO-3 traces; utilization-vs-world-size for
caching vs GMLake, plus the throughput proxy (paper: GMLake keeps caching-
level throughput — its cost is amortized by S1 convergence).
"""

from __future__ import annotations

from repro.core import GB, PAPER_MODELS, run_workload, training_trace

from .common import A100_EFFECTIVE_FLOPS, CUMALLOC_SECONDS, Row, emit, timed

MODELS = ("opt-13b", "vicuna-13b", "gpt-neox-20b")
WORLDS = (1, 2, 4, 8, 16)


def throughput_proxy(model, batch, seq, iters, alloc_cost) -> float:
    """samples/s: compute time (A100 model) + allocator time."""
    tokens = batch * seq
    flops = 6.0 * model.param_bytes // 2 * tokens  # params ~= bytes/2 (bf16)
    step = flops / A100_EFFECTIVE_FLOPS + (alloc_cost / iters) * CUMALLOC_SECONDS
    return batch / step


def run(fast: bool = False) -> None:
    rows = []
    models = MODELS[:1] if fast else MODELS
    worlds = WORLDS[:3] if fast else WORLDS
    for mname in models:
        m = PAPER_MODELS[mname]
        for world in worlds:
            batch = 8
            tr = training_trace(m, strategies="LR", world=world, batch=batch,
                                seq=2048, iters=4 if fast else 8)
            for alloc in ("caching", "gmlake"):
                res, us = timed(run_workload, tr, alloc, capacity_bytes=80 * GB)
                thr = throughput_proxy(m, batch, 2048, 8, res.model_cost)
                rows.append(Row(
                    f"fig11/{mname}/gpus{world}/{alloc}", us, res.utilization,
                    extra=f"reserved_gb={res.reserved_gb:.1f};"
                          f"throughput={thr:.2f}sps;oom={int(res.oom)}",
                ))
    emit(rows, "Fig 11: utilization + throughput vs GPU count (LR)")
