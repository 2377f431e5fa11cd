"""Least time of the window's decode steps over their device time, in %,
for the latent-attention MoE family.

The least time of a step is the larger of its bytes over the HBM bandwidth
and its operations over the bf16 peak (``bench.cost_mla.decode_step``: the
held weights once, the latent rows at each live sequence's true length);
the device time is what the trace puts inside the model's decode spans."""

from bench import cost, cost_mla, spans as sp


def read(rec):
    tr = rec.get("trace")
    if tr is None or not tr.span_device_s.get(sp.DECODE):
        return None
    least = sum(cost.least_time(*cost_mla.decode_step(rec["cfg"], lens), rec["peaks"])[0]
                for lens in rec["decode_lengths"] if lens)
    return 100.0 * least / tr.span_device_s[sp.DECODE]
