"""Model operations of the window's output tokens over the window's
seconds times the chip's bf16 peak, in %, for the latent-attention MoE
family: each token's absorbed-path operations at its true length, with the
routed experts at the share held here (``bench.cost_mla.token_flops``)."""

from bench import cost_mla


def read(rec):
    if rec["kind"] != "serve" or not rec["out_token_ctx"]:
        return None
    flops = sum(cost_mla.token_flops(rec["cfg"], n) for n in rec["out_token_ctx"])
    return 100.0 * flops / (rec["window_s"] * rec["peaks"].bf16_flops)
