"""Operations and bytes of a DeepSeek-V2 (MLA + MoE) decode, counted from
shapes, for the configurations whose ``family`` is ``mla_moe``.

The counts are of the work itself on the absorbed path, one token per
sequence: every weight held here read once per step (the held experts
whole; of the embedding only the rows looked up), each sequence's latent
rows (``kv_lora_rank + qk_rope_head_dim`` elements per layer) read at its
true length and the new row written, and the matmul and attention
operations with the routed experts at their expected share: each token
picks ``num_experts_per_tok`` of the router's ``n_routed_experts_published``,
so ``top_k * held / published`` of its assignments land here (6 x 8 / 64 =
0.75 experts a token in ``dsv2lite``). ``cfg`` is a configuration file's
dict (Hugging Face key names).
"""

from __future__ import annotations

from typing import Iterable, Tuple

from .cost import itemsize


def _attn_params(cfg) -> int:
    d, h, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return d * h * (nope + rope) + d * (r + rope) + r * h * (nope + vd) + h * vd * d


def _mlp_params(cfg, width: int) -> int:
    return 3 * cfg["hidden_size"] * width


def _shared_width(cfg) -> int:
    return cfg["n_shared_experts"] * cfg["moe_intermediate_size"]


def layer_counts(cfg) -> Tuple[int, int]:
    """(dense layers, MoE layers)."""
    k = cfg["first_k_dense_replace"]
    return k, cfg["num_hidden_layers"] - k


def held_params(cfg) -> int:
    """Every parameter held here, the held experts only."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    n_dense, n_moe = layer_counts(cfg)
    attn = _attn_params(cfg) + cfg["kv_lora_rank"] + 2 * d  # + kv and layer norms
    dense = attn + _mlp_params(cfg, cfg["intermediate_size"])
    moe = (attn + d * cfg["n_routed_experts_published"] + _mlp_params(cfg, _shared_width(cfg))
           + cfg["n_routed_experts"] * _mlp_params(cfg, cfg["moe_intermediate_size"]))
    head = 0 if cfg["tie_word_embeddings"] else d * v
    return n_dense * dense + n_moe * moe + v * d + head + d


def latent_row_bytes(cfg) -> int:
    """One token's cached row in one layer."""
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * itemsize(cfg)


def latent_token_bytes(cfg) -> int:
    """One token's rows over every layer."""
    return cfg["num_hidden_layers"] * latent_row_bytes(cfg)


def experts_per_token(cfg) -> float:
    """Routed experts a token is expected to run here."""
    return (cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / cfg["n_routed_experts_published"])


def token_flops(cfg, ctx: int) -> float:
    """Operations of one token that sees ``ctx`` positions (itself
    included), on the absorbed path, the held share of the routed experts."""
    d, h, r = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, vd = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    n_dense, n_moe = layer_counts(cfg)
    attn = 2 * (d * h * (nope + rope) + d * (r + rope)  # q, latent and rotary key
                + h * nope * r + h * r * vd + h * vd * d)  # W_uk in q, W_uv, out
    attn += 2 * h * (r + rope) * ctx + 2 * h * r * ctx  # scores, weighted latent sum
    dense = 2 * _mlp_params(cfg, cfg["intermediate_size"])
    moe = 2 * (d * cfg["n_routed_experts_published"] + _mlp_params(cfg, _shared_width(cfg))
               + experts_per_token(cfg) * _mlp_params(cfg, cfg["moe_intermediate_size"]))
    return (cfg["num_hidden_layers"] * attn + n_dense * dense + n_moe * moe
            + 2 * d * cfg["vocab_size"])


def decode_step(cfg, lengths: Iterable[int]) -> Tuple[float, int]:
    """(flops, bytes) of one decode step; ``lengths`` are the positions each
    sequence attends to, the new token included."""
    lengths = list(lengths)
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    flops = sum(token_flops(cfg, n) for n in lengths)
    weights = held_params(cfg) - v * d + len(lengths) * d  # embedding: rows looked up
    nbytes = weights * itemsize(cfg)
    nbytes += sum(lengths) * latent_token_bytes(cfg)  # read, the new row included
    nbytes += len(lengths) * latent_token_bytes(cfg)  # the new row written
    return flops, nbytes
