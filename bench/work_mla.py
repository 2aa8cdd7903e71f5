"""Work counts of one decode tick of a DeepSeek-V2 model on the ClusterKV
service (latent attention over plan-ordered latent rows, a held share of
the routed experts), from the configuration and the service's counters.

The work counted is the tick's own, the least a tick could move and
compute: every weight outside the routed experts read once per tick (the
embedding only for the rows the tick's tokens look up), each held expert
that the tick's active tokens reached read once, each live latent row in
the selected tiles read once. Flops: two per active parameter per active
token (a token's active parameters are those outside the routed experts
plus its experts held here), plus the absorbed attention's
``2 * heads * (R + kv_lora_rank)`` per attended row and head group.
"""
from __future__ import annotations

BF16 = 2


def _mla_params(cfg: dict) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    kr, dn, dr, dv = (cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
                      cfg["qk_rope_head_dim"], cfg["v_head_dim"])
    return (d * h * (dn + dr) + d * (kr + dr) + kr * h * (dn + dv)
            + h * dv * d)


def expert_params(cfg: dict) -> int:
    """One routed expert: gate, up and down projections."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def token_params(cfg: dict) -> int:
    """Matrix parameters every token multiplies by, outside the routed
    experts: attention, the dense layers' MLP, router and shared experts,
    the head."""
    d = cfg["hidden_size"]
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    shared = 3 * d * cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
    router = d * cfg["reduced"]["n_routed_experts"]
    return (cfg["num_hidden_layers"] * _mla_params(cfg)
            + n_dense * 3 * d * cfg["intermediate_size"]
            + n_moe * (shared + router) + d * cfg["vocab_size"])


def latent_row_bytes(cfg: dict) -> int:
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * BF16


def decode_work(cfg: dict, counts: dict) -> tuple:
    """(flops, bytes) of the ticks the counters count: ``decode_ticks``,
    ``moe_experts_read``, ``decode_rows_attended``, ``moe_pairs_local``,
    ``moe_pairs_total``."""
    ticks = counts["decode_ticks"]
    n_moe = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    tokens = counts["moe_pairs_total"] / (cfg["num_experts_per_tok"] * n_moe)
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rdim = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    rows = counts["decode_rows_attended"]
    nbytes = (ticks * token_params(cfg) * BF16
              + tokens * d * BF16
              + counts["moe_experts_read"] * expert_params(cfg) * BF16
              + rows * latent_row_bytes(cfg))
    flops = (2 * token_params(cfg) * tokens
             + 2 * expert_params(cfg) * counts["moe_pairs_local"]
             + 2 * h * (rdim + cfg["kv_lora_rank"]) * rows)
    return flops, nbytes
