"""Work of the ``moonlight`` UDF forward, counted from the model's shapes
and the forward's own counts (``classifier.unpack_stats``: token slots,
valid tokens, causal pairs among valid tokens, assignments per held
expert).

Three terms, each the model's work and never one implementation's
layout (the attention's masked half, a held expert's padded block rows
and the lanes a kernel pads are left out):

* per token: the MLA projections of every layer, the dense MLP of the
  leading dense layers, and per MoE layer the router and the shared
  experts;
* per causal (query, key) pair and layer: the scores over the
  ``qk_nope + qk_rope`` dims and the values over ``v_head_dim``, every
  head;
* per (token, held expert) assignment: that expert's gated MLP.

``padded`` counts every token slot the UDF computed (bucket rows and
right padding included: a record slot is ``S(S+1)/2`` pairs); ``valid``
counts only the records' own tokens.  The head (``2·D·C`` a record) and
the embedding gather are left out.
"""
from __future__ import annotations

from typing import Dict


def token_flops(cfg: dict) -> float:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rd, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                    cfg["v_head_dim"])
    rank = cfg["kv_lora_rank"]
    attn = d * h * (nope + rd) + d * (rank + rd) + rank * h * (nope + vd) \
        + h * vd * d
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    dense = 3 * d * cfg["intermediate_size"]
    moe = d * cfg["published"]["n_routed_experts"] \
        + 3 * d * cfg["n_shared_experts"] * cfg["moe_intermediate_size"]
    return 2.0 * (cfg["num_hidden_layers"] * attn + n_dense * dense
                  + n_moe * moe)


def pair_flops(cfg: dict) -> float:
    h = cfg["num_attention_heads"]
    per_layer = 2 * h * (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) \
        + 2 * h * cfg["v_head_dim"]
    return float(cfg["num_hidden_layers"] * per_layer)


def expert_flops(cfg: dict) -> float:
    return 2.0 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def flops(cfg: dict, tokens: float, pairs: float, assignments: float) -> float:
    return (tokens * token_flops(cfg) + pairs * pair_flops(cfg)
            + assignments * expert_flops(cfg))


def weight_bytes(cfg: dict) -> float:
    """bf16 bytes of one UDF's weights as served (the chip's share: the
    vocabulary slice, the held experts, the shared experts), read once a
    call."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rd, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                    cfg["v_head_dim"])
    rank = cfg["kv_lora_rank"]
    attn = d * h * (nope + rd) + d * (rank + rd) + rank * h * (nope + vd) \
        + h * vd * d
    n_dense = cfg["first_k_dense_replace"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    experts = 3 * d * cfg["moe_intermediate_size"] * (
        cfg["n_routed_experts"] + cfg["n_shared_experts"])
    params = cfg["vocab_size"] * d + cfg["num_hidden_layers"] * attn \
        + n_dense * 3 * d * cfg["intermediate_size"] \
        + n_moe * (d * cfg["published"]["n_routed_experts"] + experts)
    return 2.0 * params


def call_work(cfg: dict, stats: Dict[str, object], record_slots: int,
              padded: bool):
    """(FLOPs, HBM bytes) of one UDF call from its counts: the padded
    work the forward computed, or the valid work its records needed."""
    s = cfg["record_tokens"]
    if padded:
        tokens = float(stats["token_slots"])
        pairs = record_slots * s * (s + 1) / 2.0
        assignments = float(stats["expert_tokens"].sum())
    else:
        tokens = float(stats["valid_tokens"])
        pairs = float(stats["valid_pairs"])
        assignments = float(stats["expert_valid_tokens"].sum())
    width = cfg["sketch_buckets"] + s
    nbytes = weight_bytes(cfg) + 4.0 * record_slots * width \
        + 4.0 * record_slots * cfg["n_classes"]
    return flops(cfg, tokens, pairs, assignments), nbytes
