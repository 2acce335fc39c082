"""Operations and bytes a latent-attention expert-share decoder needs
(sarvam-105b), from shapes and from what the expert layers counted. Kept
with the benchmark so that no PR claiming a gain can move them.

`m` is the model section `replica_sarvam.model_section` builds: the
published keys (hidden_size, num_hidden_layers, num_attention_heads,
qk_nope_head_dim, qk_rope_head_dim, v_head_dim, kv_lora_rank,
intermediate_size (the dense layers'), moe_intermediate_size (one
expert's), first_k_dense_replace, num_shared_experts,
num_experts_per_tok, vocab_size) with `num_experts` the experts HELD and
`router_width` the experts routed over. The whole published model is
`dict(m, num_hidden_layers=32, num_experts=128, vocab_size=262144)`.
"""
from __future__ import annotations

from . import costs, costs_moe


def attention_params(m: dict) -> int:
    """W_q, W_dkv, W_ukv and W_o of one layer."""
    h, nh = m["hidden_size"], m["num_attention_heads"]
    dn, dr, dv, r = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                     m["v_head_dim"], m["kv_lora_rank"])
    return (h * nh * (dn + dr) + h * (r + dr) + r * nh * (dn + dv)
            + nh * dv * h)


def expert_params(m: dict) -> int:
    """gate, up and down of ONE expert (routed or shared)."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def dense_mlp_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def router_params(m: dict) -> int:
    return m["hidden_size"] * m["router_width"]


def expert_layers(m: dict) -> int:
    return m["num_hidden_layers"] - m["first_k_dense_replace"]


def always_read_params(m: dict) -> int:
    """Matmul parameters every decode step reads whatever the routing:
    attention of every layer, the dense layers' MLPs, router and shared
    experts of the expert layers, the head."""
    return (m["num_hidden_layers"] * attention_params(m)
            + m["first_k_dense_replace"] * dense_mlp_params(m)
            + expert_layers(m) * (router_params(m) + m["num_shared_experts"]
                                  * expert_params(m))
            + m["hidden_size"] * m["vocab_size"])


def total_params(m: dict) -> int:
    """Every parameter held: `always_read_params`, the experts held, the
    embedding, norm weights and selection biases."""
    h = m["hidden_size"]
    norms = (m["num_hidden_layers"] * (2 * h + m["kv_lora_rank"]
             + m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) + h)
    return (always_read_params(m)
            + expert_layers(m) * (m["num_experts"] * expert_params(m)
                                  + m["router_width"])
            + h * m["vocab_size"] + norms)


def latent_bytes_per_token(m: dict, dtype_bytes: int = 2) -> int:
    """What the mathematics caches a token: latent + rope key in every
    layer (a pool that pads its rows reads more and shows it as lost
    roofline)."""
    return (m["num_hidden_layers"]
            * (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * dtype_bytes)


def latent_attention(m: dict, tokens: float, dtype_bytes: int = 2) -> dict:
    """Absorbed-form decode attention of steps whose rows together hold
    `tokens` cached tokens: every layer reads each token's latent once;
    every head multiplies its query with the latent and rope key
    (kv_lora_rank + rope) and the probabilities with the latent
    (kv_lora_rank). Queries and results are left out, so the share errs
    low by them."""
    r, dr = m["kv_lora_rank"], m["qk_rope_head_dim"]
    flops = (2.0 * m["num_hidden_layers"] * m["num_attention_heads"]
             * (2 * r + dr) * tokens)
    return {"flops": flops,
            "bytes": tokens * latent_bytes_per_token(m, dtype_bytes)}


def expert_matmuls(m: dict, assignments: float, touched: float,
                   weight_bytes: int = 2, act_bytes: int = 2) -> dict:
    """`costs_moe.expert_matmuls` at one expert's own width: the three
    grouped matmuls of expert layers that together ran `assignments`
    (row, expert) pairs on experts held here and touched `touched` of
    them."""
    return costs_moe.expert_matmuls(
        dict(m, intermediate_size=m["moe_intermediate_size"]), assignments,
        touched, weight_bytes, act_bytes)


def decode_step(m: dict, contexts: list, touched: float,
                assignments: float, weight_bytes: int = 2) -> dict:
    """One decode step over sequences with the given context lengths:
    the weights every step reads once, the experts the step touched and
    the pairs it ran on them (summed over the expert layers, as
    counted), every live sequence's latents."""
    rows = len(contexts)
    experts = expert_matmuls(m, assignments, touched, weight_bytes)
    attn = latent_attention(m, float(sum(contexts)))
    dense = always_read_params(m)
    return {"flops": 2.0 * dense * rows + experts["flops"] + attn["flops"],
            "bytes": dense * weight_bytes + experts["bytes"] + attn["bytes"]}


least_seconds = costs.least_seconds
