"""Operations and bytes a Xing4.0 decoder needs (residual streams mixed
by hyper-connections, latent attention with a low-rank query, routed
experts all held here beside a shared one), from shapes and from what
the layers counted. Kept with the benchmark so that no PR claiming a
gain can move them.

`m` is the model section `replica_xing.model_section` builds: the
published keys (hidden_size, num_hidden_layers, num_attention_heads,
q_lora_rank, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
v_head_dim, intermediate_size (the dense layers'), moe_intermediate_size
(one expert's), first_k_dense_replace, n_routed_experts,
n_shared_experts, num_experts_per_tok, hc_mult, vocab_size). The whole
published model is `dict(m, num_hidden_layers=40,
first_k_dense_replace=2)`; its multi-token-prediction module is not
counted (it is not served).
"""
from __future__ import annotations

from . import costs

LANES = 128


def attention_params(m: dict) -> int:
    """W_qa, W_qb, W_dkv, W_ukv and W_o of one layer."""
    h, nh, ql = (m["hidden_size"], m["num_attention_heads"],
                 m["q_lora_rank"])
    dn, dr, dv, r = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                     m["v_head_dim"], m["kv_lora_rank"])
    return (h * ql + ql * nh * (dn + dr) + h * (r + dr)
            + r * nh * (dn + dv) + nh * dv * h)


def n_maps(m: dict) -> int:
    """Values of one sub-layer's three mappings: n + n + n * n."""
    n = m["hc_mult"]
    return n * n + 2 * n


def mapping_params(m: dict) -> int:
    """phi of ONE sub-layer: (n^2 + 2n) x n * hidden."""
    return n_maps(m) * m["hc_mult"] * m["hidden_size"]


def expert_params(m: dict) -> int:
    """gate, up and down of ONE expert (routed or shared)."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def dense_mlp_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def router_params(m: dict) -> int:
    return m["hidden_size"] * m["n_routed_experts"]


def expert_layers(m: dict) -> int:
    return m["num_hidden_layers"] - m["first_k_dense_replace"]


def sub_layers(m: dict) -> int:
    return 2 * m["num_hidden_layers"]


def always_read_params(m: dict) -> int:
    """Matmul parameters every decode step reads whatever the routing:
    attention and both mappings of every layer, the dense layers' MLPs,
    router and shared experts of the expert layers, the head."""
    return (m["num_hidden_layers"] * (attention_params(m)
                                      + 2 * mapping_params(m))
            + m["first_k_dense_replace"] * dense_mlp_params(m)
            + expert_layers(m) * (router_params(m) + m["n_shared_experts"]
                                  * expert_params(m))
            + m["hidden_size"] * m["vocab_size"])


def total_params(m: dict) -> int:
    """Every parameter held: `always_read_params`, the routed experts,
    the embedding, norm weights (two a block, the latent's, the
    low-rank query's, the final one), selection biases, and each
    mapping's b and three scalars."""
    h = m["hidden_size"]
    norms = (m["num_hidden_layers"] * (2 * h + m["kv_lora_rank"]
                                       + m["q_lora_rank"]) + h)
    return (always_read_params(m)
            + expert_layers(m) * (m["n_routed_experts"] * expert_params(m)
                                  + m["n_routed_experts"])
            + sub_layers(m) * (n_maps(m) + 3)
            + h * m["vocab_size"] + norms)


def pool_row_bytes(m: dict, dtype_bytes: int = 2) -> int:
    """One token's latent in one layer AS THE POOL HOLDS IT: latent +
    rope key padded to whole 128-lane tiles (576 -> 640), which is what
    the kernel's DMAs move."""
    width = m["kv_lora_rank"] + m["qk_rope_head_dim"]
    return -(-width // LANES) * LANES * dtype_bytes


def latent_attention(m: dict, tokens: float, dtype_bytes: int = 2) -> dict:
    """Absorbed-form decode attention of steps whose rows together hold
    `tokens` cached tokens: every layer reads each token's pool row
    once; every head multiplies its query with the latent and rope key
    (kv_lora_rank + rope) and the probabilities with the latent
    (kv_lora_rank). Queries and results are left out, so the share errs
    low by them."""
    r, dr = m["kv_lora_rank"], m["qk_rope_head_dim"]
    flops = (2.0 * m["num_hidden_layers"] * m["num_attention_heads"]
             * (2 * r + dr) * tokens)
    return {"flops": flops, "bytes": tokens * m["num_hidden_layers"]
            * pool_row_bytes(m, dtype_bytes)}


def hc_kernels(m: dict, rows: float, calls: float, act_bytes: int = 2,
               phi_bytes: int = 2) -> dict:
    """`hc_mix_in` and `hc_mix_out` over `rows` (row, sub-layer) pairs in
    `calls` sub-layer calls: mix_in reads the row's n streams and writes
    h and the packed mapping (n^2 + 2n + 2 float32); mix_out reads the
    streams, y and the mapping and writes the streams; phi (in its
    stored dtype) is read once a call. The 24-wide product is the only
    matrix work."""
    n, h = m["hc_mult"], m["hidden_size"]
    packed = (n_maps(m) + 2) * 4
    per_row = (3 * n * h + 2 * h) * act_bytes + 2 * packed
    return {"flops": 2.0 * rows * mapping_params(m)
            + rows * n * h * (2 + 2 * n + 2),
            "bytes": rows * per_row + calls * mapping_params(m) * phi_bytes}


def expert_matmuls(m: dict, assignments: float, touched: float,
                   weight_bytes: int = 2, act_bytes: int = 2) -> dict:
    """The three grouped matmuls of expert layers that together ran
    `assignments` (row, expert) pairs and touched `touched` experts:
    every touched expert's weights read once, every assignment's row
    read once and its result written once."""
    return {"flops": 2.0 * assignments * expert_params(m),
            "bytes": touched * expert_params(m) * weight_bytes
            + 2.0 * assignments * m["hidden_size"] * act_bytes}


def decode_step(m: dict, contexts: list, touched: float,
                assignments: float, weight_bytes: int = 2) -> dict:
    """One decode step over sequences with the given context lengths:
    the weights every step reads once (phi among them), the experts the
    step touched and the pairs it ran on them (summed over the expert
    layers, as counted), every live sequence's pool rows, and the
    residual streams through both kernels of every sub-layer."""
    rows = len(contexts)
    experts = expert_matmuls(m, assignments, touched, weight_bytes)
    attn = latent_attention(m, float(sum(contexts)))
    # phi's bytes are among the weights; its product among the streams'
    streams = hc_kernels(m, rows * sub_layers(m), 0)
    dense = always_read_params(m)
    matmuls = dense - sub_layers(m) * mapping_params(m)
    return {"flops": (2.0 * matmuls * rows + experts["flops"]
                      + attn["flops"] + streams["flops"]),
            "bytes": (dense * weight_bytes + experts["bytes"]
                      + attn["bytes"] + streams["bytes"])}


least_seconds = costs.least_seconds
