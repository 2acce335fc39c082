"""Operations and bytes a Nemotron-3-Super decoder needs (Mamba-2 layers
with a fixed recurrent state a sequence, one NoPE grouped-query layer
that pages K and V, expert layers that hold a share of relu^2 experts
computed in a latent beside a shared expert at full width), from shapes
and from what the engine counted. Kept with the benchmark so that no PR
claiming a gain can move them.

`m` is the model section `replica_nemotron.model_section` builds: the
published keys (hidden_size, num_attention_heads, num_key_value_heads,
head_dim, mamba_num_heads, mamba_head_dim, ssm_state_size, n_groups,
conv_kernel, moe_intermediate_size, moe_latent_size,
moe_shared_expert_intermediate_size, n_routed_experts (= num_experts:
the experts HELD), num_experts_per_tok, vocab_size,
hybrid_override_pattern: one of M, *, E a PUBLISHED layer) and
`router_width` (the router's 512 outputs). The whole published model is
`dict(m, hybrid_override_pattern=<all 88>, num_experts=512,
vocab_size=131072)`: `total_params` then gives 120.7 B.

Counted: every matmul weight (the untied head is read as a matmul, the
embedding is a lookup), the experts a step touched and the pairs it ran
(1 024-wide rows in and out), K and V a token in the `*` layers at the
PUBLISHED 1 024 B (1 layer x 2 x 2 heads x 128 x 2 B here; the pool lays
out 8 heads, 4 096 B: the padding is the program's, so a share errs low
by it), a decoding row's state once in and once out in every `M` layer
(4 MiB each way) and its convolution tail. Left out: the norms, the
convolution's multiply-adds, the gates, the router's top-22, rows in
and out of the dense matmuls; every share errs low by them.
"""
from __future__ import annotations

from . import costs


def layers(m: dict, kind: str) -> int:
    """Published layers of one kind: "M", "*" or "E"."""
    return m["hybrid_override_pattern"].count(kind)


def inner_width(m: dict) -> int:
    return m["mamba_num_heads"] * m["mamba_head_dim"]


def conv_width(m: dict) -> int:
    """Columns of xs | B | C, what the convolution runs over."""
    return inner_width(m) + 2 * m["n_groups"] * m["ssm_state_size"]


def state_elements(m: dict) -> int:
    """One `M` layer's recurrent state of one sequence: a P x N matrix a
    head."""
    return inner_width(m) * m["ssm_state_size"]


def mamba_params(m: dict) -> int:
    """W_in (z | xs B C | dt) and W_out of one `M` layer (its matmuls)."""
    d = m["hidden_size"]
    return (d * (inner_width(m) + conv_width(m) + m["mamba_num_heads"])
            + inner_width(m) * d)


def attention_params(m: dict) -> int:
    """W_q, W_k, W_v and W_o of one `*` layer."""
    d, hd = m["hidden_size"], m["head_dim"]
    return (2 * d * m["num_attention_heads"] * hd
            + 2 * d * m["num_key_value_heads"] * hd)


def expert_params(m: dict) -> int:
    """The two matrices of ONE routed expert, in the latent."""
    return 2 * m["moe_latent_size"] * m["moe_intermediate_size"]


def expert_layer_dense_params(m: dict) -> int:
    """What an `E` layer reads whatever the routing: the router, the
    latent pair and the shared expert at full width."""
    d = m["hidden_size"]
    return (d * m["router_width"] + 2 * d * m["moe_latent_size"]
            + 2 * d * m["moe_shared_expert_intermediate_size"])


def always_read_params(m: dict) -> int:
    """Matmul parameters every decode step reads whatever the routing."""
    return (layers(m, "M") * mamba_params(m)
            + layers(m, "*") * attention_params(m)
            + layers(m, "E") * expert_layer_dense_params(m)
            + m["hidden_size"] * m["vocab_size"])


def total_params(m: dict) -> int:
    """Every parameter held: `always_read_params`, the experts held, the
    embedding, the convolutions with their bias, A_log, dt_bias, D, the
    norms' weights and the selection biases."""
    d, h = m["hidden_size"], m["mamba_num_heads"]
    small_m = ((m["conv_kernel"] + 1) * conv_width(m) + 3 * h
               + inner_width(m) + d)
    return (always_read_params(m)
            + layers(m, "M") * small_m + layers(m, "*") * d
            + layers(m, "E") * (m["num_experts"] * expert_params(m)
                                + m["router_width"] + d)
            + d * m["vocab_size"] + d)


def state_bytes_per_slot(m: dict, state_bytes: int = 4,
                         act_bytes: int = 2) -> int:
    """What a sequence keeps whatever its length: in every `M` layer the
    float32 state and the convolution's last K - 1 inputs."""
    return layers(m, "M") * (
        state_elements(m) * state_bytes
        + (m["conv_kernel"] - 1) * conv_width(m) * act_bytes)


def kv_bytes_per_token(m: dict, dtype_bytes: int = 2) -> int:
    """What the mathematics caches a token: K and V of the `*` layers'
    published KV heads."""
    return (layers(m, "*") * 2 * m["num_key_value_heads"] * m["head_dim"]
            * dtype_bytes)


def ssm_step(m: dict, layer_rows: float, state_bytes: int = 4) -> dict:
    """The one-token step of the recurrence over `layer_rows` (decoding
    row, `M` layer) pairs: each pair's state read once and written once;
    per state element a decay, a product and a sum for the written rank
    one, a product and a sum for the read, 5 operations. The row's C, B,
    xs, decay and result (some tens of KiB beside 8 MiB) are left out,
    so the share errs low by them."""
    n = layer_rows * state_elements(m)
    return {"flops": 5.0 * n, "bytes": 2.0 * n * state_bytes}


def _attention_flops(m: dict, tokens: float) -> float:
    """One query token over `tokens` cached keys in every `*` layer: two
    products."""
    return (4.0 * layers(m, "*") * m["num_attention_heads"]
            * m["head_dim"] * tokens)


def paged_attention(m: dict, live_pages: float, page_size: int,
                    dtype_bytes: int = 2) -> dict:
    """The `*` layers read K and V of each live page once (a partly
    filled page whole), at the published bytes a token."""
    tokens = live_pages * page_size
    return {"flops": _attention_flops(m, tokens),
            "bytes": tokens * kv_bytes_per_token(m, dtype_bytes)}


def expert_matmuls(m: dict, assignments: float, touched: float,
                   weight_bytes: int = 2, act_bytes: int = 2) -> dict:
    """The two grouped matmuls of expert layers that together ran
    `assignments` (row, expert) pairs and touched `touched` experts:
    every touched expert's weights read once, every assignment's latent
    row read once and its result written once."""
    return {"flops": 2.0 * assignments * expert_params(m),
            "bytes": touched * expert_params(m) * weight_bytes
            + 2.0 * assignments * m["moe_latent_size"] * act_bytes}


def decode_step(m: dict, contexts: list, touched: float,
                assignments: float, weight_bytes: int = 2) -> dict:
    """One decode step over sequences with the given context lengths:
    the weights every step reads once, the experts the step touched and
    the pairs it ran on them (summed over the layers, as counted), K and
    V of every live sequence's tokens in the `*` layers, the live rows'
    state and convolution tail in and out in the `M` layers."""
    rows = len(contexts)
    experts = expert_matmuls(m, assignments, touched, weight_bytes)
    scan = ssm_step(m, rows * layers(m, "M"))
    tokens = float(sum(contexts))
    dense = always_read_params(m)
    return {"flops": 2.0 * dense * rows + experts["flops"] + scan["flops"]
            + _attention_flops(m, tokens),
            "bytes": dense * weight_bytes + experts["bytes"]
            + tokens * kv_bytes_per_token(m)
            + 2.0 * rows * state_bytes_per_slot(m)}


least_seconds = costs.least_seconds
