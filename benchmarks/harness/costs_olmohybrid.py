"""Operations and bytes a hybrid decoder needs (Olmo-Hybrid-7B: Gated
DeltaNet layers with a fixed recurrent state a sequence, and
full-attention layers that page K and V), from shapes and from what the
engine counted. Kept with the benchmark so that no PR claiming a gain
can move them.

`m` is the model section `replica_olmohybrid.model_section` builds: the
Llama-shaped keys (hidden_size, num_hidden_layers, num_attention_heads,
num_key_value_heads, head_dim, intermediate_size, vocab_size) and
`layer_types` (one entry a layer held), linear_num_key_heads,
linear_num_value_heads, linear_key_head_dim, linear_value_head_dim,
linear_conv_kernel_dim. The whole published model is
`dict(m, num_hidden_layers=32, layer_types=<all 32>)`.
"""
from __future__ import annotations

from . import costs

LINEAR, FULL = "linear_attention", "full_attention"


def linear_layers(m: dict) -> int:
    return sum(k == LINEAR for k in m["layer_types"])


def full_layers(m: dict) -> int:
    return sum(k == FULL for k in m["layer_types"])


def conv_width(m: dict) -> int:
    """Columns of the fused q | k | v projection of a linear layer."""
    return (2 * m["linear_num_key_heads"] * m["linear_key_head_dim"]
            + m["linear_num_value_heads"] * m["linear_value_head_dim"])


def state_elements(m: dict) -> int:
    """One linear layer's recurrent state of one sequence: a d_v x d_k
    matrix a head."""
    return (m["linear_num_value_heads"] * m["linear_value_head_dim"]
            * m["linear_key_head_dim"])


def mlp_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def linear_mixer_params(m: dict) -> int:
    """W_qkv, W_g, W_o and the two per-head gate projections of one
    linear layer (its matmuls)."""
    h = m["hidden_size"]
    values = m["linear_num_value_heads"] * m["linear_value_head_dim"]
    return (h * conv_width(m) + 2 * h * values
            + 2 * h * m["linear_num_value_heads"])


def full_mixer_params(m: dict) -> int:
    """W_q, W_k, W_v and W_o of one full layer."""
    h, hd = m["hidden_size"], m["head_dim"]
    return (2 * h * m["num_attention_heads"] * hd
            + 2 * h * m["num_key_value_heads"] * hd)


def matmul_params(m: dict) -> int:
    """Parameters every token is multiplied with: the layers and the
    untied head (the embedding is a lookup)."""
    return (linear_layers(m) * (linear_mixer_params(m) + mlp_params(m))
            + full_layers(m) * (full_mixer_params(m) + mlp_params(m))
            + m["hidden_size"] * m["vocab_size"])


def total_params(m: dict) -> int:
    """Every parameter held: `matmul_params`, the embedding, the
    convolutions, A_log, dt_bias and the norm weights."""
    h, nl, nf = m["hidden_size"], linear_layers(m), full_layers(m)
    hl = m["linear_num_value_heads"]
    small_linear = (m["linear_conv_kernel_dim"] * conv_width(m) + 2 * hl
                    + m["linear_value_head_dim"] + 2 * h)
    small_full = (m["num_attention_heads"] + m["num_key_value_heads"]) \
        * m["head_dim"] + 2 * h
    return (matmul_params(m) + h * m["vocab_size"] + nl * small_linear
            + nf * small_full + h)


def state_bytes_per_slot(m: dict, state_bytes: int = 4,
                         act_bytes: int = 2) -> int:
    """What a sequence keeps whatever its length: in every linear layer
    the float32 state and the convolution's last K - 1 inputs."""
    return linear_layers(m) * (
        state_elements(m) * state_bytes
        + (m["linear_conv_kernel_dim"] - 1) * conv_width(m) * act_bytes)


def kv_bytes_per_token(m: dict, dtype_bytes: int = 2) -> int:
    """What the mathematics caches a token: K and V in the FULL layers
    only (a pool laid out for more heads reads more and shows it as lost
    roofline)."""
    return (full_layers(m) * 2 * m["num_key_value_heads"] * m["head_dim"]
            * dtype_bytes)


def gdn_step(m: dict, layer_rows: float, state_bytes: int = 4) -> dict:
    """The one-token step of the recurrence over `layer_rows` (decoding
    row, linear layer) pairs: each pair's state read once and written
    once; per state element a decay, two products with k (S k and the
    rank-one update) and one with q, 7 operations. The row's q, k, v and
    result (a few KiB) are left out, so the share errs low by them."""
    n = layer_rows * state_elements(m)
    return {"flops": 7.0 * n, "bytes": 2.0 * n * state_bytes}


def paged_attention(m: dict, live_pages: float, page_size: int,
                    dtype_bytes: int = 2) -> dict:
    """`costs_paged.paged_attention` with K and V counted in the layers
    that have them: the full layers read K and V of each live page
    once; one query token over every live key is two products."""
    tokens = live_pages * page_size
    return {"flops": 4.0 * full_layers(m) * m["num_attention_heads"]
            * m["head_dim"] * tokens,
            "bytes": tokens * kv_bytes_per_token(m, dtype_bytes)}


def decode_step(m: dict, contexts: list, weight_bytes: int = 2) -> dict:
    """One decode step over sequences with the given context lengths:
    every matmul weight read once, every live sequence's state read and
    written once in every linear layer, K and V of its tokens read once
    in every full layer."""
    rows = len(contexts)
    gdn = gdn_step(m, rows * linear_layers(m))
    tokens = float(sum(contexts))
    attn_flops = (4.0 * full_layers(m) * m["num_attention_heads"]
                  * m["head_dim"] * tokens)
    return {"flops": 2.0 * matmul_params(m) * rows + gdn["flops"]
            + attn_flops,
            "bytes": matmul_params(m) * weight_bytes + gdn["bytes"]
            + tokens * kv_bytes_per_token(m)}


least_seconds = costs.least_seconds
