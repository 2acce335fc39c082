"""Operations and bytes the algorithm needs, from shapes alone. Kept
with the benchmark so that no PR claiming a gain can move them.

`m` is a configuration file's model section: hidden_size,
num_hidden_layers, num_attention_heads, num_key_value_heads, head_dim,
intermediate_size, vocab_size.
"""
from __future__ import annotations


def layer_matmul_params(m: dict) -> int:
    h, hd = m["hidden_size"], m["head_dim"]
    q = h * m["num_attention_heads"] * hd
    kv = 2 * h * m["num_key_value_heads"] * hd
    o = m["num_attention_heads"] * hd * h
    mlp = 3 * h * m["intermediate_size"]
    return q + kv + o + mlp


def matmul_params(m: dict) -> int:
    """Parameters every token is multiplied with: the layers and the
    untied head (the embedding is a lookup)."""
    return (m["num_hidden_layers"] * layer_matmul_params(m)
            + m["hidden_size"] * m["vocab_size"])


def total_params(m: dict) -> int:
    norms = (2 * m["num_hidden_layers"] + 1) * m["hidden_size"]
    return matmul_params(m) + m["hidden_size"] * m["vocab_size"] + norms


def kv_bytes_per_token(m: dict, dtype_bytes: int = 2) -> int:
    return (m["num_hidden_layers"] * 2 * m["num_key_value_heads"]
            * m["head_dim"] * dtype_bytes)


def attention_flops(m: dict, q_tokens: float, context: float) -> float:
    """QK^T and PV for q_tokens queries over `context` keys each."""
    return (4.0 * m["num_hidden_layers"] * m["num_attention_heads"]
            * m["head_dim"] * q_tokens * context)


def decode_step(m: dict, contexts: list, weight_bytes: int = 2) -> dict:
    """One decode step over sequences with the given context lengths:
    every weight is read once, every sequence's cache is read once."""
    rows = len(contexts)
    flops = 2.0 * matmul_params(m) * rows + sum(
        attention_flops(m, 1, c) for c in contexts)
    nbytes = (matmul_params(m) * weight_bytes
              + sum(contexts) * kv_bytes_per_token(m))
    return {"flops": flops, "bytes": nbytes}


def least_seconds(cost: dict, peaks: dict) -> dict:
    """Roofline: the least time the chip could take, and which bound."""
    by_flops = cost["flops"] / peaks["bf16_flops"]
    by_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return {"seconds": max(by_flops, by_bytes),
            "bound": "compute" if by_flops > by_bytes else "memory"}


def train_flops_per_token(m: dict, seq_len: int) -> float:
    """Forward and backward: 6 per matmul parameter, plus causal
    attention (half of the full square) at 3x its forward cost.
    Recomputation under remat is not counted."""
    attn_fwd = attention_flops(m, 1, seq_len / 2.0)
    return 6.0 * matmul_params(m) + 3.0 * attn_fwd
