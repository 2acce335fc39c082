"""Operations and bytes an LFM2-MoE decoder needs (LFM2-24B-A2B: gated
short-convolution layers that keep K - 1 inputs a sequence, full
attention layers that page K and V of 64-wide heads, a dense SwiGLU in
the leading layers and sigmoid-routed experts after them), from shapes
and from what the engine counted. Kept with the benchmark so that no PR
claiming a gain can move them.

`m` is the model section `replica_lfm2moe.model_section` builds: the
published keys (hidden_size, num_hidden_layers, num_attention_heads,
num_key_value_heads, head_dim, intermediate_size (the dense layers'),
moe_intermediate_size (one expert's), num_experts, num_experts_per_tok,
num_dense_layers, conv_L_cache, vocab_size) and `layer_types` (one entry
a layer held). The whole published model is
`dict(m, num_hidden_layers=40, num_dense_layers=2, layer_types=<all 40>)`.

Counted: every matmul weight (the tied head is read as a matmul, the
embedding lookup is not counted again), the experts a step touched and
the pairs it ran, K and V a token in the FULL layers at the published
4 096 B (2 layers x 2 x 8 heads x 64 x 2 B here), the convolution's
state of a decoding row once in and once out. Left out: the norms, the
convolution's three multiply-adds a channel, the router's sort, rows
in and out of the dense matmuls; every share errs low by them.
"""
from __future__ import annotations

from . import costs, costs_moe

CONV, FULL = "conv", "full_attention"


def conv_layers(m: dict) -> int:
    return sum(k == CONV for k in m["layer_types"])


def full_layers(m: dict) -> int:
    return sum(k == FULL for k in m["layer_types"])


def expert_layers(m: dict) -> int:
    return m["num_hidden_layers"] - m["num_dense_layers"]


def conv_mixer_params(m: dict) -> int:
    """W_in (d x 3d), W_out (d x d) and the K taps a channel."""
    d = m["hidden_size"]
    return 4 * d * d + m["conv_L_cache"] * d


def full_mixer_params(m: dict) -> int:
    """W_q, W_k, W_v and W_o of one full layer."""
    h, hd = m["hidden_size"], m["head_dim"]
    return (2 * h * m["num_attention_heads"] * hd
            + 2 * h * m["num_key_value_heads"] * hd)


def expert_params(m: dict) -> int:
    """gate, up and down of ONE expert."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def dense_mlp_params(m: dict) -> int:
    return 3 * m["hidden_size"] * m["intermediate_size"]


def router_params(m: dict) -> int:
    return m["hidden_size"] * m["num_experts"]


def always_read_params(m: dict) -> int:
    """Matmul parameters every decode step reads whatever the routing:
    every mixer, the dense layers' SwiGLU, the routers, the tied head."""
    return (conv_layers(m) * conv_mixer_params(m)
            + full_layers(m) * full_mixer_params(m)
            + m["num_dense_layers"] * dense_mlp_params(m)
            + expert_layers(m) * router_params(m)
            + m["hidden_size"] * m["vocab_size"])


def total_params(m: dict) -> int:
    """Every parameter held: `always_read_params` (the tied embedding
    once), every expert, norm weights and selection biases."""
    h = m["hidden_size"]
    norms = (m["num_hidden_layers"] * 2 * h + h
             + full_layers(m) * 2 * m["head_dim"])
    return (always_read_params(m)
            + expert_layers(m) * (m["num_experts"] * expert_params(m)
                                  + m["num_experts"])
            + norms)


def state_bytes_per_slot(m: dict, act_bytes: int = 2) -> int:
    """What a sequence keeps whatever its length: in every conv layer
    the convolution's last K - 1 inputs."""
    return (conv_layers(m) * (m["conv_L_cache"] - 1) * m["hidden_size"]
            * act_bytes)


def kv_bytes_per_token(m: dict, dtype_bytes: int = 2) -> int:
    """What the mathematics caches a token: K and V in the FULL layers
    only, at the heads' published width (a pool that pads its rows
    reads more and shows it as lost roofline)."""
    return (full_layers(m) * 2 * m["num_key_value_heads"] * m["head_dim"]
            * dtype_bytes)


def _attention_flops(m: dict, tokens: float) -> float:
    """One query token over `tokens` cached keys in every full layer:
    two products at the heads' published width."""
    return (4.0 * full_layers(m) * m["num_attention_heads"]
            * m["head_dim"] * tokens)


def paged_attention(m: dict, live_pages: float, page_size: int,
                    dtype_bytes: int = 2) -> dict:
    """The full layers read K and V of each live page once (a partly
    filled page whole); one query token over every live key is two
    products at the heads' published width."""
    tokens = live_pages * page_size
    return {"flops": _attention_flops(m, tokens),
            "bytes": tokens * kv_bytes_per_token(m, dtype_bytes)}


def expert_matmuls(m: dict, assignments: float, touched: float,
                   weight_bytes: int = 2, act_bytes: int = 2) -> dict:
    """`costs_moe.expert_matmuls` at one expert's own width: the three
    grouped matmuls of expert layers that together ran `assignments`
    (row, expert) pairs and touched `touched` experts."""
    return costs_moe.expert_matmuls(
        dict(m, intermediate_size=m["moe_intermediate_size"]), assignments,
        touched, weight_bytes, act_bytes)


def decode_step(m: dict, contexts: list, touched: float,
                assignments: float, weight_bytes: int = 2) -> dict:
    """One decode step over sequences with the given context lengths:
    the weights every step reads once, the experts the step touched and
    the pairs it ran on them (summed over the expert layers, as
    counted), K and V of every live sequence's tokens in the full
    layers, the live rows' conv state in and out."""
    rows = len(contexts)
    experts = expert_matmuls(m, assignments, touched, weight_bytes)
    tokens = float(sum(contexts))
    dense = always_read_params(m)
    return {"flops": 2.0 * dense * rows + experts["flops"]
            + _attention_flops(m, tokens),
            "bytes": dense * weight_bytes + experts["bytes"]
            + tokens * kv_bytes_per_token(m)
            + 2.0 * rows * state_bytes_per_slot(m)}


least_seconds = costs.least_seconds
