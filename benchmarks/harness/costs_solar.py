"""Operations and bytes a Solar-Open2 decoder needs (delta-rule layers
with a decay a key channel and a fixed recurrent state a sequence,
gated grouped-query layers that page K and V, in every layer an expert
share beside a shared expert), from shapes and from what the engine
counted. Kept with the benchmark so that no PR claiming a gain can move
them.

`m` is the model section `replica_solar.model_section` builds: the
published keys (hidden_size, num_hidden_layers, num_attention_heads,
num_key_value_heads, head_dim, moe_intermediate_size, n_routed_experts
(= num_experts: the experts HELD), n_shared_experts, num_experts_per_tok,
vocab_size, linear_attn_config {num_heads, head_dim,
short_conv_kernel_size}, kda_rank), `router_width` (the router's 320
outputs) and `layer_types` (one entry a layer held). The whole
published model is `dict(m, num_hidden_layers=48, num_experts=320,
vocab_size=196608, layer_types=<all 48>)`.

Counted: every matmul weight (the untied head is read as a matmul, the
embedding is a lookup), the experts a step touched and the pairs it ran,
K and V a token in the FULL layers at the published 4 096 B (1 layer x
2 x 8 heads x 128 x 2 B here), a decoding row's state once in and once
out in every delta-rule layer (4 MiB each way) and its convolution tail.
Left out: the norms, the convolution's multiply-adds, the gates, the
router's sort, rows in and out of the dense matmuls; every share errs
low by them.
"""
from __future__ import annotations

from . import costs

KDA, FULL = "kda", "full_attention"


def kda_layers(m: dict) -> int:
    return sum(k == KDA for k in m["layer_types"])


def full_layers(m: dict) -> int:
    return sum(k == FULL for k in m["layer_types"])


def _lin(m: dict) -> tuple:
    lin = m["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]


def conv_width(m: dict) -> int:
    """Columns of the q | k | v projections of a delta-rule layer."""
    h, hd, _ = _lin(m)
    return 3 * h * hd


def state_elements(m: dict) -> int:
    """One delta-rule layer's recurrent state of one sequence: a d_v x
    d_k matrix a head."""
    h, hd, _ = _lin(m)
    return h * hd * hd


def kda_mixer_params(m: dict) -> int:
    """W_q, W_k, W_v, W_o, the two low-rank pairs and beta's projection
    of one delta-rule layer (its matmuls)."""
    d, (h, hd, _), r = m["hidden_size"], _lin(m), m["kda_rank"]
    return (d * conv_width(m) + h * hd * d + 2 * (d * r + r * h * hd)
            + d * h)


def full_mixer_params(m: dict) -> int:
    """W_q, W_gate, W_k, W_v and W_o of one full layer."""
    d, hd = m["hidden_size"], m["head_dim"]
    return (3 * d * m["num_attention_heads"] * hd
            + 2 * d * m["num_key_value_heads"] * hd)


def expert_params(m: dict) -> int:
    """gate, up and down of ONE expert (routed or shared)."""
    return 3 * m["hidden_size"] * m["moe_intermediate_size"]


def router_params(m: dict) -> int:
    return m["hidden_size"] * m["router_width"]


def always_read_params(m: dict) -> int:
    """Matmul parameters every decode step reads whatever the routing:
    every mixer, every layer's router and shared expert, the head."""
    return (kda_layers(m) * kda_mixer_params(m)
            + full_layers(m) * full_mixer_params(m)
            + m["num_hidden_layers"] * (router_params(m)
                                        + m["n_shared_experts"]
                                        * expert_params(m))
            + m["hidden_size"] * m["vocab_size"])


def total_params(m: dict) -> int:
    """Every parameter held: `always_read_params`, the experts held, the
    embedding, the convolutions, A_log, dt_bias, norm weights and
    selection biases."""
    d, (h, hd, k) = m["hidden_size"], _lin(m)
    small_kda = k * conv_width(m) + h + h * hd + hd
    return (always_read_params(m)
            + m["num_hidden_layers"] * (m["num_experts"] * expert_params(m)
                                        + m["router_width"] + 2 * d)
            + kda_layers(m) * small_kda + d * m["vocab_size"] + d)


def state_bytes_per_slot(m: dict, state_bytes: int = 4,
                         act_bytes: int = 2) -> int:
    """What a sequence keeps whatever its length: in every delta-rule
    layer the float32 state and the convolution's last K - 1 inputs."""
    _, _, k = _lin(m)
    return kda_layers(m) * (state_elements(m) * state_bytes
                            + (k - 1) * conv_width(m) * act_bytes)


def kv_bytes_per_token(m: dict, dtype_bytes: int = 2) -> int:
    """What the mathematics caches a token: K and V in the FULL layers
    only."""
    return (full_layers(m) * 2 * m["num_key_value_heads"] * m["head_dim"]
            * dtype_bytes)


def kda_step(m: dict, layer_rows: float, state_bytes: int = 4) -> dict:
    """The one-token step of the recurrence over `layer_rows` (decoding
    row, delta-rule layer) pairs: each pair's state read once and
    written once; per state element a decay, two products with k (S k
    and the rank-one update) and one with q, 7 operations. The row's q,
    k, v, decay and result (a few tens of KiB beside 8 MiB) are left
    out, so the share errs low by them."""
    n = layer_rows * state_elements(m)
    return {"flops": 7.0 * n, "bytes": 2.0 * n * state_bytes}


def _attention_flops(m: dict, tokens: float) -> float:
    """One query token over `tokens` cached keys in every full layer:
    two products."""
    return (4.0 * full_layers(m) * m["num_attention_heads"]
            * m["head_dim"] * tokens)


def paged_attention(m: dict, live_pages: float, page_size: int,
                    dtype_bytes: int = 2) -> dict:
    """The full layers read K and V of each live page once (a partly
    filled page whole)."""
    tokens = live_pages * page_size
    return {"flops": _attention_flops(m, tokens),
            "bytes": tokens * kv_bytes_per_token(m, dtype_bytes)}


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
    the weights every step reads once, the experts the step touched and
    the pairs it ran on them (summed over the layers, as counted), K and
    V of every live sequence's tokens in the full layers, the live
    rows' state and convolution tail in and out in the delta-rule
    layers."""
    rows = len(contexts)
    experts = expert_matmuls(m, assignments, touched, weight_bytes)
    kda = kda_step(m, rows * kda_layers(m))
    tokens = float(sum(contexts))
    dense = always_read_params(m)
    return {"flops": 2.0 * dense * rows + experts["flops"] + kda["flops"]
            + _attention_flops(m, tokens),
            "bytes": dense * weight_bytes + experts["bytes"]
            + tokens * kv_bytes_per_token(m)
            + 2.0 * rows * state_bytes_per_slot(m)}


least_seconds = costs.least_seconds
