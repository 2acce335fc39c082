"""Operations and bytes a sparse-expert decoder needs, from shapes and
from what the expert layers counted, never "all experts" by assumption.
Kept with the benchmark so that no PR claiming a gain can move them.

`m` is a configuration file's model section with the expert keys:
hidden_size, num_hidden_layers, num_attention_heads,
num_key_value_heads, head_dim, intermediate_size (one expert's width),
vocab_size, num_experts, num_experts_per_tok.
"""
from __future__ import annotations

from . import costs


def attention_params(m: dict) -> int:
    """q, k, v and o projections of one layer."""
    h, hd = m["hidden_size"], m["head_dim"]
    return (2 * h * m["num_attention_heads"] * hd
            + 2 * h * m["num_key_value_heads"] * hd)


def expert_params(m: dict) -> int:
    """gate, up and down of ONE expert."""
    return 3 * m["hidden_size"] * m["intermediate_size"]


def router_params(m: dict) -> int:
    return m["hidden_size"] * m["num_experts"]


def total_params(m: dict) -> int:
    layer = (attention_params(m) + router_params(m)
             + m["num_experts"] * expert_params(m)
             + 2 * m["hidden_size"])                 # two norms
    # q/k norm weights: one vector over the projected q, one over k
    qk = (m["num_attention_heads"] + m["num_key_value_heads"]) \
        * m["head_dim"]
    return (m["num_hidden_layers"] * (layer + qk)
            + 2 * m["hidden_size"] * m["vocab_size"] + m["hidden_size"])


def expert_matmuls(m: dict, assignments: float, touched: float,
                   weight_bytes: int = 2, act_bytes: int = 2) -> dict:
    """The three grouped matmuls of expert layers that together ran
    `assignments` (row, expert) pairs and touched `touched` experts
    (summed over the layers and calls counted): every touched expert's
    weights read once, every assignment's row read once and its result
    written once (the intermediate of width intermediate_size need not
    leave the chip's fast memory)."""
    flops = 2.0 * assignments * expert_params(m)
    nbytes = (touched * expert_params(m) * weight_bytes
              + 2.0 * assignments * m["hidden_size"] * act_bytes)
    return {"flops": flops, "bytes": nbytes}


def decode_step(m: dict, contexts: list, touched_per_layer: float,
                weight_bytes: int = 2) -> dict:
    """One decode step over sequences with the given context lengths:
    attention, router and head weights read once, the weights of the
    experts the step touched read once (per layer, as counted), every
    sequence's cache read once; each row is multiplied with the dense
    weights and with its own k experts."""
    rows, layers = len(contexts), m["num_hidden_layers"]
    dense = (layers * (attention_params(m) + router_params(m))
             + m["hidden_size"] * m["vocab_size"])
    experts = expert_matmuls(
        m, rows * m["num_experts_per_tok"] * layers,
        touched_per_layer * layers, weight_bytes)
    flops = (2.0 * dense * rows + experts["flops"]
             + sum(costs.attention_flops(m, 1, c) for c in contexts))
    nbytes = (dense * weight_bytes + experts["bytes"]
              + sum(contexts) * costs.kv_bytes_per_token(m))
    return {"flops": flops, "bytes": nbytes}
