"""Mixture-of-Experts: routing, dropless dispatch and capacity dispatch.

Two ways from routed tokens to expert batches, both with static shapes:

* `moe_dropless` (serving and the plain forward). The G x k assignments
  are sorted by expert, the tokens gathered in that order, each SwiGLU
  matmul is one grouped matmul over the ragged groups
  (`grouped_matmul`: the megablox Pallas kernel that ships with JAX on
  the TPU, `jax.lax.ragged_dot` elsewhere), and the results are gathered
  back and summed with their weights. Cost follows G x k; nothing is
  ever dropped, whatever the routing, so a row's result does not depend
  on the other rows of the call.
* `moe_dispatch_combine` (GShard/Switch, for `ep`-sharded training):
  one-hot einsums, dispatch (G,E,C) x tokens (G,d) -> (E,C,d), which
  XLA lowers to an all-to-all when the expert dim is sharded over `ep`.
  Tokens over an expert's capacity C are dropped.

`route` holds three published conventions: Mixtral's (softmax over the
selected logits), OLMoE's (softmax over all experts, then the k
largest, renormalised or not) and the aux-loss-free one of the
latent-attention families (sigmoid scores, a learned bias that enters
the selection only, weights renormalised over the selected and scaled).
Router maths is float32.

A share. `moe_dropless` may hold a share of a layer's experts (`first`,
`count`: the chip's part of an expert-parallel deployment). The router
still scores and selects over all experts; assignments to experts that
live elsewhere are given to no group, exactly as the assignments of
unreal rows are, and the result is this share's part of the sum. On
one chip the layer runs without its exchange: nothing stands in for
the absent experts.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from .activations import relu2, swiglu

ROUTINGS = ("topk_softmax", "softmax_topk", "sigmoid_bias")

# What a dropless expert layer counts in one call, in this order, as one
# int32 vector (models sow it, the engine sums it over layers and steps).
# `moe_assignments` counts the (row, expert) pairs that RAN HERE: real
# rows on experts this layer holds, which is every routed pair unless
# the layer holds a share. `moe_routed_assignments` is what the router
# handed out (real rows x k), wherever the experts live.
MOE_STATS = ("moe_assignments", "moe_rows", "moe_pad_rows",
             "moe_expert_load_max", "moe_experts_touched",
             "moe_routed_assignments")


class MoEAux(NamedTuple):
    load_balance_loss: jax.Array   # scalar, Switch-style
    router_z_loss: jax.Array       # scalar
    expert_load: jax.Array         # (E,) assignments per token, by expert


def expert_capacity(n_tokens: int, n_experts: int, k: int,
                    capacity_factor: float) -> int:
    cap = int(n_tokens * k * capacity_factor / n_experts)
    return max(cap, 1)


def route(router_logits: jax.Array, k: int, routing: str = "topk_softmax",
          norm_topk_prob: bool = False,
          select_bias: Optional[jax.Array] = None, scale: float = 1.0,
          norm_eps: float = 0.0):
    """router_logits: (G, E). Returns float32 weights (G, k) and expert
    indices (G, k).

    "topk_softmax" (Mixtral): the k largest logits, softmax over them.
    "softmax_topk" (OLMoE): softmax over all E, then the k largest
    probabilities; they sum to less than 1 unless `norm_topk_prob`.
    "sigmoid_bias" (aux-loss-free balancing): scores s = sigmoid(logits);
    the k experts with the largest s + `select_bias` (E,) are selected,
    the bias entering the selection only; the weights are the selected
    experts' s, divided by their sum plus `norm_eps` if
    `norm_topk_prob`, times `scale`."""
    logits = router_logits.astype(jnp.float32)
    if routing == "sigmoid_bias":
        scores = jax.nn.sigmoid(logits)
        biased = scores if select_bias is None \
            else scores + select_bias.astype(jnp.float32)
        top_idx = jax.lax.top_k(biased, k)[1]
        weights = jnp.take_along_axis(scores, top_idx, axis=-1)
        if norm_topk_prob:
            weights = weights / (weights.sum(-1, keepdims=True) + norm_eps)
        return weights * scale, top_idx
    if routing == "topk_softmax":
        top_logits, top_idx = jax.lax.top_k(logits, k)
        return jax.nn.softmax(top_logits, axis=-1), top_idx
    if routing == "softmax_topk":
        weights, top_idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        if norm_topk_prob:
            weights = weights / weights.sum(-1, keepdims=True)
        return weights, top_idx
    raise ValueError(f"routing={routing!r}; valid: {ROUTINGS}")


def router_aux(router_logits: jax.Array, top_idx: jax.Array) -> MoEAux:
    """Switch load-balance = E * sum(frac_tokens * frac_prob) / k, and the
    router z-loss, in float32."""
    logits = router_logits.astype(jnp.float32)
    e, k = logits.shape[-1], top_idx.shape[-1]
    frac_prob = jax.nn.softmax(logits, axis=-1).mean(axis=0)        # (E,)
    frac_tokens = jax.nn.one_hot(top_idx, e, dtype=jnp.float32) \
        .sum(axis=1).mean(axis=0)                                   # (E,)
    lb = e * jnp.sum(frac_prob * frac_tokens) / k
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return MoEAux(lb, z, frac_tokens)


# rows of a grouped matmul come in multiples of the kernel's row tile
_ROW_TILE = 128
# the kernel's k and n tiles are cut here where the cut divides the side
_SIDE_TILE = 1024
# and are at most this wide where it does not: 1 536 x 1 024 in bf16 is
# 3 MB a buffer of the weights' tile, two of them in flight, under the
# 16 MiB of VMEM the kernel is given
_SIDE_TILE_MAX = 1536


def _side_tile(side: int) -> int:
    if side <= _SIDE_TILE or side % _SIDE_TILE == 0:
        return min(side, _SIDE_TILE)
    return next((t for t in range(_SIDE_TILE_MAX, 0, -_ROW_TILE)
                 if side % t == 0), _SIDE_TILE)


def gmm_tiling(k: int, n: int) -> tuple:
    """(row, k, n) tiles of megablox `gmm` for experts of k x n, from
    the shape alone, each side by itself: a side up to 1 024 is one
    tile and a multiple of 1 024 is cut at 1 024 (the cut every side
    had before PR 41: OLMoE's 2 048 x 1 024 and sarvam-105b's 4 096 x
    2 048 keep their programs); any other side gets its largest divisor
    that is a multiple of 128 and at most 1 536 (LFM2's 1 536: whole),
    and only a side with no such divisor is cut at 1 024 with a ragged
    last tile (toy widths)."""
    return _ROW_TILE, _side_tile(k), _side_tile(n)


def grouped_matmul(xs: jax.Array, w: jax.Array, group_sizes: jax.Array,
                   interpret: bool = False) -> jax.Array:
    """xs: (M, k) rows sorted by group, M a multiple of 128; w: (E, k, n);
    group_sizes: (E,) int32. Row i of group e comes back as xs[i] @ w[e];
    rows behind the last group hold nothing meaningful.

    On the TPU this is megablox's `gmm` (jax.experimental.pallas.ops.tpu)
    at the tiles `gmm_tiling` derives from k and n, whole tiles wherever
    the side has a divisor: a tile that hangs over the matrix (n) costs
    the whole tile's product for part of its bytes, and one that hangs
    over k is masked, both operands through float32 and a select, before
    a whole product. One layer's three matmuls at a decode step's rows,
    64 experts, bf16 (`python -m tools.gmm_microbench`, PERF.md, PR 41):
    LFM2's 2 048 x 1 536 at 128 rows x 4 read 1.98 ms cut at 1 024 and
    1.75 ms on tiles of 1 024 x 1 536 (gate, up) and 1 536 x 1 024
    (down), 1.47 ms being the weights at the chip's bandwidth; OLMoE's
    2 048 x 1 024 at 65 rows x 8 read 1.21 ms (0.98 ms the weights),
    where XLA's own lowering of `jax.lax.ragged_dot` reads 1.43, and
    3.87 at LFM2's. Off the TPU `ragged_dot` is the reference lowering."""
    if jax.default_backend() != "tpu" and not interpret:
        return jax.lax.ragged_dot(xs, w, group_sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm  # noqa: PLC0415
    _e, k, n = w.shape
    return gmm(xs, w, group_sizes, preferred_element_type=xs.dtype,
               tiling=gmm_tiling(k, n), interpret=interpret)


def moe_dropless(x: jax.Array, weights: jax.Array, top_idx: jax.Array,
                 w_gate: Optional[jax.Array], w_up: jax.Array,
                 w_down: jax.Array,
                 row_mask: Optional[jax.Array] = None,
                 first: int = 0, count: Optional[int] = None):
    """x: (G, d) tokens; weights, top_idx: (G, k) from `route`; w_gate,
    w_up: (E, d, f); w_down: (E, f, d). `w_gate` None: experts without a
    gate, two matmuls each, down(relu(up x)^2). `row_mask` (G,) marks
    the real rows: the others (bucket padding, empty slots) are given to
    no expert, cost nothing in the grouped matmuls and come back as zeros.

    `first`, `count` (static): the layer holds experts first ..
    first + count - 1 of those `top_idx` names, and E = count. An
    assignment to any other expert is given to no group and adds
    nothing: the result is this share's part of the layer's sum. The
    default holds all of them.

    Returns (out (G, d) in x's dtype, stats): stats is the int32 vector
    MOE_STATS names, counted over the real rows.
    """
    g, k = top_idx.shape
    e = w_up.shape[0]
    share = count is not None
    if share and count != e:
        raise ValueError(f"count={count} but the weights hold {e} experts")
    with jax.named_scope("moe.dispatch"):
        expert_of = top_idx.reshape(g * k).astype(jnp.int32)
        here = None
        if share:
            expert_of = expert_of - first
            here = (expert_of >= 0) & (expert_of < e)
        if row_mask is not None:
            real = jnp.repeat(row_mask, k)
            here = real if here is None else here & real
        if here is not None:
            # expert E does not exist: those assignments sort behind
            # every group and belong to none
            expert_of = jnp.where(here, expert_of, e)
        order = jnp.argsort(expert_of, stable=True)       # by expert
        group_sizes = jnp.zeros((e + 1,), jnp.int32).at[expert_of].add(
            1)[:e]
        # padded to whole row tiles; the padding sits behind every group
        rows = jnp.pad(order // k, (0, -(g * k) % _ROW_TILE))
        xs = x[rows]                                       # (M, d)
    with jax.named_scope("moe.experts"):
        gate = None if w_gate is None else grouped_matmul(
            xs, w_gate.astype(x.dtype), group_sizes)
        up = grouped_matmul(xs, w_up.astype(x.dtype), group_sizes)
        ys = grouped_matmul(relu2(up) if gate is None else swiglu(gate, up),
                            w_down.astype(x.dtype), group_sizes)
    with jax.named_scope("moe.combine"):
        # back to (row, choice) order, then the weighted sum over the
        # row's k experts in float32; rows behind the last group hold
        # whatever the grouped matmul left there, so select, not multiply
        back = jnp.zeros((g * k,), jnp.int32).at[order].set(
            jnp.arange(g * k, dtype=jnp.int32))
        y = ys[back].reshape(g, k, -1).astype(jnp.float32)
        if share:
            y = jnp.where(here.reshape(g, k, 1), y, 0.0)
        out = jnp.einsum("gkd,gk->gd", y, weights.astype(jnp.float32))
        if row_mask is not None and not share:
            out = jnp.where(row_mask[:, None], out, 0.0)
        out = out.astype(x.dtype)
    rows = (jnp.int32(g) if row_mask is None
            else row_mask.sum().astype(jnp.int32))
    ran = here.sum().astype(jnp.int32) if share else rows * k
    stats = jnp.stack([ran, rows, g - rows, group_sizes.max(),
                       (group_sizes > 0).sum().astype(jnp.int32),
                       rows * k])
    return out, stats


def moe_dispatch_combine(x: jax.Array, router_logits: jax.Array,
                         expert_fn: Callable[[jax.Array], jax.Array],
                         *, k: int = 2,
                         capacity_factor: float = 1.25,
                         capacity: Optional[int] = None,
                         routing: str = "topk_softmax",
                         norm_topk_prob: bool = False):
    """x: (G, d) flattened tokens; router_logits: (G, E).

    expert_fn: (E, C, d) -> (E, C, d_out), typically a vmap over the expert
    dim of stacked expert weights (sharded over `ep`).

    Returns (out (G, d_out), MoEAux).
    """
    g, d = x.shape
    e = router_logits.shape[-1]
    c = capacity if capacity is not None else expert_capacity(
        g, e, k, capacity_factor)

    weights, top_idx = route(router_logits, k, routing, norm_topk_prob)
    # (G, k, E) one-hot of chosen experts, ranked by k-slot priority.
    assign = jax.nn.one_hot(top_idx, e, dtype=jnp.float32)
    # Position of each (token, slot) within its expert queue: slot-major
    # ordering so slot-0 (highest-priority) choices win capacity (GShard).
    # int32 cumsum keeps queue positions exact past 2^24 assignments.
    slot_major = assign.transpose(1, 0, 2).reshape(k * g, e).astype(jnp.int32)
    pos_slot_major = jnp.cumsum(slot_major, axis=0) - slot_major   # (k*G, E)
    pos = pos_slot_major.reshape(k, g, e).transpose(1, 0, 2)       # (G,k,E)
    keep = assign * (pos < c)                                       # (G,k,E)
    slot_pos = (pos * keep).sum(-1).astype(jnp.int32)               # (G,k)

    # dispatch (G, E, C): one-hot over capacity slot for kept assignments.
    cap_onehot = jax.nn.one_hot(slot_pos, c, dtype=jnp.float32)     # (G,k,C)
    dispatch = jnp.einsum("gke,gkc->gec", keep, cap_onehot)
    combine = jnp.einsum("gke,gk,gkc->gec", keep, weights, cap_onehot)

    expert_in = jnp.einsum("gec,gd->ecd", dispatch.astype(x.dtype), x)
    expert_out = expert_fn(expert_in)                               # (E,C,do)
    out = jnp.einsum("gec,ecd->gd", combine.astype(expert_out.dtype),
                     expert_out)
    return out, router_aux(router_logits, top_idx)
