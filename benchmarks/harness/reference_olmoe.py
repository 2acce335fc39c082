"""Plain reference of the OLMoE-1B-7B forward pass, kept with the
benchmark: jax.numpy in float32 under
`default_matmul_precision("highest")`, no cache, no kernels, no
capacity, no batching. It imports nothing from the program. It follows
the published model (HF `OlmoeForCausalLM`, modeling_olmoe.py):

    RMSNorm -> q, k, v projections (no bias; clip_qkv is null)
            -> RMSNorm over the whole projected q vector and over the
               whole projected k vector (OlmoeAttention.q_norm/k_norm)
            -> rotary (half-split rotation, HF `rotate_half`)
            -> causal multi-head attention -> output projection
            -> residual
    RMSNorm -> router logits over all experts -> softmax over ALL of
               them in float32 -> the k largest probabilities, NOT
               renormalised (norm_topk_prob false) -> sum over the k of
               probability x SwiGLU expert -> residual
    final RMSNorm -> untied head.

Departures from the published code, each because of what it is compared
with and none changing the function computed:
  * experts are applied as a dense masked sum: every expert sees every
    position and a position's result is multiplied by its routing
    weight, which is 0 unless the expert was chosen (HF gathers the
    chosen positions; the sum is the same);
  * the experts run one at a time (`lax.map`), each cast to float32 by
    itself, so that the reference fits beside the served model;
  * HF computes the router in the activations' dtype and casts to
    float32 for the softmax; here everything is float32.

Weights are read from the system's own parameter tree (flax names of
ray_tpu/models/mixtral.py), one layer at a time.

Near-ties. The k-th and (k+1)-th router probabilities of a position can
lie closer together than bf16 activations resolve, and a system that
computes in bf16 then picks the other expert: both are right answers of
"the k largest" at that precision, and they give different logits. So
the reference reports, for every layer and position, the margin between
its k-th and (k+1)-th probability relative to the k-th, and can be told
to `follow` a system's choices: where the system chose another set, and
every expert it added or left out is within `tie_margin` of the
reference's own k-th probability, the reference takes the system's set
(with its own float32 probabilities as weights). A choice outside that
margin is not followed; it is counted in `not_followed`, and the
comparison that uses this fails on it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32

def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    # x: (S, H, D); rotate halves (x1, x2) by position * inv_freq
    s, _h, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(x, p, m: dict):
    """The attention half of a layer on x (S, hidden): returns x + attn."""
    s = x.shape[0]
    nh, nkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    eps = m["rms_norm_eps"]
    a = p["attention"]
    h = _rms(x, p["attn_norm"].astype(F32), eps)
    q = _rms(h @ a["q_proj"]["kernel"].astype(F32),
             a["q_norm"].astype(F32), eps).reshape(s, nh, hd)
    k = _rms(h @ a["k_proj"]["kernel"].astype(F32),
             a["k_norm"].astype(F32), eps).reshape(s, nkv, hd)
    v = (h @ a["v_proj"]["kernel"].astype(F32)).reshape(s, nkv, hd)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    rep = nh // nkv                     # 1 for OLMoE: plain MHA
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    attn = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    return x + attn.reshape(s, nh * hd) @ a["o_proj"]["kernel"].astype(F32)


def routing(h, router_kernel, m: dict, follow=None, tie_margin=0.0):
    """h: (S, hidden) normed input of the expert layer. Returns the
    (S, E) matrix of routing weights (0 where not chosen) and a dict:
    `chosen` (S, E) bool, `margin_rel` (S,), `own` (S,) bool (the set is
    the reference's own), `not_followed` (S,) bool and `swap_rel` (S,):
    how far from the k-th probability, relative to it, the farthest
    expert lies that the followed system swapped in or out (0 where it
    chose as the reference does): the margin that position needs."""
    k = m["num_experts_per_tok"]
    probs = jax.nn.softmax(h @ router_kernel.astype(F32), axis=-1)
    ranked = jnp.sort(probs, axis=-1)[:, ::-1]
    kth, nxt = ranked[:, k - 1], ranked[:, k]
    chosen = jnp.zeros(probs.shape, bool).at[
        jnp.arange(h.shape[0])[:, None],
        jax.lax.top_k(probs, k)[1]].set(True)   # the k largest
    info = {"margin_rel": (kth - nxt) / kth,
            "own": jnp.ones(h.shape[0], bool),
            "not_followed": jnp.zeros(h.shape[0], bool),
            "swap_rel": jnp.zeros(h.shape[0], F32)}
    if follow is not None:
        theirs = jnp.zeros_like(chosen).at[
            jnp.arange(h.shape[0])[:, None], follow].set(True)
        differs = theirs != chosen
        # every expert they swapped in or out lies within tie_margin of
        # this reference's own k-th probability
        away = jnp.abs(probs - kth[:, None]) / kth[:, None]
        near = away <= tie_margin
        info["swap_rel"] = jnp.where(differs, away, 0.0).max(-1)
        valid = jnp.all(~differs | near, axis=-1) \
            & (theirs.sum(-1) == k)
        changed = jnp.any(differs, axis=-1)
        info["own"] = ~changed
        info["not_followed"] = changed & ~valid
        chosen = jnp.where((changed & valid)[:, None], theirs, chosen)
    weights = jnp.where(chosen, probs, 0.0)
    if m["norm_topk_prob"]:
        weights = weights / weights.sum(-1, keepdims=True)
    info["chosen"] = chosen
    return weights, info


def experts(h, weights, moe):
    """sum_e weights[:, e] * down_e(silu(gate_e(h)) * up_e(h)), the
    experts one at a time."""
    def one(args):
        wg, wu, wd, w_e = args                  # this expert's, (S,)
        y = (jax.nn.silu(h @ wg.astype(F32)) * (h @ wu.astype(F32))) \
            @ wd.astype(F32)
        return y * w_e[:, None]
    per_expert = jax.lax.map(one, (
        moe["experts_gate_kernel"], moe["experts_up_kernel"],
        moe["experts_down_kernel"], weights.T))
    return per_expert.sum(0)


def layer_forward(x, p, m: dict, follow=None, tie_margin=0.0):
    x = attention(x, p, m)
    h = _rms(x, p["mlp_norm"].astype(F32), m["rms_norm_eps"])
    weights, info = routing(h, p["moe"]["router_kernel"], m, follow,
                            tie_margin)
    return x + experts(h, weights, p["moe"]), info


def forward(params, tokens, m: dict, last: int | None = None,
            follow=None, tie_margin: float = 0.0):
    """Logits (S or last, vocab) in float32 for one sequence of token
    ids, and per layer the routing record of `routing` (arrays over the
    S positions). `follow`: per layer an (S, k) array of a system's
    chosen experts, or None."""
    with jax.default_matmul_precision("highest"):
        x = params["token_embed"]["embedding"][tokens].astype(F32)
        step = jax.jit(lambda x, p, f: layer_forward(x, p, m, f,
                                                     tie_margin))
        records = []
        for i in range(m["num_hidden_layers"]):
            x, info = step(x, params[f"layer_{i}"],
                           None if follow is None else follow[i])
            records.append(info)
        if last is not None:
            x = x[-last:]
        x = _rms(x, params["final_norm"].astype(F32), m["rms_norm_eps"])
        return x @ params["lm_head"]["kernel"].astype(F32), records


def forward_logits(params, tokens, m: dict, last: int | None = None):
    return forward(params, tokens, m, last)[0]
