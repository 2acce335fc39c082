"""Plain reference of the LFM2-24B-A2B forward pass (`model_type`
`lfm2_moe`), kept with the benchmark: jax.numpy in float32 under
`default_matmul_precision("highest")`, the convolution as a sum of
shifted copies, plain softmax attention, experts as a dense masked sum:
no kernel, no cache, no batching. It imports nothing from the program.
Written from the published config.json (the keys of `m` below are its
keys). Layer i is what `layer_types[i]` names; x_t is the residual
stream at position t, d = hidden_size = 2 048, eps = norm_eps:

    block      h = x + Mixer(RMSNorm(x)),  y = h + FF(RMSNorm(h))
    conv       [B | C | X]_t = W_in u_t            three blocks of d
               z_t = B_t * X_t
               c_t = sum_{j=0..K-1} w[:, j] * z_{t-(K-1)+j}    K = 3,
                     depthwise, causal, zeros before the start, no bias,
                     NO activation
               out_t = W_out (C_t * c_t)
    full       q, k, v = W_q u, W_k u, W_v u: 32 / 8 / 8 heads of 64
               q_h <- RMSNorm_64(q_h), k_h <- RMSNorm_64(k_h)  (one
                     learned weight of 64 for q, one for k)
               q, k <- RoPE_t (theta 1e6, halves (x1, x2) rotated)
               causal softmax at 64^-1/2, 4 query heads a KV head, W_o
    FF, i < num_dense_layers:   W_2 (SiLU(W_1 h) * W_3 h), width 11 776
    FF, otherwise:  s = sigmoid(W_r h) (64 scores); the 4 experts with
               the largest s + b are selected (b = expert_bias, in the
               selection only); w_e = s_e / (sum of the selected s +
               1e-6), x routed_scaling_factor; y = sum_e w_e E_e(h),
               E_e a SwiGLU of width 1 536; no shared expert
    final RMSNorm, then the head, which is the embedding (tied).

What the config has no key for and this file, with the program, reads
one way (the configuration file's `assumed`): pre-norm blocks, the
order B | C | X and the absence of an activation, per-head q/k RMSNorm
before the rotation, half-split pairing of rotated columns, sigmoid
scores and the 1e-6 in the renormalisation, the tied head.

Departures that change no function computed: experts are applied as a
dense masked sum, one expert at a time (`lax.map`), each cast to float32
by itself; attention goes a KV head at a time and the head in blocks of
rows of the embedding, so that the reference fits beside the served
model.

Near-ties. As `reference_olmoe` / `reference_sarvam`: the reference
reports, for every expert layer and position, the margin between its
4th and 5th biased score relative to the 4th, and can be told to
`follow` a system's choices where every expert swapped lies within
`tie_margin` of its own 4th biased score. A choice outside the margin
is not followed and is counted in `not_followed`.

`m["controls"]` (a set of names, empty in every benchmark run) computes
a deliberately wrong model instead, for the measured controls that the
comparison must fail: "no_conv_gate_C" (out = W_out c), "conv_with_silu"
(c <- SiLU(c)), "conv_state_to_bucket_end" (the prompt padded with
token 0 to `m["bucket"]` positions and the convolution run over the
padding, which attention does not see and the rotation does not count:
what a prefill that does not stop its state at the prompt's true length
computes), "select_without_bias", "no_topk_norm", "no_qk_headnorm",
"qk_norm_whole_width" (one RMS over all of q's 2 048 and of k's 512),
"rope_before_norm", "post_norm" (h = x + RMSNorm(Mixer(x)), y = h +
RMSNorm(FF(h))), and one of precision: "int8_weights" (every matmul
weight rounded to 8 bits with one scale per output column).

Weights are read from the system's own parameter tree (flax names of
ray_tpu/models/hybrid.py), one layer at a time. Its convolution kernel
is (K, d) with row 0 on the current token: w[:, j] above is row
K - 1 - j.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
FULL = "full_attention"
ROUTE_EPS = 1e-6
# the deliberately wrong models of `m["controls"]` (docstring above)
CONTROLS = ("no_conv_gate_C", "conv_with_silu", "conv_state_to_bucket_end",
            "select_without_bias", "no_topk_norm", "no_qk_headnorm",
            "qk_norm_whole_width", "rope_before_norm", "post_norm",
            "int8_weights")


def _controls(m: dict) -> frozenset:
    return frozenset(m.get("controls", ()))


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _w(kernel, m: dict):
    """A matmul kernel in float32 (control "int8_weights": rounded to 8
    bits with one scale per output column first)."""
    w = kernel.astype(F32)
    if "int8_weights" in _controls(m):
        scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
        w = jnp.round(w / scale) * scale
    return w


def _rope(x, positions, theta):
    # x: (S, H, D); rotate halves (x1, x2) by position * theta^(-2i/D)
    d = x.shape[-1]
    inv = theta ** (-2.0 * jnp.arange(d // 2, dtype=F32) / d)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def conv_mixer(u, p, m: dict):
    """The gated short convolution on u (S, d), the mixer's normed
    input."""
    ctl, k = _controls(m), m["conv_L_cache"]
    s, d = u.shape
    bcx = u @ _w(p["in_proj"]["kernel"], m)
    b_gate, c_gate, x = bcx[:, :d], bcx[:, d:2 * d], bcx[:, 2 * d:]
    z = b_gate * x
    w = p["conv_kernel"].astype(F32)[::-1].T               # (d, K)
    zp = jnp.concatenate([jnp.zeros((k - 1, d), F32), z])
    c = sum(w[:, j] * zp[j:j + s] for j in range(k))
    if "conv_with_silu" in ctl:
        c = jax.nn.silu(c)
    if "no_conv_gate_C" not in ctl:
        c = c_gate * c
    return c @ _w(p["out_proj"]["kernel"], m)


def full_mixer(u, p, m: dict, positions, real=None):
    """Grouped-query attention on u (S, d). Keys that are not `real`
    are seen by no query but themselves."""
    ctl = _controls(m)
    s = u.shape[0]
    nh, nkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    eps, theta = m["norm_eps"], float(m["rope_theta"])
    q = u @ _w(p["q_proj"]["kernel"], m)
    k = u @ _w(p["k_proj"]["kernel"], m)
    v = (u @ _w(p["v_proj"]["kernel"], m)).reshape(s, nkv, hd)
    qw, kw = p["q_norm"].astype(F32), p["k_norm"].astype(F32)
    if "qk_norm_whole_width" in ctl:
        q = _rms(q, jnp.tile(qw, nh), eps)
        k = _rms(k, jnp.tile(kw, nkv), eps)
    q, k = q.reshape(s, nh, hd), k.reshape(s, nkv, hd)
    if "rope_before_norm" in ctl:
        q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    if not ctl & {"no_qk_headnorm", "qk_norm_whole_width"}:
        q, k = _rms(q, qw, eps), _rms(k, kw, eps)
    if "rope_before_norm" not in ctl:
        q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    rep = nh // nkv
    seen = jnp.tril(jnp.ones((s, s), bool))
    if real is not None:
        seen = (seen & real[None, :]) | jnp.eye(s, dtype=bool)

    def group(qkv):
        qg, kg, vg = qkv                       # (rep, S, D), (S, D), (S, D)
        scores = jnp.einsum("rqd,kd->rqk", qg, kg) * hd ** -0.5
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return jnp.einsum("rqk,kd->rqd", jax.nn.softmax(scores, -1), vg)

    attn = jax.lax.map(group, (
        q.reshape(s, nkv, rep, hd).transpose(1, 2, 0, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))      # (nkv, rep, S, D)
    attn = attn.reshape(nh, s, hd).transpose(1, 0, 2).reshape(s, nh * hd)
    return attn @ _w(p["o_proj"]["kernel"], m)


def swiglu_mlp(h, p, m: dict):
    return (jax.nn.silu(h @ _w(p["gate_proj"]["kernel"], m))
            * (h @ _w(p["up_proj"]["kernel"], m))) \
        @ _w(p["down_proj"]["kernel"], m)


def routing(h, moe, m: dict, follow=None, tie_margin=0.0):
    """h: (S, hidden) normed input of the expert layer. Returns the
    (S, num_experts) matrix of routing weights (0 where not chosen) and
    a dict as `reference_olmoe.routing`'s: `chosen`, `margin_rel`,
    `own`, `not_followed`, `swap_rel`."""
    k, ctl = m["num_experts_per_tok"], _controls(m)
    scores = jax.nn.sigmoid(h @ moe["router_kernel"].astype(F32))
    biased = scores
    if m.get("use_expert_bias", True) and "select_without_bias" not in ctl:
        biased = scores + moe["router_bias"].astype(F32)
    ranked = jnp.sort(biased, axis=-1)[:, ::-1]
    kth, nxt = ranked[:, k - 1], ranked[:, k]
    rows = jnp.arange(h.shape[0])[:, None]
    chosen = jnp.zeros(scores.shape, bool).at[
        rows, jax.lax.top_k(biased, k)[1]].set(True)
    info = {"margin_rel": (kth - nxt) / kth,
            "own": jnp.ones(h.shape[0], bool),
            "not_followed": jnp.zeros(h.shape[0], bool),
            "swap_rel": jnp.zeros(h.shape[0], F32)}
    if follow is not None:
        theirs = jnp.zeros_like(chosen).at[rows, follow].set(True)
        differs = theirs != chosen
        away = jnp.abs(biased - kth[:, None]) / kth[:, None]
        info["swap_rel"] = jnp.where(differs, away, 0.0).max(-1)
        valid = jnp.all(~differs | (away <= tie_margin), axis=-1) \
            & (theirs.sum(-1) == k)
        changed = jnp.any(differs, axis=-1)
        info["own"] = ~changed
        info["not_followed"] = changed & ~valid
        chosen = jnp.where((changed & valid)[:, None], theirs, chosen)
    weights = jnp.where(chosen, scores, 0.0)
    if m["norm_topk_prob"] and "no_topk_norm" not in ctl:
        weights = weights / (weights.sum(-1, keepdims=True) + ROUTE_EPS)
    info["chosen"] = chosen
    return weights * m["routed_scaling_factor"], info


def experts(h, weights, moe, m: dict):
    """sum over the experts of weights[:, e] * E_e(h), one expert at a
    time."""
    def one(args):
        wg, wu, wd, w_e = args
        y = (jax.nn.silu(h @ _w(wg, m)) * (h @ _w(wu, m))) @ _w(wd, m)
        return y * w_e[:, None]
    return jax.lax.map(one, (
        moe["experts_gate_kernel"], moe["experts_up_kernel"],
        moe["experts_down_kernel"], weights.T)).sum(0)


def layer_forward(x, p, kind: str, m: dict, positions, real=None,
                  follow=None, tie_margin=0.0):
    """One block on x (S, hidden); the routing record is None for a
    dense layer."""
    eps, post = m["norm_eps"], "post_norm" in _controls(m)
    an, mn = p["attn_norm"].astype(F32), p["mlp_norm"].astype(F32)

    def mixer(u):
        if kind == FULL:
            return full_mixer(u, p["attention"], m, positions, real)
        return conv_mixer(u, p["conv"], m)

    info = None

    def ff(h):
        nonlocal info
        if "moe" not in p:
            return swiglu_mlp(h, p["mlp"], m)
        weights, info = routing(h, p["moe"], m, follow, tie_margin)
        return experts(h, weights, p["moe"], m)

    if post:
        x = x + _rms(mixer(x), an, eps)
        return x + _rms(ff(x), mn, eps), info
    x = x + mixer(_rms(x, an, eps))
    return x + ff(_rms(x, mn, eps)), info


def head(x, params, m: dict, block: int = 8192):
    """Final norm and the tied head, a block of the embedding's rows at
    a time."""
    x = _rms(x, params["final_norm"].astype(F32), m["norm_eps"])
    table = params["token_embed"]["embedding"]
    return jnp.concatenate(
        [x @ _w(table[i:i + block], m).T
         for i in range(0, table.shape[0], block)], axis=-1)


def forward(params, tokens, m: dict, follow=None, tie_margin: float = 0.0):
    """Logits (S, vocab) in float32 for one sequence of token ids, and
    per expert layer the routing record (arrays over the S positions).
    `follow`: per expert layer an (S, k) array of a system's chosen
    experts, or None. Under the control "conv_state_to_bucket_end" the
    first `m["prompt_len"]` tokens are followed by token 0 up to
    `m["bucket"]` positions before the rest; the logits and records of
    those positions are cut out again."""
    tokens = jnp.asarray(tokens)
    s = tokens.shape[0]
    positions, real = jnp.arange(s), None
    if "conv_state_to_bucket_end" in _controls(m):
        p, pad = m["prompt_len"], m["bucket"] - m["prompt_len"]
        tokens = jnp.concatenate([tokens[:p], jnp.zeros((pad,), tokens.dtype),
                                  tokens[p:]])
        at = jnp.arange(s + pad)
        real = (at < p) | (at >= p + pad)
        positions = jnp.where(at < p + pad, at, at - pad)
        if follow is not None:
            k = m["num_experts_per_tok"]
            follow = [jnp.concatenate(
                [f[:p], jnp.zeros((pad, k), f.dtype), f[p:]])
                for f in follow]
    with jax.default_matmul_precision("highest"):
        x = params["token_embed"]["embedding"][tokens].astype(F32)
        kinds = m["layer_types"][:m["num_hidden_layers"]]
        # one program a layer kind and feed-forward (jit keys on p's tree)
        steps = {kind: jax.jit(lambda x, p, f, kind=kind: layer_forward(
            x, p, kind, m, positions, real, f, tie_margin))
            for kind in set(kinds)}
        records, n_moe = [], 0
        for i, kind in enumerate(kinds):
            p = params[f"layer_{i}"]
            f = None
            if "moe" in p and follow is not None:
                f = follow[n_moe]
            n_moe += "moe" in p
            x, info = steps[kind](x, p, f)
            if info is not None:
                if real is not None:
                    info = {name: v[real] for name, v in info.items()}
                records.append(info)
        if real is not None:
            x = x[real]
        return head(x, params, m), records


def forward_logits(params, tokens, m: dict):
    return forward(params, tokens, m)[0]
