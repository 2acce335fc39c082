"""Plain reference of the sarvam-105b forward pass (`model_type`
`sarvam_mla`), kept with the benchmark: jax.numpy in float32 under
`default_matmul_precision("highest")`, the expanded form of latent
attention only: no absorption, no cache, no kernels, no batching. It
imports nothing from the program. Written from the published
config.json (the keys of `m` below are its keys):

    RMSNorm(x) = h
    q = W_q h: 64 heads of 192 = [128 nope | 64 rope]
    [c | k_r] = W_dkv h (512 + 64); c <- RMSNorm_512(c) (learned weight);
      k_r <- RoPE_p(k_r), ONE rope key for all heads; q_rope <- RoPE_p
    [k_nope_h | v_h] = W_ukv,h c (128 + 128 a head)
    score_h(t, s) = (q_nope_h . k_nope_h,s + q_rope_h . k_r,s) x scale,
      causal softmax, o_h = sum_s p_s v_h,s, out = W_o [o_1 .. o_64]
    scale = 192^-1/2 x m^2, m = 0.1 x mscale_all_dim x ln(factor) + 1
    RoPE frequencies are YaRN's (`deepseek_yarn`): a blend of
      theta^(-2i/64) and that over `factor`, the linear ramp between the
      correction dimensions of beta_fast and beta_slow over the original
      positions; with mscale = mscale_all_dim the tables carry factor 1
    layer 0: dense SwiGLU of width 16 384
    layers 1..: s = sigmoid(W_r h) (128 scores, float32); the 8 experts
      with the largest s + b are selected (b enters the selection only);
      w_e = 2.5 x s_e / sum over the 8 selected of s;
      y = sum_e w_e E_e(h) + E_shared(h), all SwiGLUs of width 2 048
    final RMSNorm -> untied head.

The ONE departure that rests on inference, `use_qk_norm: true`: the
config does not say where the norms sit. Read here, and in the program,
as the RMSNorm over the 512-wide latent before it is cached and
expanded (the k side) and a learned RMSNorm over each head's 192-wide
query before its rope part is rotated (the q side, one weight of 192
shared by the heads). A norm over each head's expanded key is not taken:
the cache the config declares (576 = 512 + 64) could not serve it.
Further readings of keys the config lacks (the configuration file's
`assumed`): sigmoid scores, renormalisation over the selected, no expert
groups, and the half-split pairing of rotated columns (with seeded
weights a relabelling of W_q's and W_dkv's columns).

The share. `m["num_experts"]` experts from `m["expert_first"]` are held
here of the router's `m["router_width"]`; the weights are normalised over
all 8 selected wherever they live, and the layer's result is
`sum over selected experts held here of w_e E_e(h) + E_shared(h)`. What
the absent experts would add is left out, here as in the program.
`m["vocab_size"]` is the slice of the vocabulary held.

Departures that change no function computed: experts are applied as a
dense masked sum, one expert at a time (`lax.map`), each cast to float32
by itself, so that the reference fits beside the served model.

Near-ties. As `reference_olmoe`: the reference reports, for every layer
and position, the margin between its 8th and 9th biased score relative
to the 8th, and can be told to `follow` a system's choices where every
expert swapped lies within `tie_margin` of its own 8th biased score. A
choice outside the margin is not followed and is counted in
`not_followed`.

`m["controls"]` (a set of names, empty in every benchmark run) computes
a deliberately wrong model instead, for the measured controls that the
comparison must fail: "no_shared", "select_without_bias", "no_scaling",
"norm_over_held", "no_latent_norm", "scale_without_yarn",
"int8_weights".

Weights are read from the system's own parameter tree (flax names of
ray_tpu/models/latent_moe.py), one layer at a time.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def softmax_scale(m: dict) -> float:
    y = m["rope_scaling"]
    mm = 0.1 * y["mscale_all_dim"] * math.log(y["factor"]) + 1.0
    if "scale_without_yarn" in m.get("controls", ()):
        mm = 1.0
    return (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]) ** -0.5 * mm * mm


def yarn_inv_freq(m: dict):
    """(rope_dim / 2,) rotation frequencies."""
    y, d, theta = m["rope_scaling"], m["qk_rope_head_dim"], m["rope_theta"]

    def correction_dim(turns):
        return d * math.log(y["original_max_position_embeddings"]
                            / (turns * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(correction_dim(y["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(y["beta_slow"])), d - 1)
    i = jnp.arange(d // 2, dtype=F32)
    plain = theta ** (-2.0 * i / d)
    ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return plain * (1.0 - ramp) + plain / y["factor"] * ramp


def _rope(x, inv_freq):
    # x: (S, H, D); rotate halves (x1, x2) by position * inv_freq
    s, _h, d = x.shape
    ang = jnp.arange(s, dtype=F32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _w(kernel, m: dict):
    """A matmul kernel in float32 (control "int8_weights": rounded to 8
    bits with one scale per output column first)."""
    w = kernel.astype(F32)
    if "int8_weights" in m.get("controls", ()):
        scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
        w = jnp.round(w / scale) * scale
    return w


def attention(x, p, m: dict):
    """The attention half of a layer on x (S, hidden): returns x + attn."""
    s = x.shape[0]
    nh, dn, dr, dv, r = (m["num_attention_heads"], m["qk_nope_head_dim"],
                         m["qk_rope_head_dim"], m["v_head_dim"],
                         m["kv_lora_rank"])
    eps, inv = m["rms_norm_eps"], yarn_inv_freq(m)
    a = p["attention"]
    h = _rms(x, p["attn_norm"].astype(F32), eps)
    q = (h @ _w(a["q_proj"]["kernel"], m)).reshape(s, nh, dn + dr)
    q = _rms(q, a["q_norm"].astype(F32), eps)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], inv)
    down = h @ _w(a["kv_down_proj"]["kernel"], m)
    c = down[:, :r]
    if "no_latent_norm" not in m.get("controls", ()):
        c = _rms(c, a["kv_norm"].astype(F32), eps)
    k_rope = _rope(down[:, None, r:], inv)[:, 0]                # (S, dr)
    kv = (c @ _w(a["kv_up_kernel"], m)).reshape(s, nh, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
              + jnp.einsum("qhd,kd->hqk", q_rope, k_rope)) * softmax_scale(m)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    return x + o.reshape(s, nh * dv) @ _w(a["o_proj"]["kernel"], m)


def swiglu_mlp(h, p, m: dict):
    return (jax.nn.silu(h @ _w(p["gate_proj"]["kernel"], m))
            * (h @ _w(p["up_proj"]["kernel"], m))) \
        @ _w(p["down_proj"]["kernel"], m)


def routing(h, moe, m: dict, follow=None, tie_margin=0.0):
    """h: (S, hidden) normed input of the expert layer. Returns the
    (S, router_width) matrix of routing weights over ALL experts (0 where
    not chosen) and a dict as `reference_olmoe.routing`'s: `chosen`,
    `margin_rel`, `own`, `not_followed`, `swap_rel`."""
    k, controls = m["num_experts_per_tok"], m.get("controls", ())
    scores = jax.nn.sigmoid(h @ moe["router_kernel"].astype(F32))
    biased = scores if "select_without_bias" in controls \
        else scores + moe["router_bias"].astype(F32)
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
    if "norm_over_held" in controls:
        lo = m["expert_first"]
        held = jnp.zeros(scores.shape[-1], bool).at[
            lo:lo + m["num_experts"]].set(True)
        total = jnp.where(held, weights, 0.0).sum(-1, keepdims=True)
        weights = weights / jnp.where(total == 0.0, 1.0, total)
    else:
        weights = weights / weights.sum(-1, keepdims=True)
    if "no_scaling" not in controls:
        weights = weights * m["routed_scaling_factor"]
    info["chosen"] = chosen
    return weights, info


def experts(h, weights, moe, m: dict):
    """sum over the experts held of weights[:, e] * E_e(h), one expert at
    a time; `weights` (S, held) are the held experts' columns."""
    def one(args):
        wg, wu, wd, w_e = args
        y = (jax.nn.silu(h @ _w(wg, m)) * (h @ _w(wu, m))) @ _w(wd, m)
        return y * w_e[:, None]
    return jax.lax.map(one, (
        moe["experts_gate_kernel"], moe["experts_up_kernel"],
        moe["experts_down_kernel"], weights.T)).sum(0)


def layer_forward(x, p, m: dict, follow=None, tie_margin=0.0):
    """One layer on x (S, hidden); the routing record is None for a
    dense layer."""
    x = attention(x, p, m)
    h = _rms(x, p["mlp_norm"].astype(F32), m["rms_norm_eps"])
    if "moe" not in p:
        return x + swiglu_mlp(h, p["mlp"], m), None
    moe = p["moe"]
    weights, info = routing(h, moe, m, follow, tie_margin)
    lo = m["expert_first"]
    y = experts(h, weights[:, lo:lo + m["num_experts"]], moe, m)
    if "no_shared" not in m.get("controls", ()):
        y = y + swiglu_mlp(h, moe["shared"], m)
    return x + y, info


def forward(params, tokens, m: dict, follow=None, tie_margin: float = 0.0):
    """Logits (S, vocab) in float32 for one sequence of token ids, and
    per expert layer the routing record (arrays over the S positions).
    `follow`: per expert layer an (S, k) array of a system's chosen
    experts, or None."""
    with jax.default_matmul_precision("highest"):
        x = params["token_embed"]["embedding"][tokens].astype(F32)
        step = jax.jit(lambda x, p, f: layer_forward(x, p, m, f,
                                                     tie_margin))
        records, n_moe = [], 0
        for i in range(m["num_hidden_layers"]):
            p = params[f"layer_{i}"]
            f = None
            if "moe" in p and follow is not None:
                f = follow[n_moe]
            n_moe += "moe" in p
            x, info = step(x, p, f)
            if info is not None:
                records.append(info)
        x = _rms(x, params["final_norm"].astype(F32), m["rms_norm_eps"])
        head = params["lm_head"]["kernel"]
        # the head in blocks of columns, so that its float32 copy fits
        block = 8192
        logits = jnp.concatenate(
            [x @ _w(head[:, j:j + block], m)
             for j in range(0, head.shape[1], block)], axis=-1)
        return logits, records


def forward_logits(params, tokens, m: dict):
    return forward(params, tokens, m)[0]
