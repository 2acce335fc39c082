"""Plain reference of the Xing4.0-29B-A4B forward pass (`model_type`
`xing4_0`), kept with the benchmark: jax.numpy in float32 under
`default_matmul_precision("highest")`, the expanded form of latent
attention only: no absorption, no cache, no kernels, no batching. It
imports nothing from the program. Written from the published
config.json (the keys of `m` below are its keys); the attention, the
router and the experts carry DeepSeek-V3's keys, the residual keys are
those of mHC (arXiv:2512.24880).

A token's state is X in R^{n x C}, n = hc_mult, C = hidden_size; X_0 is
the embedding in each of the n rows. Each block has two sub-layers F
(latent attention; then the dense SwiGLU in the leading
`first_k_dense_replace` layers, else routed + shared experts), each with
its own phi ((n^2 + 2n) x nC as stored), b (n^2 + 2n) and a = (a_pre,
a_post, a_res):

    x  = vec(X)
    m  = (phi x) * rsqrt(mean(x^2) + rms_norm_eps)
    Hpre  = sigmoid(a_pre m[0:n] + b[0:n])
    Hpost = 2 sigmoid(a_post m[n:2n] + b[n:2n])
    M     = exp(clip(a_res mat(m[2n:]) + mat(b[2n:]), clamp_min, clamp_max))
    hc_sinkhorn_iters times:  M <- M / (colsum(M) + hc_eps);
                              M <- M / (rowsum(M) + hc_eps)
    h  = sum_i Hpre[i] X[i];  y = F(RMSNorm(h))
    X'[i] = sum_j M[i, j] X[j] + Hpost[i] y

After the last block x_out = sum_i X[i], the final RMSNorm, the untied
head. The Sinkhorn loop is written as a loop over one token's matrix
entries (`_sinkhorn_token`, vmapped over the positions), not as the
program's array expression.

Attention, g = RMSNorm(h): q = W_qb RMSNorm_768(W_qa g), 32 heads of
[128 nope | 64 rope], no norm a head, the rope part rotated;
[c | k_r] = W_dkv g, c <- RMSNorm_512(c), k_r (one for all heads)
rotated; [k_nope_h | v_h] = W_ukv,h c; causal softmax at scale
192^-1/2 (0.1 ln 64 + 1)^2; YaRN frequencies (factor 64 over 4 096
positions, beta 32 / 1).
Experts: s = sigmoid(W_r g) (64 scores, float32); the 4 with the largest
s + bias are selected (n_group = topk_group = 1: no group stage);
w_e = 2 s_e / sum over the 4 selected of s; y = sum_e w_e E_e(g) +
E_shared(g), SwiGLUs of width 1 024.

Departures that change no function computed: experts are applied as a
dense masked sum, one expert at a time (`lax.map`), each cast to float32
by itself; the head in blocks of columns.

Near-ties. As `reference_sarvam`: the reference reports, for every
expert layer and position, the margin between its 4th and 5th biased
score relative to the 4th, and can be told to `follow` a system's
choices where every expert swapped lies within `tie_margin` of its own
4th biased score. A choice outside the margin is not followed and is
counted in `not_followed`.

`m["controls"]` (a set of names, empty in every benchmark run) computes
a deliberately wrong model instead, for the measured controls that the
comparison must fail: "bf16_mapping" (m, the sigmoid arms, exp and the
Sinkhorn loop in bfloat16), "sinkhorn_2_iters", "hpost_without_2",
"no_q_a_norm", "no_shared", "select_without_bias", "no_scaling",
"scale_without_yarn", "int8_weights". (The order inside an iteration
and the clamp are no controls: a converged loop reaches the one doubly
stochastic scaling of M whichever side it starts with, and no logit
reaches the clamp under seeded weights.)

Weights are read from the system's own parameter tree (flax names of
ray_tpu/models/latent_moe.py), one layer at a time.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
CONTROLS = ("bf16_mapping", "sinkhorn_2_iters", "hpost_without_2",
            "no_q_a_norm", "no_shared", "select_without_bias", "no_scaling",
            "scale_without_yarn", "int8_weights")


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


# -- the residual path --------------------------------------------------------
def _sinkhorn_token(mat, m: dict, dt):
    """ONE token's (n, n) matrix, entry by entry: columns, then rows,
    `hc_sinkhorn_iters` times, `hc_eps` added to each normalising sum."""
    n = m["hc_mult"]
    iters = 2 if "sinkhorn_2_iters" in m.get("controls", ()) \
        else m["hc_sinkhorn_iters"]
    eps = jnp.asarray(m["hc_eps"], dt)

    def once(_, mat):
        e = [[mat[i, j] for j in range(n)] for i in range(n)]
        for j in range(n):                          # columns
            total = eps
            for i in range(n):
                total = total + e[i][j]
            for i in range(n):
                e[i][j] = e[i][j] / total
        for i in range(n):                          # then rows
            total = eps
            for j in range(n):
                total = total + e[i][j]
            for j in range(n):
                e[i][j] = e[i][j] / total
        return jnp.stack([jnp.stack(r) for r in e])
    return jax.lax.fori_loop(0, iters, once, mat)


def mappings(x, p, name: str, m: dict):
    """x (S, n*C) float32, one sub-layer's parameters -> Hpre (S, n),
    Hpost (S, n), Hres (S, n, n)."""
    n = m["hc_mult"]
    controls = m.get("controls", ())
    dt = jnp.bfloat16 if "bf16_mapping" in controls else F32
    phi = p[f"hc_{name}_phi"].astype(F32)
    b = p[f"hc_{name}_b"].astype(F32)
    a = p[f"hc_{name}_a"].astype(F32)
    mm = (x @ phi.T) * jax.lax.rsqrt(
        jnp.mean(x * x, -1, keepdims=True) + m["rms_norm_eps"])
    mm, b, a = mm.astype(dt), b.astype(dt), a.astype(dt)
    pre = jax.nn.sigmoid(a[0] * mm[:, :n] + b[:n])
    post = jax.nn.sigmoid(a[1] * mm[:, n:2 * n] + b[n:2 * n])
    if "hpost_without_2" not in controls:
        post = 2 * post
    z = jnp.clip(a[2] * mm[:, 2 * n:] + b[2 * n:],
                 m["mhc_h_res_clamp_min"], m["mhc_h_res_clamp_max"])
    res = jax.vmap(lambda t: _sinkhorn_token(t, m, dt))(
        jnp.exp(z).reshape(-1, n, n))
    return pre.astype(F32), post.astype(F32), res.astype(F32)


def sub_layer(x, p, name: str, m: dict, f):
    """x (S, n*C) -> x' through sub-layer `f` (takes h (S, C), returns
    (y, extra)): returns (x', extra)."""
    n = m["hc_mult"]
    s = x.shape[0]
    streams = x.reshape(s, n, -1)
    pre, post, res = mappings(x, p, name, m)
    h = jnp.einsum("si,sic->sc", pre, streams)
    y, extra = f(h)
    out = (jnp.einsum("sij,sjc->sic", res, streams)
           + post[:, :, None] * y[:, None, :])
    return out.reshape(s, -1), extra


# -- the sub-layers -----------------------------------------------------------
def attention(h, p, m: dict):
    """Latent attention on h (S, hidden), its pre-norm included."""
    s = h.shape[0]
    nh, dn, dr, dv, r = (m["num_attention_heads"], m["qk_nope_head_dim"],
                         m["qk_rope_head_dim"], m["v_head_dim"],
                         m["kv_lora_rank"])
    eps, inv = m["rms_norm_eps"], yarn_inv_freq(m)
    a = p["attention"]
    g = _rms(h, p["attn_norm"].astype(F32), eps)
    qa = g @ _w(a["q_a_proj"]["kernel"], m)
    if "no_q_a_norm" not in m.get("controls", ()):
        qa = _rms(qa, a["q_a_norm"].astype(F32), eps)
    q = (qa @ _w(a["q_b_proj"]["kernel"], m)).reshape(s, nh, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], inv)
    down = g @ _w(a["kv_down_proj"]["kernel"], m)
    c = _rms(down[:, :r], a["kv_norm"].astype(F32), eps)
    k_rope = _rope(down[:, None, r:], inv)[:, 0]                # (S, dr)
    kv = (c @ _w(a["kv_up_kernel"], m)).reshape(s, nh, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
              + jnp.einsum("qhd,kd->hqk", q_rope, k_rope)) * softmax_scale(m)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v)
    return o.reshape(s, nh * dv) @ _w(a["o_proj"]["kernel"], m)


def swiglu_mlp(h, p, m: dict):
    return (jax.nn.silu(h @ _w(p["gate_proj"]["kernel"], m))
            * (h @ _w(p["up_proj"]["kernel"], m))) \
        @ _w(p["down_proj"]["kernel"], m)


def routing(g, moe, m: dict, follow=None, tie_margin=0.0):
    """g: (S, hidden) normed input of the expert layer. Returns the
    (S, n_routed_experts) matrix of routing weights (0 where not chosen)
    and a dict as `reference_sarvam.routing`'s: `chosen`, `margin_rel`,
    `own`, `not_followed`, `swap_rel`."""
    k, controls = m["num_experts_per_tok"], m.get("controls", ())
    scores = jax.nn.sigmoid(g @ moe["router_kernel"].astype(F32))
    biased = scores if "select_without_bias" in controls \
        else scores + moe["router_bias"].astype(F32)
    ranked = jnp.sort(biased, axis=-1)[:, ::-1]
    kth, nxt = ranked[:, k - 1], ranked[:, k]
    rows = jnp.arange(g.shape[0])[:, None]
    chosen = jnp.zeros(scores.shape, bool).at[
        rows, jax.lax.top_k(biased, k)[1]].set(True)
    info = {"margin_rel": (kth - nxt) / kth,
            "own": jnp.ones(g.shape[0], bool),
            "not_followed": jnp.zeros(g.shape[0], bool),
            "swap_rel": jnp.zeros(g.shape[0], F32)}
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
    if m["norm_topk_prob"]:
        weights = weights / weights.sum(-1, keepdims=True)
    if "no_scaling" not in controls:
        weights = weights * m["routed_scaling_factor"]
    info["chosen"] = chosen
    return weights, info


def experts(g, weights, moe, m: dict):
    """sum over the experts of weights[:, e] * E_e(g), one at a time."""
    def one(args):
        wg, wu, wd, w_e = args
        y = (jax.nn.silu(g @ _w(wg, m)) * (g @ _w(wu, m))) @ _w(wd, m)
        return y * w_e[:, None]
    return jax.lax.map(one, (
        moe["experts_gate_kernel"], moe["experts_up_kernel"],
        moe["experts_down_kernel"], weights.T)).sum(0)


def feed_forward(h, p, m: dict, follow=None, tie_margin=0.0):
    """The second sub-layer on h (S, hidden), its pre-norm included;
    the routing record is None for a dense layer."""
    g = _rms(h, p["mlp_norm"].astype(F32), m["rms_norm_eps"])
    if "moe" not in p:
        return swiglu_mlp(g, p["mlp"], m), None
    moe = p["moe"]
    weights, info = routing(g, moe, m, follow, tie_margin)
    y = experts(g, weights, moe, m)
    if "no_shared" not in m.get("controls", ()):
        y = y + swiglu_mlp(g, moe["shared"], m)
    return y, info


def layer_forward(x, p, m: dict, follow=None, tie_margin=0.0):
    """One block on x (S, n * hidden)."""
    x, _ = sub_layer(x, p, "attn", m, lambda h: (attention(h, p, m), None))
    return sub_layer(x, p, "mlp", m,
                     lambda h: feed_forward(h, p, m, follow, tie_margin))


def forward(params, tokens, m: dict, follow=None, tie_margin: float = 0.0):
    """Logits (S, vocab) in float32 for one sequence of token ids, and
    per expert layer the routing record (arrays over the S positions).
    `follow`: per expert layer an (S, k) array of a system's chosen
    experts, or None."""
    n = m["hc_mult"]
    with jax.default_matmul_precision("highest"):
        x = params["token_embed"]["embedding"][tokens].astype(F32)
        x = jnp.concatenate([x] * n, axis=-1)             # X_0: n copies
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
        x = x.reshape(x.shape[0], n, -1).sum(1)
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
