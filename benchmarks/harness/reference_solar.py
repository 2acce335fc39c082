"""Plain reference of the Solar-Open2-250B forward pass (`model_type`
`solar_open2`), kept with the benchmark: jax.numpy in float32 under
`default_matmul_precision("highest")`, the delta-rule layers' recurrence
token by token in a `lax.scan`, plain softmax attention in the full
layers, experts as a dense masked sum: no chunkwise form, no kernel, no
cache, no batching. It imports nothing from the program. Written from
the published config.json (the keys of `m` below are its keys) and, for
the linear layers, from Kimi Linear (arXiv:2510.26692), whose Kimi Delta
Attention the `kda_*` and `linear_attn_config` keys name. Layer i is a
full layer where i is in `gqa_layers` (0, 4, 8, ...) and a KDA layer
otherwise; x_t is the residual stream, u_t = RMSNorm(x_t) a block's
normed input, d = hidden_size = 4 096, eps = rms_norm_eps:

    block   h = x + Mixer(RMSNorm(x)),  y = h + FF(RMSNorm(h))
    KDA     H = 64 heads, d_k = d_v = 128, r = 128, S a head's (d_v x d_k)
            state, S_0 = 0:
            [q~ | k~ | v~]_t = SiLU(sum_{j<K} w_j * (W_qkv u)_{t-j})
                          depthwise, causal, K = 4, zeros before the start
            q_t = q~_t / ||q~_t|| * d_k^-1/2 ,  k_t = k~_t / ||k~_t||
            g_t = -exp(A_log_h) softplus(W_f2 (W_f1 u_t) + dt_bias)
                          in R^(H x d_k): a rate a CHANNEL, alpha = exp(g)
            beta_t = 2 sigmoid(w_b . u_t)        (2: kda_allow_neg_eigval)
            S_t = S_{t-1} Diag(alpha_t) (I - beta_t k_t k_t^T)
                  + beta_t v_t k_t^T ,   o_t = S_t q_t
            y_t = W_o concat_h[RMSNorm_128(o_t,h) * sigmoid(W_g2 (W_g1 u_t))_h]
    full    q, k, v = W_q u, W_k u, W_v u: 64 / 8 / 8 heads of 128, no
            norm, no rotation (use_rope false); causal softmax at
            128^-1/2, 8 query heads a KV head;
            y = W_o [attn * sigmoid(W_gate u)]    (use_gqa_gate)
    FF      s = sigmoid(W_r h) (320 scores); the 8 experts with the
            largest s + b are selected (b in the selection only);
            w_e = s_e / (sum of the selected s + 1e-6) x
            routed_scaling_factor; y = sum over the selected experts
            HELD HERE of w_e E_e(h) + E_shared(h), E a SwiGLU of 1 280
    final RMSNorm, then the untied head.

The share. `m["num_experts"]` experts from `m["expert_first"]` are held
here of the router's `m["router_width"]`; the weights are normalised
over all 8 selected wherever they live. What the absent experts would
add is left out, here as in the program. `m["vocab_size"]` is the slice
of the vocabulary held.

Departures from the catalog's row, and readings of what the config has
no key for (the configuration file's `assumed`, kept by the program
alike): r = 128 (the head's width, the family's convention);
`kda_use_full_proj: false` read as the two low-rank pairs above; W_q,
W_k and W_v of a KDA layer are the three column blocks of one W_qkv and
their convolutions the blocks of one depthwise kernel (the same
function); the full layers' gate elementwise over 64 x 128 from a
projection of its own; pre-norm blocks; sigmoid scores with a selection
bias and the 1e-6 in the renormalisation; no expert groups; a float32
state; no convolution bias; one learned weight of 128 on the output
norm shared by the heads; 1e-6 inside the root of the L2 norms.

Departures that change no function computed: experts one at a time
(`lax.map`), each cast to float32 by itself; attention a KV head at a
time; the head in blocks of columns: so that the reference fits beside
the served model.

Near-ties. As `reference_sarvam`: the reference reports, for every
layer and position, the margin between its 8th and 9th biased score
relative to the 8th, and can be told to `follow` a system's choices
where every expert swapped lies within `tie_margin` of its own 8th
biased score. A choice outside the margin is not followed and is
counted in `not_followed`.

`m["controls"]` (a set of names, empty in every benchmark run) computes
a deliberately wrong model instead, for the measured controls that the
comparison must fail: "bf16_state" (the state rounded to bfloat16 after
every token: under a bf16 model's own rounding it moves the logits by a
tenth of their error, so it is read where nothing hides it:
`kda_recurrence` on what the engine's own step programs fed the first
delta-rule layer's recurrence, against what they got from it:
`replica_solar.compare`'s `recurrence`),
"scalar_decay" (every channel of a head decays at the
mean of the head's rates: the sibling layer's function),
"beta_without_2", "no_decay" (alpha = 1), "state_to_bucket_end" (the
prompt padded with token 0 to `m["bucket"]` positions and the
recurrence and convolution run over the padding, which attention does
not see: what a prefill that does not stop its state at the prompt's
true length computes), "no_out_gate" (the full layers'), "no_shared",
"norm_over_held", "int8_weights" (every matmul weight rounded to 8 bits
with one scale per output column).

Weights are read from the system's own parameter tree (flax names of
ray_tpu/models/hybrid.py), one layer at a time. Its convolution kernel
is (K, C) with row 0 on the current token.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
FULL, KDA = "full_attention", "kda"
ROUTE_EPS = 1e-6
L2_EPS = 1e-6
# the deliberately wrong models of `m["controls"]` (docstring above)
CONTROLS = ("bf16_state", "scalar_decay", "beta_without_2", "no_decay",
            "state_to_bucket_end", "no_out_gate", "no_shared",
            "norm_over_held", "int8_weights")


def _controls(m: dict) -> frozenset:
    return frozenset(m.get("controls", ()))


def layer_types(m: dict) -> list:
    """One entry a layer held, from `gqa_layers`."""
    full = set(m["gqa_layers"])
    return [FULL if i in full else KDA
            for i in range(m["num_hidden_layers"])]


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


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def kda_inputs(u, p, m: dict):
    """What one KDA layer's recurrence reads, from its normed input u
    (S, d): q, k (S, H, d_k) normalised, v (S, H, d_v), g = log alpha
    (S, H, d_k), beta (S, H)."""
    ctl, lin = _controls(m), m["linear_attn_config"]
    s = u.shape[0]
    h, dk, kk = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    qkv = u @ _w(p["qkv_proj"]["kernel"], m)
    w = p["conv_kernel"].astype(F32)                      # (K, C), w[0] now
    padded = jnp.concatenate([jnp.zeros((kk - 1, qkv.shape[1]), F32), qkv])
    conv = jax.nn.silu(sum(w[j] * padded[kk - 1 - j:kk - 1 - j + s]
                           for j in range(kk)))
    q = _l2(conv[:, :h * dk].reshape(s, h, dk)) * dk ** -0.5
    k = _l2(conv[:, h * dk:2 * h * dk].reshape(s, h, dk))
    v = conv[:, 2 * h * dk:].reshape(s, h, dk)
    a = (u @ _w(p["f_a_proj"]["kernel"], m)) @ _w(p["f_b_proj"]["kernel"], m)
    g = -jnp.exp(p["A_log"].astype(F32))[:, None] * jax.nn.softplus(
        a.reshape(s, h, dk) + p["dt_bias"].astype(F32))
    if "scalar_decay" in ctl:
        g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
    if "no_decay" in ctl:
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(u @ _w(p["b_proj"]["kernel"], m))
    if m["kda_allow_neg_eigval"] and "beta_without_2" not in ctl:
        beta = 2.0 * beta
    return q, k, v, g, beta


def kda_recurrence(q, k, v, g, beta, m: dict):
    """The recurrence itself, token by token from S_0 = 0: o (S, H, d_v)
    and the last state (H, d_v, d_k). No mask: it runs over whatever it
    is given, which is the point of the control that pads the prompt."""
    ctl = _controls(m)
    h, dk, dv = q.shape[1], q.shape[2], v.shape[2]

    def token(state, xs):                                 # (H, d_v, d_k)
        qt, kt, vt, at, bt = xs
        state = state * at[:, None, :]
        sk = jnp.einsum("hvk,hk->hv", state, kt)
        state = state + (bt[:, None] * (vt - sk))[:, :, None] \
            * kt[:, None, :]
        if "bf16_state" in ctl:
            # bfloat16's 8 exponent and 7 mantissa bits, as an operation
            # of its own: a compiler may drop a cast there and back
            state = jax.lax.reduce_precision(state, 8, 7)
        return state, jnp.einsum("hvk,hk->hv", state, qt)

    last, o = jax.lax.scan(token, jnp.zeros((h, dv, dk), F32),
                           (q, k, v, jnp.exp(g), beta))
    return o, last


def kda_mixer(u, p, m: dict):
    """One KDA layer on u (S, d), the mixer's normed input."""
    s = u.shape[0]
    o, _ = kda_recurrence(*kda_inputs(u, p, m), m)
    o = _rms(o, p["o_norm"].astype(F32), m["rms_norm_eps"])
    gate = jax.nn.sigmoid((u @ _w(p["g_a_proj"]["kernel"], m))
                          @ _w(p["g_b_proj"]["kernel"], m))
    return (o.reshape(s, -1) * gate) @ _w(p["o_proj"]["kernel"], m)


def full_mixer(u, p, m: dict, real=None):
    """Grouped-query attention on u (S, d): no norm, no rotation, an
    output gate. Keys that are not `real` are seen by no query but
    themselves."""
    s = u.shape[0]
    nh, nkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    q = (u @ _w(p["q_proj"]["kernel"], m)).reshape(s, nh, hd)
    k = (u @ _w(p["k_proj"]["kernel"], m)).reshape(s, nkv, hd)
    v = (u @ _w(p["v_proj"]["kernel"], m)).reshape(s, nkv, hd)
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
    if m["use_gqa_gate"] and "no_out_gate" not in _controls(m):
        attn = attn * jax.nn.sigmoid(u @ _w(p["gate_proj"]["kernel"], m))
    return attn @ _w(p["o_proj"]["kernel"], m)


def swiglu_mlp(h, p, m: dict):
    return (jax.nn.silu(h @ _w(p["gate_proj"]["kernel"], m))
            * (h @ _w(p["up_proj"]["kernel"], m))) \
        @ _w(p["down_proj"]["kernel"], m)


def routing(h, moe, m: dict, follow=None, tie_margin=0.0):
    """h: (S, hidden) normed input of the expert layer. Returns the
    (S, router_width) matrix of routing weights over ALL experts (0 where
    not chosen) and a dict as `reference_sarvam.routing`'s: `chosen`,
    `margin_rel`, `own`, `not_followed`, `swap_rel`."""
    k, ctl = m["num_experts_per_tok"], _controls(m)
    scores = jax.nn.sigmoid(h @ moe["router_kernel"].astype(F32))
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
    if m["norm_topk_prob"]:
        if "norm_over_held" in ctl:
            lo = m["expert_first"]
            held = jnp.zeros(scores.shape[-1], bool).at[
                lo:lo + m["num_experts"]].set(True)
            total = jnp.where(held, weights, 0.0).sum(-1, keepdims=True)
        else:
            total = weights.sum(-1, keepdims=True)
        weights = weights / (total + ROUTE_EPS)
    info["chosen"] = chosen
    return weights * m["routed_scaling_factor"], info


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


def expert_layer(h, moe, m: dict, follow=None, tie_margin=0.0):
    """The feed-forward on its normed input h (S, hidden): this share's
    part of the routed sum and the shared expert; the routing record."""
    weights, info = routing(h, moe, m, follow, tie_margin)
    lo = m["expert_first"]
    y = experts(h, weights[:, lo:lo + m["num_experts"]], moe, m)
    if m["n_shared_experts"] and "no_shared" not in _controls(m):
        y = y + swiglu_mlp(h, moe["shared"], m)
    return y, info


def layer_forward(x, p, kind: str, m: dict, real=None, follow=None,
                  tie_margin=0.0):
    """One block on x (S, hidden) and its routing record."""
    eps = m["rms_norm_eps"]
    u = _rms(x, p["attn_norm"].astype(F32), eps)
    x = x + (full_mixer(u, p["attention"], m, real) if kind == FULL
             else kda_mixer(u, p["kda"], m))
    y, info = expert_layer(_rms(x, p["mlp_norm"].astype(F32), eps),
                           p["moe"], m, follow, tie_margin)
    return x + y, info


def head(x, params, m: dict, block: int = 8192):
    """Final norm and the untied head, a block of columns at a time."""
    x = _rms(x, params["final_norm"].astype(F32), m["rms_norm_eps"])
    kernel = params["lm_head"]["kernel"]
    return jnp.concatenate(
        [x @ _w(kernel[:, j:j + block], m)
         for j in range(0, kernel.shape[1], block)], axis=-1)


def forward(params, tokens, m: dict, follow=None, tie_margin: float = 0.0):
    """Logits (S, vocab) in float32 for one sequence of token ids, and
    per layer the routing record (arrays over the S positions).
    `follow`: per layer an (S, k) array of a system's chosen experts, or
    None. Under the control "state_to_bucket_end" the first
    `m["prompt_len"]` tokens are followed by token 0 up to `m["bucket"]`
    positions before the rest; the logits and records of those positions
    are cut out again."""
    tokens = jnp.asarray(tokens)
    real = None
    if "state_to_bucket_end" in _controls(m):
        p, pad = m["prompt_len"], m["bucket"] - m["prompt_len"]
        tokens = jnp.concatenate([tokens[:p], jnp.zeros((pad,), tokens.dtype),
                                  tokens[p:]])
        at = jnp.arange(tokens.shape[0])
        real = (at < p) | (at >= p + pad)
        if follow is not None:
            k = m["num_experts_per_tok"]
            follow = [jnp.concatenate(
                [f[:p], jnp.zeros((pad, k), f.dtype), f[p:]])
                for f in follow]
    with jax.default_matmul_precision("highest"):
        x = params["token_embed"]["embedding"][tokens].astype(F32)
        kinds = layer_types(m)
        # one program a layer kind (jit keys on p's tree)
        steps = {kind: jax.jit(lambda x, p, f, kind=kind: layer_forward(
            x, p, kind, m, real, f, tie_margin)) for kind in set(kinds)}
        records = []
        for i, kind in enumerate(kinds):
            x, info = steps[kind](x, params[f"layer_{i}"],
                                  None if follow is None else follow[i])
            if real is not None:
                info = {name: v[real] for name, v in info.items()}
            records.append(info)
        if real is not None:
            x = x[real]
        return head(x, params, m), records


def forward_logits(params, tokens, m: dict):
    return forward(params, tokens, m)[0]
