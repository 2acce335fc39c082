"""Plain reference of the Nemotron-3-Super-120B-A12B forward pass
(`model_type` `nemotron_h`), kept with the benchmark: jax.numpy in
float32 under `default_matmul_precision("highest")`, the state-space
layers' recurrence token by token in a `lax.scan`, plain softmax
attention, experts as a dense masked sum: no chunkwise form, no kernel,
no cache, no batching. It imports nothing from the program. Written from
the published config.json (the keys of `m` below are its keys) and, for
the `M` layers, from Mamba-2 (Dao, Gu, arXiv:2405.21060), whose keys
`mamba_num_heads`, `mamba_head_dim`, `ssm_state_size`, `n_groups`,
`conv_kernel`, `use_conv_bias` name.

Each published layer is ONE sub-layer behind one RMSNorm and one
residual add, h <- h + Mixer_i(RMSNorm(h)), its kind read from
`hybrid_override_pattern` (`M` Mamba-2, `*` attention, `E` experts); one
function a layer here, in the published indexing. With x the layer's
normed input, d = hidden_size = 4 096, eps = layer_norm_epsilon = 1e-5:

    M   H = 128 heads of P = 64 (8 192 inner), G = n_groups = 8,
        N = ssm_state_size = 128, K = conv_kernel = 4:
        [z | u | dt~] = W_in x       z in R^8192, u in R^(8192 + 2 G N),
                                     dt~ in R^128
        u' = SiLU(sum_{j<K} w_j * u_{t-j} + b_conv)   depthwise, causal,
                                     zeros before the start, WITH bias
        [xs | B | C] = u'            xs (H, P); B, C (G, N); head h
                                     reads group h // (H / G)
        dt_h = softplus(dt~_h + dt_bias_h), a_h = exp(dt_h A_h),
        A_h = -exp(A_log_h)          one scalar a head
        S_h <- a_h S_h + (dt_h xs_h) B_g^T    S_h (P x N) float32, S = 0
        y_h = S_h C_g + D_h xs_h
        out = W_out RMSNorm_G(y * SiLU(z))    the gate FIRST, then an
                                     RMSNorm over each of the G groups of
                                     H P / G = 1 024 channels, one
                                     weight of 8 192; no projection bias
    *   q = W_q x (32 x 128), k, v = W_k x, W_v x (2 x 128);
        out = W_o softmax(q k^T / sqrt(128)) v, causal, 16 query heads a
        KV head, no bias and NO rotation
    E   s = sigmoid(W_r x) in R^512 (float32); the 22 experts with the
        largest s + b are chosen (b the selection bias; n_group =
        topk_group = 1: no group limit);
        w_i = 5 s_i / (sum of the chosen s + 1e-20)
        l = W_dn x in R^1024 (moe_latent_size)
        e_i(l) = W2_i relu(W1_i l)^2, W1_i 2 688 x 1 024   (NOT gated)
        out = W_up(sum over the chosen experts HELD HERE of w_i e_i(l))
              + V2 relu(V1 x)^2      the shared expert, 5 376 wide, at
                                     FULL width
    final RMSNorm, then the untied head.

The published count from these equations: an `M` layer 4 096 x 18 560
+ 8 192 x 4 096 + 4 x 10 240 + 10 240 + 3 x 128 + 8 192 = 109.6 M; the
`*` layer 4 096 x (4 096 + 2 x 256) + 4 096 x 4 096 = 35.7 M; an expert
2 x 1 024 x 2 688 = 5.505 M; an `E` layer beside its experts 54.5 M
(router 2.1 M, the latent pair 8.4 M, the shared expert 44.0 M); 40 x
109.6 + 8 x 35.7 + 40 x (54.5 + 512 x 5.505) + 2 x 131 072 x 4 096 =
120.7 B in all, 12.8 B a token: "120B-A12B".

The share. `m["num_experts"]` experts from `m["expert_first"]` are held
here of the router's `m["router_width"]`; the weights are normalised
over all 22 chosen wherever they live. What the absent experts would
add is left out, here as in the program; the up-projection is linear,
so a share's partial sum is projected on its own. `m["vocab_size"]` is
the slice of the vocabulary held.

Readings of what the config has no key for (the configuration file's
`assumed`, kept by the program alike): no rotation (`rope_theta` is
published and unused: the family applies no positional embedding); the
gate before the grouped norm; the router and the shared expert at full
width and only the routed experts in the latent; the state in float32;
1e-20 in the renormalisation; W_z, W_u, W_dt the three column blocks of
one W_in. The multi-token-prediction module
(`num_nextn_predict_layers` 1) is not built: it adds a draft head, not
a term of the next-token logits.

Departures that change no function computed: experts one at a time
(`lax.map`), each cast to float32 by itself; attention a KV head at a
time; the head in blocks of columns: so that the reference fits beside
the served model. Weights are read from the system's own parameter tree
(flax names of ray_tpu/models/hybrid.py), whose blocks hold a mixer
layer and the expert layer behind it: `published_layers` undoes that
pairing from the pattern alone. Its convolution kernel is (K, C) with
row 0 on the current token.

Near-ties. As `reference_solar`: the reference reports, for every `E`
layer and position, the margin between its 22nd and 23rd biased score
relative to the 22nd, and can be told to `follow` a system's choices
where every expert swapped lies within `tie_margin` of its own 22nd
biased score. A choice outside the margin is not followed and is
counted in `not_followed`.

`m["controls"]` (a set of names, empty in every benchmark run) computes
a deliberately wrong model instead, for the measured controls that the
comparison must fail: "bf16_state" (the state rounded to bfloat16 after
every token; read where nothing hides it: `ssm_recurrence` on what the
engine's own step programs fed the first `M` layer's recurrence, against
what they got from it), "no_decay" (a = 1), "dt_without_bias", "no_D",
"norm_before_gate" (RMSNorm_G(y) * SiLU(z)), "one_norm_group" (one norm
over all 8 192), "bc_head_modulo" (head h reads group h mod G: B and C
sliced otherwise), "no_conv_bias", "relu_not_squared" (every expert,
the shared one too), "shared_in_latent" (the shared expert reads the
latent's round trip W_up W_dn x instead of x: the one reading of "in the
latent" its 4 096-wide weights allow), "no_scaling" (5 left out),
"norm_over_held", "bias_in_weights" (w from s + b), "rope_10000" (q and
k rotated at `rope_theta`), "state_to_bucket_end" (the prompt padded
with token 0 to `m["bucket"]` positions and the recurrence and
convolution run over the padding, which attention does not see),
"int8_weights" (every matmul weight rounded to 8 bits with one scale
per output column).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
ROUTE_EPS = 1e-20
CONTROLS = ("bf16_state", "no_decay", "dt_without_bias", "no_D",
            "norm_before_gate", "one_norm_group", "bc_head_modulo",
            "no_conv_bias", "relu_not_squared", "shared_in_latent",
            "no_scaling", "norm_over_held", "bias_in_weights",
            "rope_10000", "state_to_bucket_end", "int8_weights")


def _controls(m: dict) -> frozenset:
    return frozenset(m.get("controls", ()))


def published_layers(params, m: dict) -> list:
    """One (kind, norm weight, parameters) a PUBLISHED layer, in the
    pattern's order, out of the program's blocks: a mixer opens a block
    (`attn_norm` and `mamba2` or `attention`), an `E` is the expert
    layer of the block its mixer opened (`mlp_norm`, `moe`)."""
    out, block = [], -1
    for c in m["hybrid_override_pattern"]:
        if c in "M*":
            block += 1
            p = params[f"layer_{block}"]
            out.append((c, p["attn_norm"],
                        p["mamba2" if c == "M" else "attention"]))
        elif c == "E":
            p = params[f"layer_{block}"]
            out.append((c, p["mlp_norm"], p["moe"]))
        else:
            raise ValueError(f"pattern {m['hybrid_override_pattern']!r}: "
                             f"{c!r} is none of M, *, E")
    return out


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


def _relu2(x, m: dict):
    r = jax.nn.relu(x)
    return r if "relu_not_squared" in _controls(m) else r * r


def ssm_inputs(u, p, m: dict):
    """What one `M` layer's recurrence reads, from its normed input u
    (S, d): C, B (S, G, N), xs (S, H, P), g = log a = dt A and dt (S, H);
    and the gate z (S, H P)."""
    ctl = _controls(m)
    s = u.shape[0]
    h, pd, n, grp, kk = (m["mamba_num_heads"], m["mamba_head_dim"],
                         m["ssm_state_size"], m["n_groups"],
                         m["conv_kernel"])
    inner = h * pd
    proj = u @ _w(p["in_proj"]["kernel"], m)
    z, xbc, dt_raw = (proj[:, :inner], proj[:, inner:-h], proj[:, -h:])
    w = p["conv_kernel"].astype(F32)                      # (K, C), w[0] now
    padded = jnp.concatenate([jnp.zeros((kk - 1, xbc.shape[1]), F32), xbc])
    conv = sum(w[j] * padded[kk - 1 - j:kk - 1 - j + s] for j in range(kk))
    if m["use_conv_bias"] and "no_conv_bias" not in ctl:
        conv = conv + p["conv_bias"].astype(F32)
    conv = jax.nn.silu(conv)
    xs = conv[:, :inner].reshape(s, h, pd)
    bm = conv[:, inner:inner + grp * n].reshape(s, grp, n)
    cm = conv[:, inner + grp * n:].reshape(s, grp, n)
    dt = jax.nn.softplus(dt_raw if "dt_without_bias" in ctl
                         else dt_raw + p["dt_bias"].astype(F32))
    g = -jnp.exp(p["A_log"].astype(F32)) * dt
    if "no_decay" in ctl:
        g = jnp.zeros_like(g)
    return (cm, bm, xs, g, dt), z


def ssm_recurrence(cm, bm, xs, g, dt, m: dict):
    """The recurrence itself, token by token from S = 0: y (S, H, P)
    WITHOUT the skip and the last state (H, P, N). No mask: it runs over
    whatever it is given, which is the point of the control that pads
    the prompt."""
    ctl = _controls(m)
    h, pd = xs.shape[1:]
    grp, n = bm.shape[1:]
    of_head = (jnp.arange(h) % grp if "bc_head_modulo" in ctl
               else jnp.arange(h) // (h // grp))

    def token(state, inp):                                # (H, P, N)
        ct, bt, xt, at, dtt = inp
        state = state * at[:, None, None] \
            + (dtt[:, None] * xt)[:, :, None] * bt[of_head][:, None, :]
        if "bf16_state" in ctl:
            # bfloat16's 8 exponent and 7 mantissa bits, as an operation
            # of its own: a compiler may drop a cast there and back
            state = jax.lax.reduce_precision(state, 8, 7)
        return state, jnp.einsum("hpn,hn->hp", state, ct[of_head])

    last, y = jax.lax.scan(token, jnp.zeros((h, pd, n), F32),
                           (cm, bm, xs, jnp.exp(g), dt))
    return y, last


def mamba_layer(u, p, m: dict):
    """One `M` layer on its normed input u (S, d)."""
    ctl = _controls(m)
    s = u.shape[0]
    (cm, bm, xs, g, dt), z = ssm_inputs(u, p, m)
    y, _ = ssm_recurrence(cm, bm, xs, g, dt, m)
    if "no_D" not in ctl:
        y = y + p["D"].astype(F32)[:, None] * xs
    y = y.reshape(s, -1)
    groups = 1 if "one_norm_group" in ctl else m["n_groups"]
    eps, w = m["layer_norm_epsilon"], p["norm"].astype(F32)

    def group_norm(a):
        ag = a.reshape(s, groups, -1)
        return (ag * jax.lax.rsqrt(jnp.mean(ag * ag, -1, keepdims=True)
                                   + eps)).reshape(s, -1) * w
    y = (group_norm(y) * jax.nn.silu(z) if "norm_before_gate" in ctl
         else group_norm(y * jax.nn.silu(z)))
    return y @ _w(p["out_proj"]["kernel"], m)


def _rotate(x, theta: float):
    """x (S, heads, D) rotated by position, halves paired."""
    s, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention_layer(u, p, m: dict, real=None):
    """Grouped-query attention on u (S, d): no norm, no rotation, no
    gate. Keys that are not `real` are seen by no query but
    themselves."""
    s = u.shape[0]
    nh, nkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    q = (u @ _w(p["q_proj"]["kernel"], m)).reshape(s, nh, hd)
    k = (u @ _w(p["k_proj"]["kernel"], m)).reshape(s, nkv, hd)
    v = (u @ _w(p["v_proj"]["kernel"], m)).reshape(s, nkv, hd)
    if "rope_10000" in _controls(m):
        q, k = _rotate(q, m["rope_theta"]), _rotate(k, m["rope_theta"])
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


def routing(h, moe, m: dict, follow=None, tie_margin=0.0):
    """h: (S, hidden) normed input of the expert layer. Returns the
    (S, router_width) matrix of routing weights over ALL experts (0 where
    not chosen) and a dict as `reference_solar.routing`'s: `chosen`,
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
    weights = jnp.where(chosen, biased if "bias_in_weights" in ctl
                        else scores, 0.0)
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
    scale = 1.0 if "no_scaling" in ctl else m["routed_scaling_factor"]
    return weights * scale, info


def experts(lat, weights, moe, m: dict):
    """sum over the experts held of weights[:, e] * e_e(lat), one expert
    at a time, IN THE LATENT; `weights` (S, held) are the held experts'
    columns."""
    def one(args):
        w1, w2, w_e = args
        return (_relu2(lat @ _w(w1, m), m) @ _w(w2, m)) * w_e[:, None]
    return jax.lax.map(one, (moe["experts_up_kernel"],
                             moe["experts_down_kernel"], weights.T)).sum(0)


def expert_layer(h, moe, m: dict, follow=None, tie_margin=0.0):
    """One `E` layer on its normed input h (S, hidden): this share's
    part of the routed sum, computed in the latent and projected up, and
    the shared expert at full width; the routing record."""
    weights, info = routing(h, moe, m, follow, tie_margin)
    lo = m["expert_first"]
    lat = h @ _w(moe["latent_down_proj"]["kernel"], m)
    w_up = _w(moe["latent_up_proj"]["kernel"], m)
    y = experts(lat, weights[:, lo:lo + m["num_experts"]], moe, m) @ w_up
    seen = lat @ w_up if "shared_in_latent" in _controls(m) else h
    shared = moe["shared"]
    return y + _relu2(seen @ _w(shared["up_proj"]["kernel"], m), m) \
        @ _w(shared["down_proj"]["kernel"], m), info


def layer_forward(x, kind: str, norm_w, p, m: dict, real=None, follow=None,
                  tie_margin=0.0):
    """One PUBLISHED layer on the residual stream x (S, hidden): its
    result and, for an `E` layer, the routing record (else None)."""
    u = _rms(x, norm_w.astype(F32), m["layer_norm_epsilon"])
    if kind == "M":
        return x + mamba_layer(u, p, m), None
    if kind == "*":
        return x + attention_layer(u, p, m, real), None
    y, info = expert_layer(u, p, m, follow, tie_margin)
    return x + y, info


def head(x, params, m: dict, block: int = 8192):
    """Final norm and the untied head, a block of columns at a time."""
    x = _rms(x, params["final_norm"].astype(F32), m["layer_norm_epsilon"])
    kernel = params["lm_head"]["kernel"]
    return jnp.concatenate(
        [x @ _w(kernel[:, j:j + block], m)
         for j in range(0, kernel.shape[1], block)], axis=-1)


def forward(params, tokens, m: dict, follow=None, tie_margin: float = 0.0):
    """Logits (S, vocab) in float32 for one sequence of token ids, and
    per `E` layer the routing record (arrays over the S positions).
    `follow`: per `E` layer an (S, k) array of a system's chosen experts,
    or None. Under the control "state_to_bucket_end" the first
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
        # one program a layer kind (jit keys on p's tree)
        steps = {kind: jax.jit(lambda x, w, p, f, kind=kind: layer_forward(
            x, kind, w, p, m, real, f, tie_margin)) for kind in "M*E"}
        records = []
        for kind, norm_w, p in published_layers(params, m):
            f = None
            if kind == "E" and follow is not None:
                f = follow[len(records)]
            x, info = steps[kind](x, norm_w, p, f)
            if info is not None:
                if real is not None:
                    info = {name: v[real] for name, v in info.items()}
                records.append(info)
        if real is not None:
            x = x[real]
        return head(x, params, m), records


def forward_logits(params, tokens, m: dict):
    return forward(params, tokens, m)[0]
