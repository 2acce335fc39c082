"""Plain reference of the Olmo-Hybrid-7B forward pass (`model_type`
`olmo_hybrid`), kept with the benchmark: jax.numpy in float32 under
`default_matmul_precision("highest")`, the linear layers' recurrence
token by token in a `lax.scan`, plain softmax attention in the full
layers: no chunkwise form, no kernel, no cache, no batching. It imports
nothing from the program. Written from the published config.json (the
keys of `m` below are its keys) and, for the linear layers, from the
Gated DeltaNet paper (Yang, Kautz, Hatamizadeh, arXiv:2412.06464),
whose layer the `linear_*` keys describe. Layer i is what
`layer_types[i]` names. With H = linear_num_value_heads heads of key
width d_k = linear_key_head_dim and value width d_v =
linear_value_head_dim, x_t the layer's input, per head:

    u_t         = W_qkv x_t                   (H d_k | H d_k | H d_v columns)
    [q~,k~,v]_t = SiLU(sum_{j<K} w_j * u_{t-j})     depthwise, causal, K = 4
    q_t = q~_t / ||q~_t|| * d_k^-1/2 ,  k_t = k~_t / ||k~_t||
    beta_t  = 2 sigmoid(w_b . x_t)            (2: linear_allow_neg_eigval)
    alpha_t = exp(-exp(A_log) softplus(w_a . x_t + dt_bias))
    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t                             S in R^(d_v x d_k), S_0 = 0
    y_t = W_o concat_h[RMSNorm_dv(o_t) * SiLU(W_g x_t)_h]

A full layer: q, k, v = W_q x, W_k x, W_v x; q and k each through a
learned RMSNorm over their WHOLE width (num_heads x head_dim), causal
softmax attention at head_dim^-1/2 with no rotation, W_o. The block:
h = x + RMSNorm(Mixer(x)), y = h + RMSNorm(MLP(h)), MLP a SwiGLU; a
final RMSNorm and an untied head.

What the config does not say and this file, with the program, reads one
way (the configuration file's `assumed`, each with its reason): the
norm placement above (each sub-layer's OUTPUT is normalised, as the
OLMo 2 and 3 family does), no rotary embedding in the full layers
(`rope_theta` is null), no bias on the convolution, a learned weight of
d_v on the output norm shared by the heads, the L2 norms' epsilon 1e-6
inside the root, and the state in float32.

Departures that change no function computed: every projection is its
own jitted call on its one weight cast to float32 by itself, the head
goes in blocks of columns, and the full layers' heads one at a time
(`lax.map`), so that the reference fits beside the served model.

`m["controls"]` (a set of names, empty in every benchmark run) computes
a deliberately wrong model instead, for the measured controls that the
comparison must fail: "bf16_state" (the state rounded to bfloat16 after
every token), "beta_without_2", "no_decay" (alpha = 1),
"state_to_bucket_end" (the prompt padded with token 0 to
`m["bucket"]` positions and the recurrence and convolution run over the
padding, which attention does not see: what a prefill that does not
stop at the prompt's true length computes), "no_qk_l2norm",
"no_qk_rmsnorm" (the full layers').

Weights are read from the system's own parameter tree (flax names of
ray_tpu/models/hybrid.py).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

F32 = jnp.float32
LINEAR, FULL = "linear_attention", "full_attention"
L2_EPS = 1e-6


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


# one projection: the only place a matmul weight is cast, so that one
# float32 weight is live at a time
_project = jax.jit(lambda x, kernel: x @ kernel.astype(F32))


def _w(x, p, name):
    return _project(x, p[name]["kernel"])


def _controls(m: dict) -> frozenset:
    return frozenset(m.get("controls", ()))


class _Sizes(NamedTuple):
    """What the jitted cores read of `m`, hashable."""
    heads: int
    kv_heads: int
    head_dim: int
    lin_heads: int
    dk: int
    dv: int
    conv: int
    neg_eigval: bool
    eps: float
    controls: frozenset


def _sizes(m: dict) -> _Sizes:
    return _Sizes(m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"], m["linear_num_value_heads"],
                  m["linear_key_head_dim"], m["linear_value_head_dim"],
                  m["linear_conv_kernel_dim"],
                  bool(m["linear_allow_neg_eigval"]),
                  float(m["rms_norm_eps"]), _controls(m))


@functools.partial(jax.jit, static_argnames=("c",))
def _delta_rule(u, a, b, p, c: _Sizes):
    """Convolution, norms, gates and the recurrence of one Gated
    DeltaNet layer from its projections u (S, 2 H d_k + H d_v), a, b
    (S, H): o (S, H, d_v)."""
    ctl = c.controls
    s = u.shape[0]
    h, dk, dv, kk = c.lin_heads, c.dk, c.dv, c.conv
    w = p["conv_kernel"].astype(F32)                      # (K, C), w[0] now
    padded = jnp.concatenate([jnp.zeros((kk - 1, u.shape[1]), F32), u])
    conv = sum(w[j] * padded[kk - 1 - j:kk - 1 - j + s] for j in range(kk))
    conv = jax.nn.silu(conv)
    q = conv[:, :h * dk].reshape(s, h, dk)
    k = conv[:, h * dk:2 * h * dk].reshape(s, h, dk)
    v = conv[:, 2 * h * dk:].reshape(s, h, dv)
    if "no_qk_l2norm" not in ctl:
        q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + L2_EPS)
        k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + L2_EPS)
    q = q * dk ** -0.5
    beta = jax.nn.sigmoid(b)
    if c.neg_eigval and "beta_without_2" not in ctl:
        beta = 2.0 * beta
    alpha = jnp.exp(-jnp.exp(p["A_log"].astype(F32)) * jax.nn.softplus(
        a + p["dt_bias"].astype(F32)))
    if "no_decay" in ctl:
        alpha = jnp.ones_like(alpha)

    def token(state, xs):                                 # (H, d_v, d_k)
        qt, kt, vt, at, bt = xs
        state = state * at[:, None, None]
        sk = jnp.einsum("hvk,hk->hv", state, kt)
        state = state + (bt[:, None] * (vt - sk))[:, :, None] \
            * kt[:, None, :]
        if "bf16_state" in ctl:
            # bfloat16's 8 exponent and 7 mantissa bits, as an operation
            # of its own: a compiler may drop a cast there and back
            state = jax.lax.reduce_precision(state, 8, 7)
        return state, jnp.einsum("hvk,hk->hv", state, qt)

    _, o = jax.lax.scan(token, jnp.zeros((h, dv, dk), F32),
                        (q, k, v, alpha, beta))
    return _rms(o, p["o_norm"].astype(F32), c.eps)


def linear_mixer(x, p, m: dict):
    """One Gated DeltaNet layer on x (S, hidden). No mask: the
    recurrence runs over whatever it is given, which is the point of
    the control that pads the prompt."""
    small = {k: p[k] for k in ("conv_kernel", "A_log", "dt_bias", "o_norm")}
    o = _delta_rule(_w(x, p, "qkv_proj"), _w(x, p, "a_proj"),
                    _w(x, p, "b_proj"), small, _sizes(m))
    gate = jax.nn.silu(_w(x, p, "g_proj"))
    return _w(o.reshape(x.shape[0], -1) * gate, p, "o_proj")


@functools.partial(jax.jit, static_argnames=("c",))
def _attend(q, k, v, p, real, c: _Sizes):
    """Causal softmax attention of one full layer from its projections,
    a head at a time: no rotation. Keys that are not `real` are seen by
    no query but themselves."""
    s = q.shape[0]
    nh, nkv, hd = c.heads, c.kv_heads, c.head_dim
    if "no_qk_rmsnorm" not in c.controls:
        q = _rms(q, p["q_norm"].astype(F32), c.eps)
        k = _rms(k, p["k_norm"].astype(F32), c.eps)
    rep = nh // nkv
    seen = jnp.tril(jnp.ones((s, s), bool))
    if real is not None:
        seen = (seen & real[None, :]) | jnp.eye(s, dtype=bool)

    def group(qkv):
        qg, kg, vg = qkv                       # (rep, S, D), (S, D), (S, D)
        scores = jnp.einsum("rqd,kd->rqk", qg, kg) * hd ** -0.5
        scores = jnp.where(seen[None], scores, -jnp.inf)
        return jnp.einsum("rqk,kd->rqd", jax.nn.softmax(scores, -1), vg)

    qg = q.reshape(s, nkv, rep, hd).transpose(1, 2, 0, 3)
    kg = k.reshape(s, nkv, hd).transpose(1, 0, 2)
    vg = v.reshape(s, nkv, hd).transpose(1, 0, 2)
    attn = jax.lax.map(group, (qg, kg, vg))               # (nkv, rep, S, D)
    return attn.reshape(nh, s, hd).transpose(1, 0, 2).reshape(s, nh * hd)


def full_mixer(x, p, m: dict, real=None):
    """One full-attention layer on x (S, hidden)."""
    norms = {k: p[k] for k in ("q_norm", "k_norm")}
    attn = _attend(_w(x, p, "q_proj"), _w(x, p, "k_proj"),
                   _w(x, p, "v_proj"), norms, real, _sizes(m))
    return _w(attn, p, "o_proj")


def mlp(x, p):
    return _w(jax.nn.silu(_w(x, p, "gate_proj")) * _w(x, p, "up_proj"),
              p, "down_proj")


def layer_forward(x, p, kind: str, m: dict, real=None):
    """One block: h = x + Norm(Mixer(x)), y = h + Norm(MLP(h))."""
    eps = m["rms_norm_eps"]
    mix = (full_mixer(x, p["attention"], m, real) if kind == FULL
           else linear_mixer(x, p["linear_attention"], m))
    x = x + _rms(mix, p["attn_norm"].astype(F32), eps)
    return x + _rms(mlp(x, p["mlp"]), p["mlp_norm"].astype(F32), eps)


def head(x, params, m: dict, block: int = 16384):
    """Final norm and the untied head, a block of columns at a time."""
    x = _rms(x, params["final_norm"].astype(F32), m["rms_norm_eps"])
    kernel = params["lm_head"]["kernel"]
    return jnp.concatenate(
        [_project(x, kernel[:, i:i + block])
         for i in range(0, kernel.shape[1], block)], axis=-1)


def forward_logits(params, tokens, m: dict, last: int | None = None):
    """Logits (S or last, vocab) in float32 for one sequence of token
    ids. Under the control "state_to_bucket_end" the first
    `m["prompt_len"]` tokens are followed by token 0 up to `m["bucket"]`
    positions before the rest, and the logits of those positions are
    cut out again."""
    real = None
    if "state_to_bucket_end" in _controls(m):
        p, pad = m["prompt_len"], m["bucket"] - m["prompt_len"]
        tokens = jnp.concatenate([tokens[:p], jnp.zeros((pad,), tokens.dtype),
                                  tokens[p:]])
        at = jnp.arange(tokens.shape[0])
        real = (at < p) | (at >= p + pad)
    with jax.default_matmul_precision("highest"):
        x = params["token_embed"]["embedding"][tokens].astype(F32)
        for i in range(m["num_hidden_layers"]):
            x = layer_forward(x, params[f"layer_{i}"], m["layer_types"][i],
                              m, real)
        if real is not None:
            x = x[real]
        if last is not None:
            x = x[-last:]
        return head(x, params, m)
