"""The selective state-space recurrence of Mamba-2 (Dao, Gu,
arXiv:2405.21060), in the forms a serving engine needs, over the slot
state of ops/gated_deltanet.py.

H heads of width P share G groups of input and output maps of N state
channels (head h reads group h // (H / G)). With xs_t (H, P) the
convolved input, B_t and C_t (G, N), a step dt_t (H,) > 0 and
A_h = -exp(A_log_h) < 0, a head keeps S (P x N), float32:

    S_t = a_t S_{t-1} + (dt_t xs_t) B_t^T ,  a_t = exp(dt_t A_h)
    y_t = S_t C_t                             (+ D_h xs_t, the caller's)

That is the delta rule of ops/gated_deltanet.py WITHOUT its correction:
k = B, q = C, v = xs, beta = dt, g = dt A a scalar a head, and what is
written is beta v instead of beta (v - S k). The functions here keep
those names and that module's state layout, `(B, N, H * P)` float32: S
transposed, the heads side by side in the lanes (128 x 8 192 at the
published widths: Kimi Delta Attention's very shape), so the engine's
slot pool and the step kernel (ops/pallas/gdn_decode.py:
`ssm_decode_step`) are the delta rule's.

  * `step`: one token in plain XLA (two passes over the state).
  * `recurrent`: the recurrence token by token in a `lax.scan`; what the
    other forms are tested against.
  * `chunk_scan`: a whole sequence in chunks of `chunk` tokens (the
    paper's state-space dual). With Gamma_t the decay from the chunk's
    start to t and U = dt xs:
        Y   = (C B^T * Gamma_t / Gamma_s, s <= t) U + (Gamma C) S_0
        S_C = Gamma_C S_0 + (B Gamma_C / Gamma)^T U
    every ratio with the difference inside the exponent (<= 0 where it
    counts, masked elsewhere), everything of a chunk inside the scan over
    chunks so that the (H, chunk, chunk) decays live for one chunk.

Padded positions are frozen by the caller as the delta rule's are
(`gated_deltanet.freeze`: g = 0, beta = 0, which is dt = 0): the state
after a padded row is the state at its true length. Float32 products at
the highest precision: the TPU's default would round the state to
bfloat16 at every use.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def gates(dt_raw: jax.Array, a_log: jax.Array, dt_bias: jax.Array
          ) -> Tuple[jax.Array, jax.Array]:
    """(g, beta) float32 from the step's projection dt_raw (..., H):
    beta = dt = softplus(dt_raw + dt_bias), g = log a = dt A <= 0 with
    A = -exp(A_log)."""
    dt = jax.nn.softplus(dt_raw.astype(F32) + dt_bias.astype(F32))
    return -jnp.exp(a_log.astype(F32)) * dt, dt


def _to_heads(x: jax.Array, h: int) -> jax.Array:
    """(..., G, N) -> (..., H, N): each group's map for its H / G heads."""
    return jnp.repeat(x, h // x.shape[-2], axis=-2)


def step(q, k, v, g, beta, state):
    """One token in plain XLA. q = C, k = B (B, G, N), v = xs (B, H, P),
    g, beta (B, H), state (B, N, H * P) float32. Returns (y (B, H, P)
    float32 without the skip, new state)."""
    b, h, p = v.shape
    n = q.shape[-1]
    kx = jnp.swapaxes(_to_heads(k.astype(F32), h), 1, 2)    # (B, N, H)
    u = beta[..., None] * v.astype(F32)
    s4 = state.reshape(b, n, h, p) * jnp.exp(g)[:, None, :, None] \
        + kx[..., None] * u[:, None]
    o = jnp.einsum("bnhp,bhn->bhp", s4, _to_heads(q.astype(F32), h),
                   precision=_HI)
    return o, s4.reshape(b, n, h * p)


def recurrent(q, k, v, g, beta, state=None):
    """The recurrence, token by token. q, k (B, S, G, N), v (B, S, H, P),
    g, beta (B, S, H), state (B, N, H * P) or None (zeros). Returns
    (y (B, S, H, P) float32, final state)."""
    b, s, h, p = v.shape
    n = q.shape[-1]
    s0 = jnp.zeros((b, n, h * p), F32) if state is None \
        else state.astype(F32)

    def body(st, xs):
        o, st = step(*xs, st)
        return st, o

    xs = tuple(jnp.moveaxis(x.astype(F32), 1, 0) for x in (q, k, v, g, beta))
    final, o = jax.lax.scan(body, s0, xs)
    return jnp.moveaxis(o, 0, 1), final


def chunk_scan(q, k, v, g, beta, state=None, chunk: int = 128):
    """The same function as `recurrent`, chunk by chunk (same arguments
    and results). Any length: the sequence is padded with frozen
    positions to a whole number of chunks."""
    b, s, h, p = v.shape
    grp, n = q.shape[-2:]
    c = min(chunk, s)
    nc = -(-s // c)
    pad = nc * c - s

    def chunks(x):                      # (B, S, ...) -> (nc, B, c, ...)
        x = x.astype(F32)
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(x.reshape(b, nc, c, *x.shape[2:]), 1, 0)

    mm = functools.partial(jnp.einsum, precision=_HI)
    lower = jnp.tril(jnp.ones((c, c), bool))

    def body(st, xs):
        q, k, v, g, beta = xs           # (B, c, G, N), (B, c, H, P), (B, c, H)
        gc = jnp.cumsum(g, axis=1)                            # (B, c, H)
        gh = jnp.swapaxes(gc, 1, 2)                           # (B, H, c)
        # exp only where t >= s: above the diagonal the difference is
        # >= 0 and could overflow
        ratio = jnp.where(lower, jnp.exp(jnp.where(
            lower, gh[..., :, None] - gh[..., None, :], 0.0)), 0.0)
        scores = mm("btgn,bsgn->bgts", q, k)                  # (B, G, c, c)
        attn = (scores[:, :, None] * ratio.reshape(
            b, grp, h // grp, c, c)).reshape(b, h, c, c)
        u = v * beta[..., None]                               # (B, c, H, P)
        s4 = st.reshape(b, n, grp, h // grp, p)
        # a head's decay is folded into what carries its axis (y, U):
        # B and C stay a group's
        o = mm("bhts,bshp->bthp", attn, u) \
            + mm("btgn,bngrp->btgrp", q, s4).reshape(b, c, h, p) \
            * jnp.exp(gc)[..., None]
        u_out = u * jnp.exp(gc[:, -1:] - gc)[..., None]
        s4 = s4 * jnp.exp(gc[:, -1]).reshape(b, 1, grp, h // grp, 1) \
            + mm("btgn,btgrp->bngrp", k,
                 u_out.reshape(b, c, grp, h // grp, p))
        return s4.reshape(b, n, h * p), o

    s0 = jnp.zeros((b, n, h * p), F32) if state is None \
        else state.astype(F32)
    final, o = jax.lax.scan(body, s0, tuple(map(chunks, (q, k, v, g, beta))))
    o = jnp.moveaxis(o, 0, 1).reshape(b, nc * c, h, p)[:, :s]
    return o, final


def gated_group_norm(y: jax.Array, z: jax.Array, weight: jax.Array,
                     groups: int, eps: float) -> jax.Array:
    """RMSNorm over each of `groups` equal runs of the last axis of
    y * SiLU(z), the gate FIRST, one learned weight over the whole axis;
    float32 in and out of the statistics, y's dtype back."""
    x = y.astype(F32) * jax.nn.silu(z.astype(F32))
    xg = x.reshape(*x.shape[:-1], groups, x.shape[-1] // groups)
    xg = xg * jax.lax.rsqrt(jnp.mean(xg * xg, -1, keepdims=True) + eps)
    return (xg.reshape(x.shape) * weight.astype(F32)).astype(y.dtype)
