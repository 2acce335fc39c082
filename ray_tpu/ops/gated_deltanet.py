"""Gated DeltaNet: a linear-attention layer with a fixed recurrent state
(Yang, Kautz, Hatamizadeh, arXiv:2412.06464), in the two forms a serving
engine needs, over the same projections.

A head with key width d_k and value width d_v keeps a state S (d_v x
d_k) and, for token t with a decay alpha_t in (0, 1] and a writing
strength beta_t:

    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    o_t = S_t q_t

q and k are L2-normalised over the head's d_k (q also times d_k^-1/2),
both after a depthwise causal convolution of width K and a SiLU
(`causal_conv`, which the short-convolution layer of models/hybrid.py
shares, without the SiLU);
alpha_t = exp(g_t), g_t = -exp(A_log) softplus(a_t + dt_bias);
beta_t = sigmoid(b_t), times 2 where negative eigenvalues are allowed.

The state is held TRANSPOSED, heads side by side: `(B, d_k, H * d_v)`
float32. That is the layout of the serving engine's per-slot pool: its
last two dimensions fill whole (8, 128) tiles at the published widths
(96 x 5 760), where a (d_v, d_k) = (192, 96) tile a head would be padded
by a third, and a decode step is elementwise work along it
(ops/pallas/gdn_decode.py).

  * `chunk_scan`: a whole sequence in chunks of `chunk` tokens, the
    paper's WY form with the decay folded in: inside a chunk
    everything is matrix products (the inverse of a unit lower
    triangular matrix by block forward substitution), across chunks a
    `lax.scan` carries the state. Prefill and the plain forward.
  * `step`: one token, three passes over the state in plain XLA; the
    fused kernel of ops/pallas/gdn_decode.py makes it one.
  * `recurrent`: the recurrence itself, token by token in a `lax.scan`;
    what the two forms are tested against.

A decay a key CHANNEL (Kimi Delta Attention, arXiv:2510.26692): g and
alpha carry one more dimension, (.., H, d_k), and the rule reads

    S_t = S_{t-1} Diag(alpha_t) (I - beta_t k_t k_t^T) + beta_t v_t k_t^T

`gates`, `recurrent` and `step` tell the two by g's rank. `chunk_scan`
hands a rate a channel to `_chunk_scan_channel` (the serving engine's
prefill on a TPU runs the same mathematics as ONE fused kernel bounded
by each row's true length, ops/pallas/kda_prefill.py, and the tests
hold that kernel to this function): the intra-chunk matrix
is then sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c]), and its factored form
(k_t exp(G_t)) . (k_s exp(-G_s)) overflows float32 inside a 64-token
chunk at the rates the layer draws, so the chunk is cut into sub-chunks:
diagonal blocks with the difference inside the exponent, the blocks
under them referred to the row sub-chunk's edge, both factors <= 1.

Padded positions are frozen by the caller: g = 0 (alpha = 1) and
beta = 0 leave the state as it was, so the state after a padded row is
the state at its true length (`freeze`). The convolution's tail, the
last K - 1 inputs, is taken at the true length too (`causal_conv`).
Products whose operands are float32 are asked for at the highest
precision: on a TPU the default would round the state to bfloat16 at
every use.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32


def gates(a: jax.Array, b: jax.Array, a_log: jax.Array,
          dt_bias: jax.Array, allow_neg_eigval: bool
          ) -> Tuple[jax.Array, jax.Array]:
    """(g, beta) float32 from the two per-head projections a, b
    (..., H): g = log alpha <= 0, beta in (0, 1) or (0, 2). A rate a
    channel: a and dt_bias (..., H, d_k) beside a_log (H,), and g
    (..., H, d_k)."""
    a_log = a_log.astype(F32)
    if a.ndim == b.ndim + 1:
        a_log = a_log[:, None]
    g = -jnp.exp(a_log) * jax.nn.softplus(
        a.astype(F32) + dt_bias.astype(F32))
    beta = jax.nn.sigmoid(b.astype(F32))
    return g, 2.0 * beta if allow_neg_eigval else beta


def freeze(g: jax.Array, beta: jax.Array, real: Optional[jax.Array]
           ) -> Tuple[jax.Array, jax.Array]:
    """Positions that are not `real` (..., broadcast over heads) leave
    the state untouched: alpha = 1, beta = 0."""
    if real is None:
        return g, beta
    real = real[..., None]
    real_g = real[..., None] if g.ndim == beta.ndim + 1 else real
    return jnp.where(real_g, g, 0.0), jnp.where(real, beta, 0.0)


def l2norm(x: jax.Array, eps: float = 1e-6) -> jax.Array:
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


def causal_conv(u: jax.Array, w: jax.Array, tail: Optional[jax.Array],
                n_new: Optional[jax.Array] = None,
                activation=jax.nn.silu,
                bias: Optional[jax.Array] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """Depthwise causal convolution, plus `bias` (C,) where there is one
    (the state-space mixer's), then `activation` (None: nothing;
    models/hybrid.py's short-convolution layer). u (B, S, C) new inputs,
    w (K, C) with w[0] on the current token, tail (B, (K - 1) * C) the
    K - 1 inputs before them side by side, the oldest first (None:
    zeros). Returns (the result (B, S, C) in u's dtype, the new tail):
    the K - 1 inputs up to each row's true length `n_new` (B,) (None:
    S), so a row with no real token keeps its tail.

    The tail is held a ROW a sequence and never as (B, K - 1, C): an
    axis of K - 1 (or, behind a one-token `u`, of K or 1) rows in the
    tiled second-minor place is given tiles of two and four rows on the
    TPU, and the compiler then re-lays out the projection that made `u`
    and copies the whole tail pool there and back, every decode step
    (PERF.md section 6, PR 55). A tap is a slice of lanes (C fills whole
    128-lane tiles at every published width). One token (S == 1, what
    a decode step is) is elementwise work on (B, C) arrays."""
    b, s, c = u.shape
    k = w.shape[0]
    if tail is None:
        tail = jnp.zeros((b, (k - 1) * c), u.dtype)
    tail = tail.astype(u.dtype)
    taps = [tail[:, i * c:(i + 1) * c] for i in range(k - 1)]
    wf = w.astype(F32)

    def held(x):
        """x in float32 as its own dtype holds it, and as the tail
        will: where the compiler fuses the projection that made `u`
        into these sums it would otherwise hand over the unrounded
        float32 product, one step's tap 0 in another precision than
        the next step's tap 1."""
        fi = jnp.finfo(x.dtype)
        return jax.lax.reduce_precision(x.astype(F32), fi.nexp, fi.nmant)

    if s == 1:
        x = u[:, 0]
        out = sum(wf[j] * held(x if j == 0 else taps[k - 1 - j])
                  for j in range(k))[:, None]
        new_tail = jnp.concatenate(taps[1:] + [x], axis=-1)
        if n_new is not None:
            new_tail = jnp.where((n_new > 0)[:, None], new_tail, tail)
    else:
        # x_j[t] = u[t - j], and the tail's tap K - 1 - j + t where
        # t < j: u shifted where it lies, its first rows from the tail;
        # one fused pass over u on the TPU (the first K - 1 results
        # computed apart and put in front of the rest compiled to two,
        # with the float32 sums in HBM between them)
        t = jnp.arange(s)[:, None]

        def shifted(j):
            x = jnp.pad(u[:, :max(s - j, 0)],
                        ((0, 0), (min(j, s), 0), (0, 0)))
            for i in range(min(j, s)):
                x = jnp.where(t == i, taps[k - 1 - j + i][:, None], x)
            return x
        out = sum(wf[j] * held(shifted(j)) for j in range(k))
        # position n_new + i of [tail | u], i = 0 .. K - 2: a row of u,
        # or where the row is shorter than the tail one of the old taps
        n = jnp.full((b,), s, jnp.int32) if n_new is None else n_new
        rows = u.reshape(b * s, c)
        new = []
        for i in range(k - 1):
            at = n + i
            tap = rows[jnp.arange(b) * s + jnp.clip(at - (k - 1), 0, s - 1)]
            for old in range(i, k - 1):
                tap = jnp.where((at == old)[:, None], taps[old], tap)
            new.append(tap)
        new_tail = jnp.concatenate(new, axis=-1)
    if bias is not None:
        out = out + bias.astype(F32)
    if activation is not None:
        out = activation(out)
    return out.astype(u.dtype), new_tail


def _heads_last(state: jax.Array, h: int) -> jax.Array:
    """(B, d_k, H * d_v) -> (B, H, d_k, d_v)."""
    b, dk, hv = state.shape
    return state.reshape(b, dk, h, hv // h).transpose(0, 2, 1, 3)


def _heads_flat(s4: jax.Array) -> jax.Array:
    """(B, H, d_k, d_v) -> (B, d_k, H * d_v)."""
    b, h, dk, dv = s4.shape
    return s4.transpose(0, 2, 1, 3).reshape(b, dk, h * dv)


def recurrent(q, k, v, g, beta, state=None):
    """The recurrence, token by token. q, k (B, S, H, d_k) normalised,
    v (B, S, H, d_v), g, beta (B, S, H) (g (B, S, H, d_k): a rate a
    channel), state (B, d_k, H * d_v) or None (zeros). Returns
    (o (B, S, H, d_v) float32, final state)."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    s0 = (jnp.zeros((b, h, dk, dv), F32) if state is None
          else _heads_last(state.astype(F32), h))

    def body(st, xs):
        qt, kt, vt, gt, bt = xs                     # (B, H, .)
        st = st * (jnp.exp(gt)[..., None] if gt.ndim == 3
                   else jnp.exp(gt)[..., None, None])
        kv = jnp.einsum("bhkv,bhk->bhv", st, kt, precision=_HI)
        st = st + kt[..., None] * (bt[..., None] * (vt - kv))[..., None, :]
        return st, jnp.einsum("bhkv,bhk->bhv", st, qt, precision=_HI)

    xs = tuple(jnp.moveaxis(x.astype(F32), 1, 0) for x in (q, k, v, g, beta))
    final, o = jax.lax.scan(body, s0, xs)
    return jnp.moveaxis(o, 0, 1), _heads_flat(final)


def step(q, k, v, g, beta, state):
    """One token in plain XLA. q, k (B, H, d_k), v (B, H, d_v), g, beta
    (B, H) (g (B, H, d_k): a rate a channel), state (B, d_k, H * d_v)
    float32. Returns (o (B, H, d_v) float32, new state)."""
    b, h, dk = q.shape
    dv = v.shape[-1]
    s4 = state.reshape(b, dk, h, dv)
    s4 = s4 * (jnp.swapaxes(jnp.exp(g), 1, 2)[..., None] if g.ndim == 3
               else jnp.exp(g)[:, None, :, None])
    kv = jnp.einsum("bkhv,bhk->bhv", s4, k, precision=_HI)
    u = beta[..., None] * (v.astype(F32) - kv)
    s4 = s4 + jnp.swapaxes(k, 1, 2)[..., None] * u[:, None]
    o = jnp.einsum("bkhv,bhk->bhv", s4, q, precision=_HI)
    return o, s4.reshape(b, dk, h * dv)


def _unit_lower_inverse(strict: jax.Array) -> jax.Array:
    """(I + N)^-1 for N strictly lower triangular (..., C, C), by block
    forward substitution from the diagonal outward: with the inverses
    X11, X22 of two neighbouring diagonal blocks of width b known, the
    block under the diagonal of their 2b-wide block's inverse is
    -X22 N21 X11; log2(C) rounds of two products. Every
    intermediate is a block of the true inverse, which stays bounded
    for the delta rule's N (beta k.k <= 2). The series sum_k (-N)^k is
    the same matrix in exact arithmetic and useless in float32: keys
    after a SiLU all point one way, N's entries are ~0.5 of one sign,
    and its powers pass 1e30 before they cancel."""
    c = strict.shape[-1]
    n = 1 << max(c - 1, 0).bit_length()
    a = jnp.pad(strict, [(0, 0)] * (strict.ndim - 2) + [(0, n - c)] * 2)
    x = jnp.broadcast_to(jnp.eye(n, dtype=strict.dtype), a.shape)
    mm = functools.partial(jnp.matmul, precision=_HI)
    b = 1
    while b < n:
        # x is block diagonal in blocks of b: x N21 x is nonzero only in
        # the odd blocks' rows under the even blocks before them
        block = jnp.arange(n) // b
        under = (block[:, None] % 2 == 1) \
            & (block[None, :] == block[:, None] - 1)
        x = x - mm(mm(x, jnp.where(under, a, 0.0)), x)
        b *= 2
    return x[..., :c, :c]


def chunk_scan(q, k, v, g, beta, state=None, chunk: int = 64):
    """The same function as `recurrent`, chunk by chunk (same arguments
    and results). With Gamma_t the decay from the chunk's start to t,
    M[t, s] = (Gamma_t / Gamma_s) k_t . k_s for s < t and T = (I +
    diag(beta) M)^-1:
        W   = T (beta V) - T (beta Gamma K) S_0       the written rows
        O   = (Gamma Q) S_0 + (Q K^T * Gamma_t / Gamma_s, s <= t) W
        S_C = Gamma_C S_0 + (K Gamma_C / Gamma)^T W
    Any length: the sequence is padded with frozen positions to a whole
    number of chunks. g (B, S, H, d_k), a rate a channel:
    `_chunk_scan_channel`."""
    if g.ndim == 4:
        return _chunk_scan_channel(q, k, v, g, beta, state, chunk)
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    c = min(chunk, s)
    n = -(-s // c)
    pad = n * c - s

    def chunks(x):                      # (B, S, H, ...) -> (B, H, n, c, ...)
        x = x.astype(F32)
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape(b, n, c, *x.shape[2:])
        return jnp.moveaxis(x, 3, 1)

    q, k, v, g, beta = map(chunks, (q, k, v, g, beta))
    mm = functools.partial(jnp.einsum, precision=_HI)
    gc = jnp.cumsum(g, axis=-1)                               # (B,H,n,c)
    lower = jnp.tril(jnp.ones((c, c), bool))
    # exp only where t >= s: above the diagonal the difference is >= 0
    # and could overflow
    ratio = jnp.where(lower, jnp.exp(jnp.where(
        lower, gc[..., :, None] - gc[..., None, :], 0.0)), 0.0)
    kb = k * beta[..., None]
    strict = jnp.tril(mm("bhntk,bhnsk->bhnts", kb, k) * ratio, -1)
    t_inv = _unit_lower_inverse(strict)
    v_w = mm("bhnts,bhnsv->bhntv", t_inv, v * beta[..., None])
    k_w = mm("bhnts,bhnsk->bhntk", t_inv, kb * jnp.exp(gc)[..., None])
    attn = mm("bhntk,bhnsk->bhnts", q, k) * ratio
    q_in = q * jnp.exp(gc)[..., None]
    k_out = k * jnp.exp(gc[..., -1:] - gc)[..., None]
    total = jnp.exp(gc[..., -1])                              # (B,H,n)
    s0 = (jnp.zeros((b, h, dk, dv), F32) if state is None
          else _heads_last(state.astype(F32), h))

    def body(st, xs):
        v_w, k_w, attn, q_in, k_out, total = xs
        w = v_w - mm("bhtk,bhkv->bhtv", k_w, st)
        o = mm("bhtk,bhkv->bhtv", q_in, st) + mm("bhts,bhsv->bhtv", attn, w)
        st = st * total[..., None, None] + mm("bhtk,bhtv->bhkv", k_out, w)
        return st, o

    xs = tuple(jnp.moveaxis(x, 2, 0)
               for x in (v_w, k_w, attn, q_in, k_out, total))
    final, o = jax.lax.scan(body, s0, xs)                     # (n,B,H,c,dv)
    o = jnp.moveaxis(o, 0, 2).reshape(b, h, n * c, dv)[:, :, :s]
    return jnp.swapaxes(o, 1, 2), _heads_flat(final)


SUB_CHUNK = 16


def _chunk_scan_channel(q, k, v, g, beta, state=None, chunk: int = 64):
    """`chunk_scan` with a rate a key channel, g (B, S, H, d_k). With
    G_t[c] the summed rate of channel c from the chunk's start to t and
    Gamma = exp(G), the chunk's matrices are
        A[t, s] = sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])     s < t
        P[t, s] = sum_c q_t[c] k_s[c] exp(G_t[c] - G_s[c])     s <= t
    and with T = (I + diag(beta) A)^-1:
        W   = T (beta V) - T (beta K Gamma) S_0
        O   = (Q Gamma) S_0 + P W
        S_C = Diag(Gamma_C) S_0 + (K Gamma_C / Gamma)^T W
    exp(-G_s) alone passes float32's range inside a chunk (a rate of
    1.6 a token after 55 tokens), so A and P are built from sub-chunks
    of SUB_CHUNK tokens: a diagonal block with the difference inside
    the exponent (<= 0 where s <= t, masked elsewhere), a block under
    the diagonal as (x_t exp(G_t - E)) . (k_s exp(E - G_s)) with E the
    summed rate at the row sub-chunk's edge, t >= edge > s, so that
    both exponents are <= 0: a factor that underflows belongs to a
    product that is nothing beside the diagonal's. Everything of a
    chunk is computed inside the scan over chunks: the (sub-chunk x
    sub-chunk x d_k) intermediates live for one chunk only."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    sc = min(SUB_CHUNK, chunk)
    if chunk % sc:
        raise ValueError(f"chunk={chunk} is no multiple of {sc}")
    c = min(chunk, -(-s // sc) * sc)
    n, ns = -(-s // c), c // sc
    pad = n * c - s

    def chunks(x):                      # (B, S, H, ...) -> (n, B, H, c, ...)
        x = x.astype(F32)
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape(b, n, c, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 1), 2, 0)

    mm = functools.partial(jnp.einsum, precision=_HI)
    low = jnp.tril(jnp.ones((sc, sc), bool))[..., None]       # (t, s, 1)
    # columns that lie before each row sub-chunk's edge
    before = (jnp.arange(c)[None, :]
              < (jnp.arange(ns) * sc)[:, None])[..., None]    # (ns, c, 1)
    eye = jnp.eye(ns, dtype=F32)

    def sub(x):                         # (B, H, c, .) -> (B, H, ns, sc, .)
        return x.reshape(b, h, ns, sc, x.shape[-1])

    def body(st, xs):
        q, k, v, g, beta = xs           # (B, H, c, .), beta (B, H, c)
        gc = jnp.cumsum(g, axis=-2)
        gs, ks = sub(gc), sub(k)
        edge = jnp.concatenate(
            [jnp.zeros((b, h, 1, dk), F32), gs[:, :, :-1, -1]], axis=2)
        rows = jnp.exp(gs - edge[:, :, :, None])              # <= 1
        cols = jnp.where(before, k[:, :, None] * jnp.exp(jnp.where(
            before, edge[:, :, :, None] - gc[:, :, None], 0.0)), 0.0)
        ratio = jnp.where(low, jnp.exp(jnp.where(
            low, gs[:, :, :, :, None] - gs[:, :, :, None, :], 0.0)), 0.0)

        def matrix(x):
            """sum_c x_t[c] k_s[c] exp(G_t[c] - G_s[c]), s <= t: (c, c)."""
            xs_ = sub(x)
            under = mm("bhitd,bhisd->bhits", xs_ * rows, cols)
            diag = jnp.sum(xs_[:, :, :, :, None] * ks[:, :, :, None, :]
                           * ratio, axis=-1)                  # (ns, sc, sc)
            return (under + jnp.einsum("bhits,ij->bhitjs", diag, eye)
                    .reshape(b, h, ns, sc, c)).reshape(b, h, c, c)

        kb = k * beta[..., None]
        t_inv = _unit_lower_inverse(
            jnp.tril(matrix(k) * beta[..., None], -1))
        decay = jnp.exp(gc)
        v_w = mm("bhts,bhsv->bhtv", t_inv, v * beta[..., None])
        k_w = mm("bhts,bhsk->bhtk", t_inv, kb * decay)
        w = v_w - mm("bhtk,bhkv->bhtv", k_w, st)
        o = mm("bhtk,bhkv->bhtv", q * decay, st) \
            + mm("bhts,bhsv->bhtv", matrix(q), w)
        k_out = k * jnp.exp(gc[:, :, -1:] - gc)
        st = st * decay[:, :, -1, :, None] \
            + mm("bhtk,bhtv->bhkv", k_out, w)
        return st, o

    s0 = (jnp.zeros((b, h, dk, dv), F32) if state is None
          else _heads_last(state.astype(F32), h))
    final, o = jax.lax.scan(body, s0, tuple(map(chunks, (q, k, v, g, beta))))
    o = jnp.moveaxis(o, 0, 2).reshape(b, h, n * c, dv)[:, :, :s]
    return jnp.swapaxes(o, 1, 2), _heads_flat(final)
