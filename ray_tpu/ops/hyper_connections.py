"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880): the
residual path of a block whose hidden state is `n` streams of width C.

A token's state is X in R^{n x C}, kept flat as x = vec(X) (n*C lanes:
stream i is columns i*C .. (i+1)*C). Around one sub-layer F, with its own
`phi` ((n*n + 2n) x n*C, the mapping's RMSNorm weight folded in), `b`
(n*n + 2n) and `a` = (a_pre, a_post, a_res):

    m     = (phi x) * rsqrt(mean(x^2) + norm_eps)            float32
    Hpre  = sigmoid(a_pre m[0:n] + b[0:n])                   (n)
    Hpost = 2 sigmoid(a_post m[n:2n] + b[n:2n])              (n)
    M     = exp(clip(a_res mat(m[2n:]) + mat(b[2n:]), lo, hi))   (n x n)
    `iters` times:  M <- M / (colsum(M) + eps);  M <- M / (rowsum(M) + eps)
    Hres  = M          (Sinkhorn-Knopp: doubly stochastic to the
                        iterations' accuracy)
    h     = sum_i Hpre[i] X[i]                               `mix_in`
    X'[i] = sum_j Hres[i, j] X[j] + Hpost[i] F(h)            `mix_out`

`mappings`, `mix_in` and `mix_out` are that in plain jax.numpy: the CPU
route, and what the kernels of ops/pallas/hyper_connections.py are held
to. A model calls `read` and `write`, which take the kernels where
`jax.default_backend()` is the TPU and the plain form anywhere else (as
models/hybrid.py takes `gdn_decode_step`): one route a platform, nothing
switches it.

Between `read` and `write` a token's mapping travels PACKED, one float32
row `[Hpre | Hpost | Hres row-major | clamped | unconverged]` of
`packed_width(n)` values: the last two are 1.0 where a logit of Hres met
the clamp, and where a row or column sum of Hres lies farther than
`UNCONVERGED_TOL` from 1 after the last iteration (`counters`).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

HC_STATS = ("hc_rows", "hc_clamped_rows", "hc_unconverged_rows")
UNCONVERGED_TOL = 1e-3


class HCParams(NamedTuple):
    """What is static in a sub-layer's mapping (hashable: a jit key)."""
    n: int                          # streams
    iters: int                      # Sinkhorn-Knopp iterations
    eps: float                      # added to each normalising sum
    norm_eps: float                 # of the mapping's RMSNorm
    clamp: Tuple[float, float]      # of Hres's logits, before exp


def n_maps(n: int) -> int:
    return n * n + 2 * n


def packed_width(n: int) -> int:
    return n_maps(n) + 2


def arm_scales(a, n: int):
    """(n*n + 2n,) float32: a = (a_pre, a_post, a_res), each spread over
    its arm's values."""
    a = a.astype(jnp.float32)
    return jnp.concatenate([jnp.repeat(a[0], n), jnp.repeat(a[1], n),
                            jnp.repeat(a[2], n * n)])


def _logits(x, phi, b, a, hp: HCParams):
    """(..., n*n + 2n) float32: a * m + b, `a` spread over its arm."""
    xf = x.astype(jnp.float32)
    # the stream's and phi's own values (bf16 in a served model) summed in
    # float32; HIGHEST keeps float32 operands whole
    ct = jnp.promote_types(x.dtype, phi.dtype)
    m = jnp.einsum("...k,mk->...m", x.astype(ct), phi.astype(ct),
                   preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)
    m = m * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True)
                          + hp.norm_eps)
    return m * arm_scales(a, hp.n) + b.astype(jnp.float32)


def _sinkhorn(mat, iters: int, eps: float):
    """mat (..., n, n) positive: columns, then rows, `iters` times (the
    paper's T_r(T_c(M)))."""
    for _ in range(iters):
        mat = mat / (mat.sum(-2, keepdims=True) + eps)
        mat = mat / (mat.sum(-1, keepdims=True) + eps)
    return mat


def packed_mappings(x, phi, b, a, hp: HCParams):
    """x (..., n*C) -> (..., packed_width(n)) float32 (module docstring)."""
    n = hp.n
    z = _logits(x, phi, b, a, hp)
    pre = jax.nn.sigmoid(z[..., :n])
    post = 2.0 * jax.nn.sigmoid(z[..., n:2 * n])
    zr = z[..., 2 * n:]
    lo, hi = hp.clamp
    clamped = jnp.any((zr <= lo) | (zr >= hi), -1, keepdims=True)
    res = _sinkhorn(jnp.exp(jnp.clip(zr, lo, hi)).reshape(
        *zr.shape[:-1], n, n), hp.iters, hp.eps)
    off = jnp.maximum(jnp.abs(res.sum(-1) - 1.0).max(-1),
                      jnp.abs(res.sum(-2) - 1.0).max(-1))[..., None]
    return jnp.concatenate(
        [pre, post, res.reshape(*zr.shape), clamped.astype(jnp.float32),
         (off > UNCONVERGED_TOL).astype(jnp.float32)], axis=-1)


def unpack(maps, n: int):
    """packed (..., W) -> Hpre (..., n), Hpost (..., n), Hres (..., n, n)."""
    return (maps[..., :n], maps[..., n:2 * n],
            maps[..., 2 * n:n_maps(n)].reshape(*maps.shape[:-1], n, n))


def mappings(x, phi, b, a, hp: HCParams):
    """x (..., n*C) -> (Hpre, Hpost, Hres), float32."""
    return unpack(packed_mappings(x, phi, b, a, hp), hp.n)


def _streams(x, n: int):
    return x.reshape(*x.shape[:-1], n, x.shape[-1] // n)


def mix_in(x, pre):
    """x (..., n*C), Hpre (..., n) -> h (..., C) in x's dtype."""
    n = pre.shape[-1]
    return jnp.einsum("...ic,...i->...c",
                      _streams(x, n).astype(jnp.float32),
                      pre).astype(x.dtype)


def mix_out(x, y, post, res):
    """x (..., n*C), y (..., C), Hpost (..., n), Hres (..., n, n) ->
    x' (..., n*C) in x's dtype."""
    n = post.shape[-1]
    out = (jnp.einsum("...ij,...jc->...ic", res,
                      _streams(x, n).astype(jnp.float32))
           + post[..., None] * y.astype(jnp.float32)[..., None, :])
    return out.reshape(x.shape).astype(x.dtype)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def read(x, phi, b, a, hp: HCParams):
    """x (B, S, n*C) -> (h (B, S, C), packed maps (B, S, W)): the
    sub-layer's input and the token's mapping, which `write` takes."""
    if _on_tpu():
        from .pallas.hyper_connections import hc_mix_in
        with jax.named_scope("hc.mix_in"):
            lead = x.shape[:-1]
            h, maps = hc_mix_in(x.reshape(-1, x.shape[-1]), phi, b, a, hp)
            return h.reshape(*lead, -1), maps.reshape(*lead, -1)
    with jax.named_scope("hc.mappings"):
        maps = packed_mappings(x, phi, b, a, hp)
    with jax.named_scope("hc.mix_in"):
        return mix_in(x, maps[..., :hp.n]), maps


def write(x, y, maps, n: int):
    """x (B, S, n*C), the sub-layer's y (B, S, C), packed maps -> x'."""
    with jax.named_scope("hc.mix_out"):
        if _on_tpu():
            from .pallas.hyper_connections import hc_mix_out
            return hc_mix_out(
                x.reshape(-1, x.shape[-1]), y.reshape(-1, y.shape[-1]),
                maps.reshape(-1, maps.shape[-1]), n).reshape(x.shape)
        _, post, res = unpack(maps, n)
        return mix_out(x, y, post, res)


def counters(maps, row_mask=None):
    """The int32 vector HC_STATS names for one sub-layer's packed maps
    (B, S, W), over the rows `row_mask` (B, S) marks real (None: all)."""
    flags = maps[..., -2:] > 0.5
    real = (jnp.ones(maps.shape[:-1], bool) if row_mask is None
            else row_mask)
    return jnp.stack([real.sum(), (flags[..., 0] & real).sum(),
                      (flags[..., 1] & real).sum()]).astype(jnp.int32)
