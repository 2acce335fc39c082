"""Pallas TPU kernels: the residual path of manifold-constrained
hyper-connections (ops/hyper_connections.py has the equations and the
plain form these are held to).

Unfused, a sub-layer's mapping is ~45 small operations a token (a norm,
a 24-wide product, two sigmoid arms, a clamp, an exp and 20 Sinkhorn
iterations of two normalisations each) beside two passes over the
n-stream residual. Here it is two kernels the trace can name:

`hc_mix_in`   one read of x (rows, n*C): the sum of squares and the
              product with phi on the matrix unit, then, with the
              tokens moved to the lanes ((W, rows): every vector
              register full), the arms, the clamp, exp and all the
              iterations in registers; the token's packed mapping
              (rows, W) float32 and h = sum_i Hpre[i] X[i] written.
`hc_mix_out`  x, y and the packed mapping read once,
              X'[i] = sum_j Hres[i, j] X[j] + Hpost[i] y written over x
              (`input_output_aliases`: in place where the caller's x is
              dead, as a step program's is).

Both walk the rows in whole tiles that `row_tile` derives from the row
count (a 2 x 2 048 prefill: 32 tiles of 128; the 129 rows of a decode
step: one block). Every row is independent. Inference only: no backward
pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..hyper_connections import (HCParams, UNCONVERGED_TOL, arm_scales,
                                 n_maps, packed_width)

# a tile of 128 rows of four 3 584-wide bf16 streams is 3.7 MB; in and out,
# double-buffered, with the float32 streams the body works on: ~30 MB,
# over the 16 MiB a kernel gets unasked
VMEM_LIMIT_BYTES = 64 * 1024 * 1024
MAX_ROW_TILE = 128
# rows one block may hold where no tile divides them: 384 rows of four
# 3 584-wide bf16 streams in and out, with the float32 streams, ~60 MB
ONE_BLOCK_ROWS = 384
F32 = jnp.float32


def row_tile(rows: int, cap: int = MAX_ROW_TILE) -> int:
    """Rows a grid step, WHOLE tiles only: the largest multiple of 16 (a
    bf16 sublane tile) up to `cap` that divides the rows (128 for every
    prefill group of the engine's buckets), else all the rows in one
    block (the 129 of a decode step over 128 slots and the scratch
    row). A tile that hangs over the end is never made: beside the
    in-place write of `hc_mix_out` inside a step program it hung the
    chip (PERF.md, PR 48), though it ran alone."""
    for tile in range(cap, 15, -16):
        if rows % tile == 0:
            return tile
    return rows


def _padded(rows: int) -> int:
    """Rows a call runs: the rows themselves, or, where no tile divides
    them and one block of them all would not fit the VMEM asked for,
    the next multiple of the largest tile (a copy: no shape of the
    engine's comes here)."""
    if row_tile(rows) < rows or rows <= ONE_BLOCK_ROWS:
        return rows
    return -(-rows // MAX_ROW_TILE) * MAX_ROW_TILE


def _mix_in_kernel(x_ref, phi_ref, ab_ref, h_ref, maps_ref, *, hp: HCParams):
    n = hp.n
    c = h_ref.shape[-1]
    x = x_ref[...]
    if phi_ref.dtype == x.dtype:
        m = jax.lax.dot_general(x, phi_ref[...], (((1,), (1,)), ((), ())),
                                preferred_element_type=F32)
    else:
        m = jax.lax.dot_general(
            x.astype(F32), phi_ref[...].astype(F32),
            (((1,), (1,)), ((), ())), preferred_element_type=F32,
            precision=jax.lax.Precision.HIGHEST)
    ss = jnp.zeros((x.shape[0], 1), F32)
    for i in range(n):
        xi = x_ref[:, i * c:(i + 1) * c].astype(F32)
        ss = ss + jnp.sum(xi * xi, axis=-1, keepdims=True)
    m = m * jax.lax.rsqrt(ss / (n * c) + hp.norm_eps)
    # tokens to the lanes: (n*n + 2n, rows), every register full
    z = m.T * ab_ref[:, 0:1] + ab_ref[:, 1:2]
    pre = jax.nn.sigmoid(z[:n])
    post = 2.0 * jax.nn.sigmoid(z[n:2 * n])
    zr = z[2 * n:]
    lo, hi = hp.clamp
    clamped = jnp.max(jnp.where((zr <= lo) | (zr >= hi), 1.0, 0.0),
                      axis=0, keepdims=True)
    e = jnp.exp(jnp.clip(zr, lo, hi))
    # Hres row i: (n, rows), its n columns on the sublanes
    rows = [e[i * n:(i + 1) * n] for i in range(n)]
    for _ in range(hp.iters):
        col = rows[0]
        for r in rows[1:]:
            col = col + r
        col = col + hp.eps
        rows = [r / col for r in rows]
        rows = [r / (jnp.sum(r, axis=0, keepdims=True) + hp.eps)
                for r in rows]
    col = rows[0]
    for r in rows[1:]:
        col = col + r
    off = jnp.max(jnp.abs(col - 1.0), axis=0, keepdims=True)
    for r in rows:
        off = jnp.maximum(off, jnp.abs(
            jnp.sum(r, axis=0, keepdims=True) - 1.0))
    packed = jnp.concatenate(
        [pre, post] + rows
        + [clamped, jnp.where(off > UNCONVERGED_TOL, 1.0, 0.0)], axis=0).T
    maps_ref[...] = packed
    h = jnp.zeros((x.shape[0], c), F32)
    for i in range(n):
        h = h + packed[:, i:i + 1] * x_ref[:, i * c:(i + 1) * c].astype(F32)
    h_ref[...] = h.astype(h_ref.dtype)


@functools.partial(jax.jit, static_argnames=("hp", "interpret", "tile"))
def hc_mix_in(x, phi, b, a, hp: HCParams, interpret=None, tile=None):
    """x (R, n*C), phi (n*n + 2n, n*C), b (n*n + 2n), a (3,) ->
    (h (R, C) in x's dtype, packed maps (R, n*n + 2n + 2) float32).
    interpret defaults to True only on the CPU backend; `tile` is for
    the microbench (tools/hc_microbench.py) and the tests."""
    r, nc = x.shape
    n, w = hp.n, packed_width(hp.n)
    c = nc // n
    assert phi.shape == (n_maps(n), nc), (phi.shape, nc)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if tile is None and _padded(r) != r:
        h, maps = hc_mix_in(jnp.pad(x, ((0, _padded(r) - r), (0, 0))), phi,
                            b, a, hp, interpret=interpret)
        return h[:r], maps[:r]
    tr = tile or row_tile(r)
    ab = jnp.stack([arm_scales(a, n), b.astype(F32)], axis=1)  # (W - 2, 2)
    return pl.pallas_call(
        functools.partial(_mix_in_kernel, hp=hp),
        grid=(pl.cdiv(r, tr),),
        in_specs=[pl.BlockSpec((tr, nc), lambda i: (i, 0)),
                  pl.BlockSpec(phi.shape, lambda i: (0, 0)),
                  pl.BlockSpec(ab.shape, lambda i: (0, 0))],
        out_specs=[pl.BlockSpec((tr, c), lambda i: (i, 0)),
                   pl.BlockSpec((tr, w), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((r, c), x.dtype),
                   jax.ShapeDtypeStruct((r, w), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="hc_mix_in",
    )(x, phi, ab)


def _mix_out_kernel(x_ref, y_ref, maps_ref, o_ref, *, n: int):
    c = y_ref.shape[-1]
    maps = maps_ref[...]
    y = y_ref[...].astype(F32)
    xs = [x_ref[:, j * c:(j + 1) * c].astype(F32) for j in range(n)]
    for i in range(n):
        acc = maps[:, n + i:n + i + 1] * y
        for j in range(n):
            k = 2 * n + i * n + j
            acc = acc + maps[:, k:k + 1] * xs[j]
        o_ref[:, i * c:(i + 1) * c] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n", "interpret", "tile"))
def hc_mix_out(x, y, maps, n: int, interpret=None, tile=None):
    """x (R, n*C), y (R, C), packed maps (R, n*n + 2n + 2) float32 ->
    x' (R, n*C) in x's dtype, over x's buffer where the caller gives it
    up."""
    r, nc = x.shape
    c = nc // n
    assert y.shape == (r, c) and maps.shape == (r, packed_width(n)), \
        (y.shape, maps.shape)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if tile is None and _padded(r) != r:
        pad = ((0, _padded(r) - r), (0, 0))
        return hc_mix_out(jnp.pad(x, pad), jnp.pad(y, pad),
                          jnp.pad(maps, pad), n, interpret=interpret)[:r]
    tr = tile or row_tile(r)
    row = lambda i: (i, 0)                                    # noqa: E731
    return pl.pallas_call(
        functools.partial(_mix_out_kernel, n=n),
        grid=(pl.cdiv(r, tr),),
        in_specs=[pl.BlockSpec((tr, nc), row), pl.BlockSpec((tr, c), row),
                  pl.BlockSpec((tr, maps.shape[1]), row)],
        out_specs=pl.BlockSpec((tr, nc), row),
        out_shape=jax.ShapeDtypeStruct((r, nc), x.dtype),
        # (the interpreter cannot alias a buffer whose last tile hangs over)
        input_output_aliases={} if interpret else {0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="hc_mix_out",
    )(x, y.astype(x.dtype), maps)
