"""Fused RMSNorm for TPU in Pallas.

One HBM pass: each row tile streams into VMEM once, the fp32 mean-square
reduction, rsqrt, and weight multiply all fuse in-kernel, and the result
streams back in the input dtype — apex-FusedRMSNorm semantics (the
reference stacks use apex/torch fused norms; SURVEY.md §2.2 P9).

Forward is the Pallas kernel; backward goes through the XLA math of
ops.norms.rms_norm via jax.custom_vjp (same pattern as
pallas/flash_attention.py: correct grads now, Pallas backward as a later
optimization). Auto-interprets on CPU so tests run the same code path.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..norms import rms_norm as _xla_rms_norm


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)              # (block_rows, d)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(ms + eps)
    o_ref[...] = (y * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _fwd(x2d, weight, eps: float, block_rows: int, interpret: bool):
    rows, d = x2d.shape
    padded = pl.cdiv(rows, block_rows) * block_rows
    if padded != rows:
        x2d = jnp.pad(x2d, ((0, padded - rows), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(padded // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            # rank-2 weight tile: Mosaic wants (sublane, lane)-tileable
            # operands; a rank-1 ref lowers poorly on real TPU
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, d), x2d.dtype),
        interpret=interpret,
    )(x2d, weight.reshape(1, d))
    return out[:rows]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _rmsnorm(x2d, weight, eps, block_rows, interpret):
    return _fwd(x2d, weight, eps, block_rows, interpret)


def _rmsnorm_vjp_fwd(x2d, weight, eps, block_rows, interpret):
    return _fwd(x2d, weight, eps, block_rows, interpret), (x2d, weight)


def _rmsnorm_vjp_bwd(eps, block_rows, interpret, res, g):
    x2d, weight = res
    _, vjp = jax.vjp(lambda x, w: _xla_rms_norm(x, w, eps), x2d, weight)
    return vjp(g)


_rmsnorm.defvjp(_rmsnorm_vjp_fwd, _rmsnorm_vjp_bwd)


def fused_rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6,
                   *, block_rows: Optional[int] = None,
                   interpret: Optional[bool] = None) -> jax.Array:
    """Drop-in for ops.norms.rms_norm with a fused Pallas forward.

    x: (..., d); weight: (d,). Any leading shape — rows are flattened
    into the kernel grid.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    d = x.shape[-1]
    lead = x.shape[:-1]
    x2d = x.reshape(-1, d)
    if block_rows is None:
        # keep the fp32 tile well under VMEM (rows*d*4B <= ~2MB) and
        # never pad a small input up to a much bigger tile
        block_rows = max(8, min(256, (2 << 20) // max(d * 4, 1),
                                x2d.shape[0]))
    # Mosaic fp32 tiles are (8, 128): a block_rows that isn't a multiple
    # of 8 fails to lower on real TPU (grid already pads rows, so
    # rounding up is free).
    block_rows = -(-int(block_rows) // 8) * 8
    out = _rmsnorm(x2d, weight, eps, int(block_rows), bool(interpret))
    return out.reshape(*lead, d)
