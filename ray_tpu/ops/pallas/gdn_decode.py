"""Pallas TPU kernel: one decode step of the Gated DeltaNet recurrence
over the serving engine's per-slot state pool (ops/gated_deltanet.py).

    S <- alpha S (I - beta k k^T) + beta v k^T ,   o = S q

In plain XLA that is three passes over S (S k, the rank-one update,
S q): the state read three times and written once. Here one grid step
takes one slot's whole state `(d_k, H * d_v)` float32 (S transposed,
the heads side by side in the lanes: 96 x 5 760 = 2.2 MB at the
published widths, every (8, 128) tile full) into VMEM, does the step
and writes it back IN PLACE (`input_output_aliases`): read once,
written once, the copies of the next slot running under the work on
this one.

Inside a step the heads are walked in groups whose columns are whole
128-lane tiles (two heads of 192 = 384 columns). A head's k, q and
decay a key channel are columns of `(d_k, H)` inputs broadcast along the
lanes, so with kx, qx, ax the group's (d_k, columns) expansions and b, v
its rows of beta and value:

    Sd = S * ax ; u = b * (v - sum_k Sd * kx) ; S' = Sd + kx * u
    o  = sum_k S' * qx

All of it elementwise work and sublane sums in float32; no matrix unit.

Rows that are not decoding (an empty slot, a slot between two chunks of
its prompt, the scratch row) come with alpha = 1, beta = 0 and k = 0
from the caller and are WRITTEN THROUGH UNCHANGED: the grid is every
row of the pool, which is what the engine counts as
`decode_state_rows_window` beside the `decode_state_rows_live` that
moved. Inference only: no backward pass.

ONE kernel for a decay a head (Gated DeltaNet: `gdn_decode_step`, the
head's rate in every key channel) and a decay a key channel (Kimi Delta
Attention: `kda_decode_step`; ops/gated_deltanet.py), under the name
its caller's metrics look for. At Olmo-Hybrid-7B's shape (30 heads of
96 x 192, 65 rows) the kernel with the decay as a third expanded input
takes 0.4376 ms, what the kernel with the decay as a row took (0.4376:
tools/kda_microbench.py on the chip, PERF.md, PR 52), and gives the same
bits. One slot's state is 128 x 8 192 float32 = 4 MiB at Solar-Open2's
widths, in and out double-buffered 16 MiB of VMEM_LIMIT_BYTES.

A third recurrence is one static arm of the same kernel
(`ssm_decode_step`; ops/ssm.py): Mamba-2's state-space step is the delta
rule without its correction, u = b * v, so the `sum_k Sd * kx` pass is
skipped; its decay is a scalar a head and rides as a third row beside v
and beta, and its k and q are a GROUP's columns (G of them for H heads:
`share` = H / G heads read one), so the `at` input is gone and the
expansion of two heads of one group is one broadcast. Its state is
128 x 8 192 a slot too.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# one slot's state in and out, double-buffered, at the published widths:
# 4 x 2.2 MB, over the 16 MiB a kernel gets unasked
VMEM_LIMIT_BYTES = 32 * 1024 * 1024


def heads_per_group(n_heads: int, d_v: int) -> int:
    """Heads walked together: the fewest whose columns are whole lane
    tiles, or all of them where the heads do not divide so."""
    n = 128 // math.gcd(d_v, 128)
    return n if n_heads % n == 0 else n_heads


def _kernel(*refs, n_heads: int, d_v: int, group: int, correct: bool = True,
            share: int = 1):
    """`correct` False is the state-space arm (module docstring): no
    `at` input, the decay a third row of `rows`, and column h // share
    of q and k for head h."""
    if correct:
        qt_ref, kt_ref, at_ref, rows_ref, s_ref, o_ref, s_out_ref = refs
    else:
        qt_ref, kt_ref, rows_ref, s_ref, o_ref, s_out_ref = refs
    d_k = s_ref.shape[1]
    width = group * d_v
    lane = jax.lax.broadcasted_iota(jnp.int32, (d_k, width), 1)
    qt, kt = qt_ref[0], kt_ref[0]                         # (d_k, H)
    at = at_ref[0] if correct else None

    def expand(cols, h0):
        """(d_k, width): head h0 + j's column over its d_v lanes."""
        at_col = lambda j: (h0 + j) // share              # noqa: E731
        out = jnp.broadcast_to(cols[:, at_col(0):at_col(0) + 1],
                               (d_k, width))
        for j in range(1, group):
            if at_col(j) == at_col(j - 1):
                continue
            out = jnp.where(lane >= j * d_v, jnp.broadcast_to(
                cols[:, at_col(j):at_col(j) + 1], (d_k, width)), out)
        return out

    for gi in range(n_heads // group):
        cols = slice(gi * width, (gi + 1) * width)
        v = rows_ref[0, 0:1, cols]
        b = rows_ref[0, 1:2, cols]
        kx = expand(kt, gi * group)
        if correct:
            sd = s_ref[0, :, cols] * expand(at, gi * group)
            u = b * (v - jnp.sum(sd * kx, axis=0, keepdims=True))
        else:
            sd = s_ref[0, :, cols] * rows_ref[0, 2:3, cols]
            u = b * v
        new = sd + kx * u
        s_out_ref[0, :, cols] = new
        o_ref[0, :, cols] = jnp.sum(new * expand(qt, gi * group), axis=0,
                                    keepdims=True)


def _decode_step(q, k, v, g, beta, state, name: str, interpret,
                 correct: bool = True):
    """`correct` (static) False: the state-space arm. q and k are then
    (B, G, d_k), a column for each run of H / G heads, and g (B, H)."""
    b, h, d_v = v.shape
    cols_n, d_k = q.shape[1:]
    hv = h * d_v
    assert state.shape == (b, d_k, hv) and state.dtype == jnp.float32, \
        (state.shape, state.dtype)
    assert g.shape == ((b, h, d_k) if correct else (b, h)), g.shape
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    f32 = jnp.float32
    # a frozen row's k is zeroed too: 0 x a garbage k stays 0
    live = (beta != 0)[..., None] if correct \
        else (beta != 0).any(-1)[:, None, None]
    k = jnp.where(live, k.astype(f32), 0.0)
    rows = [v.astype(f32).reshape(b, hv),
            jnp.repeat(beta.astype(f32), d_v, axis=-1)]
    if not correct:
        rows.append(jnp.repeat(jnp.exp(g.astype(f32)), d_v, axis=-1))
    rows = jnp.stack(rows, axis=1)                        # (B, 2 or 3, HV)
    kernel = functools.partial(_kernel, n_heads=h, d_v=d_v,
                               group=heads_per_group(h, d_v),
                               correct=correct, share=h // cols_n)
    row3 = lambda i: (i, 0, 0)                                # noqa: E731
    cols = pl.BlockSpec((1, d_k, cols_n), row3)
    decay = [jnp.swapaxes(jnp.exp(g.astype(f32)), 1, 2)] if correct else []
    n_in = 4 + len(decay)
    o, new_state = pl.pallas_call(
        kernel,
        grid=(b,),
        in_specs=[cols] * (n_in - 2)
        + [pl.BlockSpec((1, rows.shape[1], hv), row3),
           pl.BlockSpec((1, d_k, hv), row3)],
        out_specs=[pl.BlockSpec((1, 1, hv), row3),
                   pl.BlockSpec((1, d_k, hv), row3)],
        out_shape=[jax.ShapeDtypeStruct((b, 1, hv), f32),
                   jax.ShapeDtypeStruct((b, d_k, hv), f32)],
        input_output_aliases={n_in - 1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=pltpu.InterpretParams() if interpret else False,
        name=name,
    )(jnp.swapaxes(q.astype(f32), 1, 2), jnp.swapaxes(k, 1, 2), *decay,
      rows, state)
    return o.reshape(b, h, d_v), new_state


@functools.partial(jax.jit, static_argnames=("interpret",))
def gdn_decode_step(q, k, v, g, beta, state, interpret=None):
    """q, k (B, H, d_k) normalised, v (B, H, d_v), g = log alpha and
    beta (B, H), state (B, d_k, H * d_v) float32, updated in place.
    A row to leave alone comes with g = 0 and beta = 0. Returns
    (o (B, H, d_v) float32, new state). interpret defaults to True only
    on the CPU backend."""
    return _decode_step(q, k, v, jnp.broadcast_to(g[..., None], q.shape),
                        beta, state, "gdn_decode_step", interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_decode_step(q, k, v, g, beta, state, interpret=None):
    """`gdn_decode_step` with g = log alpha (B, H, d_k), a rate a key
    channel."""
    return _decode_step(q, k, v, g, beta, state, "kda_decode_step",
                        interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_decode_step(q, k, v, g, beta, state, interpret=None):
    """The state-space arm (ops/ssm.py:step): q = C and k = B (B, G, N),
    a group's for its H / G heads, v = xs (B, H, P), g = dt A and
    beta = dt (B, H), state (B, N, H * P) float32, updated in place. A
    row to leave alone comes with g = 0 and beta = 0. Returns (y
    (B, H, P) float32 without the skip, new state)."""
    return _decode_step(q, k, v, g, beta, state, "ssm_decode_step",
                        interpret, correct=False)
