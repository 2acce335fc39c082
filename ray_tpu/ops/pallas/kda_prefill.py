"""Pallas TPU kernel: the chunkwise form of the delta rule with a decay a
key channel (Kimi Delta Attention; ops/gated_deltanet.py:
`_chunk_scan_channel`, which stays the reference the tests hold this
to), for the serving engine's prefill over a per-slot state.

The plain form is a `lax.scan` over chunks whose body cannot be hoisted
(the (sub-chunk x sub-chunk x d_k) exponent blocks are 8.4 MB a chunk a
row at the published widths) and compiles to 51 fusions and 15 copies
an iteration, over every chunk of the bucket. Here the grid is (row,
head group, chunk), the chunk axis sequential:

  * a head group's state `(d_k, group * d_v)` float32 is read from the
    row's state once, stays in VMEM (the output block, whose index does
    not move along the chunk axis) from the first chunk to the last and
    is written once, in place (`input_output_aliases`);
  * a grid step takes the chunk's q, k, v, g, beta blocks (q, k, g a
    head at a time, (B, H, S, d_k): a layout the fusions that make them
    can write themselves; v as the convolution leaves it) and does, a
    head at a time, what the scan's body does. With L the summed rate
    inside a sub-chunk of SUB_CHUNK tokens, E the summed rate at the
    sub-chunk's edge and G = L + E:
      - the blocks under the diagonal of A (k . k) and P (q . k) through
        the edge factors, (x_t exp(L_t)) . (k_s exp(E - G_s)), both
        exponents <= 0: one product a row sub-chunk, k's and q's rows
        stacked;
      - the diagonal blocks with the difference inside the exponent
        (exp(L_t - L_s), s <= t: never a product of two exponentials,
        which overflows for a fast channel), a column s of all the
        sub-chunks at once: elementwise work and a sum over the lanes;
      - (I + diag(beta) A) W = beta V - (beta K Gamma) S_0 solved by
        block forward substitution: inside a sub-chunk column by column
        (a rank-one update of its 16 rows, the columns of A as the lane
        sums left them), across sub-chunks one product with the blocks
        under the diagonal. No inverse is formed and no block that is
        zero is multiplied;
      - O = (Q Gamma) S_0 + P W and S_C = Diag(Gamma_C) S_0 +
        (K Gamma_C / Gamma)^T W.
    float32 everywhere and every product at the highest precision (six
    bfloat16 passes), as in the plain form;
  * the rows' true lengths `n_new` ride in SMEM (scalar prefetch). A
    chunk whose first position is at or past `n_new[row]` does nothing
    but zero its output rows, and the index maps of q, k, v, g, beta
    clamp to the row's last live chunk, so no copy is issued for it
    (what ops/pallas/paged_attention.py does with a row's pages): the
    state written is the state after the last live chunk. Positions
    past `n_new` inside the last live chunk come frozen from the caller
    (`gated_deltanet.freeze`: g = 0, beta = 0).

Inference only: no backward pass (training and the plain forward keep
the `jax.numpy` form).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..gated_deltanet import SUB_CHUNK

_HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
VMEM_LIMIT_BYTES = 32 * 1024 * 1024
# heads a grid step walks, so that its fixed cost (the copies' issue,
# the pipeline's bookkeeping) is spread over them: 4 / 8 / 16 read 86.5 /
# 84.8 / 84.0 us a chunk a row of 64 heads (PERF.md, PR 53)
_HEADS_PER_STEP = 8


def heads_per_step(n_heads: int) -> int:
    """Heads a grid step takes: `_HEADS_PER_STEP` where they divide,
    else all of them."""
    return _HEADS_PER_STEP if n_heads % _HEADS_PER_STEP == 0 else n_heads


def _mm(a, b):
    return jnp.dot(a, b, precision=_HI, preferred_element_type=F32)


def _mm_nt(a, b):
    """a (m, d) . b (n, d)^T."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=_HI, preferred_element_type=F32)


def _mm_tn(a, b):
    """a (t, m)^T . b (t, n)."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               precision=_HI, preferred_element_type=F32)


def _rows(x, n: int):
    """(1, d) -> (n, d)."""
    return jnp.broadcast_to(x, (n, x.shape[1]))


def _head(q, k, v, g, beta, st, sc: int):
    """One head, one chunk. q, k, g (c, d_k), v (c, d_v), beta (c, 1),
    st (d_k, d_v), all float32. Returns (o (c, d_v), new state)."""
    c, dk = k.shape
    dv = v.shape[1]
    ns = c // sc
    pos = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    off = jax.lax.rem(pos, jnp.int32(sc))       # place inside the sub-chunk

    # L: the summed rate inside each sub-chunk, inclusive
    cum = g
    sh = 1
    while sh < sc:
        cum = cum + jnp.where(off >= sh, pltpu.roll(cum, sh, 0), 0.0)
        sh *= 2
    # E: the summed rate at each sub-chunk's edge; G = L + E
    edges = [jnp.zeros((1, dk), F32)]
    for i in range(ns):
        edges.append(edges[i] + cum[(i + 1) * sc - 1:(i + 1) * sc])
    total = edges[ns]                                        # (1, d_k)
    gc = cum + jnp.concatenate([_rows(e, sc) for e in edges[:ns]], axis=0)
    decay = jnp.exp(gc)
    inner = jnp.exp(cum)                                     # <= 1
    k_in, q_in = k * inner, q * inner

    # what the state gives: (beta K Gamma) S_0 and (Q Gamma) S_0
    from_state = _mm(jnp.concatenate([(k * beta) * decay, q * decay],
                                     axis=0), st)            # (2c, d_v)
    u = v * beta - from_state[:c]

    # blocks under the diagonal, a row sub-chunk at a time: columns
    # before its edge, referred to the edge
    under = [jnp.zeros((2 * sc, c), F32)]
    for i in range(1, ns):
        before = pos < i * sc
        cols = jnp.where(before, k * jnp.exp(
            jnp.where(before, edges[i] - gc, 0.0)), 0.0)
        rows = slice(i * sc, (i + 1) * sc)
        under.append(_mm_nt(jnp.concatenate([k_in[rows], q_in[rows]],
                                            axis=0), cols))  # (2 sc, c)

    # diagonal blocks: column jj of every sub-chunk at once
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    own = jax.lax.div(col, jnp.int32(sc)) == jax.lax.div(pos, jnp.int32(sc))
    col_off = jax.lax.rem(col, jnp.int32(sc))
    a_cols = []
    p_diag = jnp.zeros((c, c), F32)
    for jj in range(sc):
        k_s = jnp.concatenate(
            [_rows(k[i * sc + jj:i * sc + jj + 1], sc) for i in range(ns)],
            axis=0)
        l_s = jnp.concatenate(
            [_rows(cum[i * sc + jj:i * sc + jj + 1], sc)
             for i in range(ns)], axis=0)
        at_or_after = off >= jj
        m = k_s * jnp.where(at_or_after, jnp.exp(
            jnp.where(at_or_after, cum - l_s, 0.0)), 0.0)
        a_cols.append(jnp.where(
            off > jj, beta * jnp.sum(k * m, axis=1, keepdims=True), 0.0))
        p_diag = jnp.where(own & (col_off == jj),
                           jnp.sum(q * m, axis=1, keepdims=True), p_diag)

    # (I + diag(beta) A) W = U, sub-chunk by sub-chunk
    done = []
    for i in range(ns):
        rows = slice(i * sc, (i + 1) * sc)
        u_i = u[rows]
        if i:
            so_far = jnp.concatenate(
                done + [jnp.zeros((c - i * sc, dv), F32)], axis=0)
            u_i = u_i - beta[rows] * _mm(under[i][:sc], so_far)
        for jj in range(sc - 1):
            u_i = u_i - a_cols[jj][rows] * u_i[jj:jj + 1]
        done.append(u_i)
    w = jnp.concatenate(done, axis=0)                        # (c, d_v)

    p = p_diag + jnp.concatenate([x[sc:] for x in under], axis=0)
    o = from_state[c:] + _mm(p, w)
    k_out = k * jnp.exp(total - gc)
    carried = jnp.transpose(jnp.broadcast_to(jnp.exp(total), (dk, dk)))
    return o, st * carried[:, :1] + _mm_tn(k_out, w)


def _kernel(n_new_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, s_ref,
            o_ref, s_out_ref, *, sc: int):
    r, ci = pl.program_id(0), pl.program_id(2)
    _, group, c, _ = q_ref.shape
    d_v = o_ref.shape[3]
    # two heads a loop turn: their chains of small products are
    # independent, so the scheduler has one head's vector work to put
    # under the other's matrix unit latency
    pair = 2 if group % 2 == 0 else 1

    @pl.when(ci == 0)
    def _():
        s_out_ref[...] = s_ref[...]

    live = ci * c < n_new_ref[r]

    @pl.when(live)
    def _():
        lane = jax.lax.broadcasted_iota(jnp.int32, (c, group), 1)
        betas = beta_ref[0, 0].astype(F32)                   # (c, group)

        def heads(i, carry):
            for j in (i * pair + u for u in range(pair)):
                cols = pl.ds(pl.multiple_of(j * d_v, d_v), d_v)
                o_ref[0, j], s_out_ref[0, :, cols] = _head(
                    q_ref[0, j].astype(F32), k_ref[0, j].astype(F32),
                    v_ref[0, :, cols].astype(F32), g_ref[0, j].astype(F32),
                    jnp.sum(jnp.where(lane == j, betas, 0.0), axis=1,
                            keepdims=True),
                    s_out_ref[0, :, cols], sc)
            return carry
        jax.lax.fori_loop(0, group // pair, heads, 0)

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def kda_chunk_scan(q, k, v, g, beta, state=None, n_new=None,
                   chunk: int = 64, interpret=None):
    """`gated_deltanet.chunk_scan` with a rate a key channel. q, k, g
    (B, S, H, d_k), v (B, S, H, d_v), beta (B, S, H), state (B, d_k,
    H * d_v) float32 or None (zeros), n_new (B,) int32 the rows' true
    lengths or None (S): positions at or past it come frozen (g = 0,
    beta = 0). Returns (o (B, S, H, d_v) float32, zero in the chunks
    past `n_new`; the new state). interpret defaults to True only on
    the CPU backend."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    sc = min(SUB_CHUNK, chunk)
    if chunk % sc:
        raise ValueError(f"chunk={chunk} is no multiple of {sc}")
    c = min(chunk, -(-s // sc) * sc)
    n = -(-s // c)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    if state is None:
        state = jnp.zeros((b, dk, h * dv), F32)
    assert state.shape == (b, dk, h * dv) and state.dtype == F32, \
        (state.shape, state.dtype)
    n_new = (jnp.full((b,), s, jnp.int32) if n_new is None
             else jnp.minimum(n_new.astype(jnp.int32), s))
    group = heads_per_step(h)

    def by_head(x):
        """(B, S, H, d) -> (B, H, n c, d): a head's chunk is whole
        tiles. The fusions that make q, k and g in a layer write this
        layout themselves (the transpose compiles to a bitcast at their
        roots); a block cut out of (S, H, d) gathers a head's rows a
        sublane at a time and took the kernel twice as long, and
        (S, H d) costs a copy of each array in front of it."""
        x = jnp.swapaxes(x.reshape(b, s, h, -1), 1, 2)
        return jnp.pad(x, ((0, 0), (0, 0), (0, n * c - s), (0, 0)))

    def live_chunk(r, ci, n_new):
        # a dead chunk's blocks are the last live chunk's: no new copy
        return jnp.minimum(ci, jnp.maximum(
            jax.lax.div(n_new[r] + (c - 1), jnp.int32(c)) - 1, 0))

    def heads_of(width):
        return pl.BlockSpec(
            (1, group, c, width),
            lambda r, hg, ci, n_new: (r, hg, live_chunk(r, ci, n_new), 0))
    states = pl.BlockSpec((1, dk, group * dv),
                          lambda r, hg, ci, n_new: (r, 0, hg))
    # beta a head group, (B, H / group, n c, group): a column of a tile
    # a head, not a tile a token
    beta = jnp.pad(beta, ((0, 0), (0, n * c - s), (0, 0))).reshape(
        b, n * c, h // group, group).transpose(0, 2, 1, 3)
    # v stays as the convolution leaves it, the heads side by side
    v = jnp.pad(v.reshape(b, s, h * dv), ((0, 0), (0, n * c - s), (0, 0)))
    o, new_state = pl.pallas_call(
        functools.partial(_kernel, sc=sc),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, h // group, n),
            in_specs=[
                heads_of(dk), heads_of(dk),
                pl.BlockSpec((1, c, group * dv), lambda r, hg, ci, n_new: (
                    r, live_chunk(r, ci, n_new), hg)),
                heads_of(dk),
                pl.BlockSpec((1, 1, c, group), lambda r, hg, ci, n_new: (
                    r, hg, live_chunk(r, ci, n_new), 0)),
                states],
            out_specs=[
                pl.BlockSpec((1, group, c, dv),
                             lambda r, hg, ci, n_new: (r, hg, ci, 0)),
                states]),
        out_shape=[jax.ShapeDtypeStruct((b, h, n * c, dv), F32),
                   jax.ShapeDtypeStruct((b, dk, h * dv), F32)],
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="kda_chunk_scan",
    )(n_new, by_head(q), by_head(k), v, by_head(g), beta, state)
    return jnp.swapaxes(o, 1, 2)[:, :s], new_state
