"""Pallas TPU kernel: single-token decode attention over a paged pool of
latents (the absorbed form of latent attention).

A latent-attention layer caches one vector a token (ops/attention.py:
PagedLatent, `(n_flat, W)`: the normed latent of `d_v` columns, then the
rotated rope key). With the key's up-projection folded into the query
and the value's into the output, decode is multi-query attention: every
query head of width W against the same key, whose first `d_v` columns
are also the value. This kernel is `paged_attention.py`'s plan over that
pool:

  * the grid is one step a sequence; the pool stays in HBM
    (`memory_space=ANY`), a loop walks the row's live pages in blocks of
    `n` pages (`choose_pages_per_block`), each page one contiguous DMA
    into a double-buffered VMEM block; the next block's copies (the next
    live row's first block included) start before the current block is
    computed, so empty slots, the scratch row and the dead tail of a
    decode window cost no copy and no product;
  * page table, lengths and query positions ride in SMEM;
  * a block is read ONCE for both products: `q (H, W) x block (T, W)^T`
    gives the scores, `P (H, T) x block[:, :d_v]` the result;
  * scores, running maximum, sum and accumulator are float32 (online
    softmax across blocks), operands keep the pool's dtype.

W need not be a multiple of the 128 lanes (576 = 512 + 64 as published):
the pool's rows are padded to whole lane tiles in HBM and in VMEM
anyway, and the kernel reads them as they lie.

Inference only: no backward pass is defined.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import (NEG_INF, VMEM_BUDGET_BYTES, VMEM_LIMIT_BYTES,
                              _cdiv)

# tokens a block should hold before a loop turn's fixed cost (DMA issue,
# semaphore waits, a rescale of the accumulator) stops showing
_PREFERRED_TOKENS = 2048


def vmem_bytes(n: int, page_size: int, h: int, w: int, itemsize: int) -> int:
    """VMEM one block of `n` pages needs: the block double-buffered (rows
    padded to whole 128-lane tiles) plus four float32 (H, tokens) tiles
    of scores."""
    tokens = n * page_size
    return 2 * tokens * (-(-w // 128) * 128) * itemsize + 4 * h * tokens * 4


def choose_pages_per_block(n_pages: int, page_size: int, h: int, w: int,
                           dtype) -> int:
    """Pages a block holds: the power of two whose tokens come nearest
    `_PREFERRED_TOKENS` from below, cut to the window and halved until
    `vmem_bytes` fits VMEM_BUDGET_BYTES. A pure function of what the call
    can observe; no knob."""
    itemsize = jnp.dtype(dtype).itemsize
    n = 1
    while 2 * n * page_size <= _PREFERRED_TOKENS and 2 * n <= n_pages:
        n *= 2
    while n > 1 and vmem_bytes(n, page_size, h, w,
                               itemsize) > VMEM_BUDGET_BYTES:
        n //= 2
    return n


def _decode_kernel(pt_ref, len_ref, qpos_ref, q_ref, c_hbm, o_ref,
                   cbuf, sems, nxt_ref, slot_ref, *,
                   scale: float, page_size: int, d_v: int, n_blk: int,
                   n_rows: int, n_table: int):
    s = pl.program_id(0)
    blk_tokens = n_blk * page_size
    h, w = q_ref.shape[1:]

    def seq_len(r):
        # keys at positions <= the query's own AND < the sequence's
        # length, inside the window: `_attend_cached`'s masks
        return jnp.minimum(jnp.minimum(len_ref[r], qpos_ref[r] + 1),
                           n_table * page_size)

    def block_copies(r, b, slot, act):
        """`act` (start or wait) on the copies of block `b` of row `r`:
        its live pages only, into buffer `slot`."""
        live = jnp.minimum(_cdiv(seq_len(r), page_size) - b * n_blk, n_blk)

        def page(i, carry):
            src = pt_ref[r * n_table + b * n_blk + i]
            act(pltpu.make_async_copy(
                c_hbm.at[src], cbuf.at[slot, i], sems.at[slot]))
            return carry
        jax.lax.fori_loop(0, live, page, 0)

    @pl.when(s == 0)
    def _first():
        # stale rows of a block are masked out of the scores, but in
        # P x V a masked 0 times a NaN left in VMEM is a NaN
        cbuf[...] = jnp.zeros_like(cbuf)

        def scan(i, nxt):               # next live row after each row
            r = n_rows - 1 - i
            nxt_ref[r] = nxt
            return jnp.where(seq_len(r) > 0, r, nxt)
        first = jax.lax.fori_loop(0, n_rows, scan, jnp.int32(n_rows))
        slot_ref[0] = 0

        @pl.when(first < n_rows)
        def _():
            block_copies(first, 0, 0, lambda c: c.start())

    length = seq_len(s)
    n_blocks = _cdiv(length, blk_tokens)
    col = jax.lax.broadcasted_iota(jnp.int32, (h, blk_tokens), 1)

    def block(b, carry):
        m_prev, l_prev, acc = carry
        slot = slot_ref[0]
        last = b + 1 == n_blocks
        nr = jnp.where(last, nxt_ref[s], s)
        nb = jnp.where(last, 0, b + 1)

        @pl.when(nr < n_rows)
        def _():
            block_copies(nr, nb, 1 - slot, lambda c: c.start())
        block_copies(s, b, slot, lambda c: c.wait())
        slot_ref[0] = 1 - slot

        q = q_ref[0]                                    # (H, W)
        c = cbuf[slot].reshape(blk_tokens, w)           # (T, W)
        scores = jax.lax.dot_general(
            q, c, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        scores = jnp.where(col < length - b * blk_tokens, scores, NEG_INF)
        m_cur = jnp.max(scores, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        pexp = jnp.exp(scores - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = corr * l_prev + jnp.sum(pexp, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            pexp.astype(c.dtype), c[:, :d_v], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # (H, d_v)
        return m_new, l_new, acc * corr + pv

    _m, l, acc = jax.lax.fori_loop(
        0, n_blocks, block,
        (jnp.full((h, 1), NEG_INF, jnp.float32),
         jnp.zeros((h, 1), jnp.float32),
         jnp.zeros((h, d_v), jnp.float32)))
    # a row with no key (an empty slot, the scratch row) gives zeros
    o_ref[0] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


# jitted so that a model's layers trace the kernel once a program
@functools.partial(jax.jit, static_argnames=(
    "page_size", "d_v", "scale", "interpret", "pages_per_block"))
def latent_decode_attention(q, flat, page_table, lengths, page_size: int,
                            *, d_v: int, scale: float, qpos=None,
                            interpret: "bool | None" = None,
                            pages_per_block: "int | None" = None):
    """q: (S, H, W) one absorbed query per sequence (the pool already
    holds its token's latent); flat: (N_flat, W) the layer's pool;
    page_table: (S, P) int32; lengths: (S,) int32, keys valid at
    positions < lengths; qpos: (S,) int32 query positions (keys at
    positions <= qpos attend; default lengths - 1). The first `d_v`
    columns of a pool row are the value. interpret defaults to True only
    on the CPU backend; pages_per_block is for the tests. Returns
    (S, H, d_v)."""
    s_n, h, w = q.shape
    n_flat = flat.shape[0]
    assert n_flat % page_size == 0 and flat.shape[1] == w, (flat.shape, w)
    P = page_table.shape[1]
    if qpos is None:
        qpos = lengths - 1
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    n_blk = pages_per_block or choose_pages_per_block(
        P, page_size, h, w, flat.dtype)
    kernel = functools.partial(
        _decode_kernel, scale=scale, page_size=page_size, d_v=d_v,
        n_blk=n_blk, n_rows=s_n, n_table=P)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,             # page_table, lengths, qpos
        grid=(s_n,),
        in_specs=[
            pl.BlockSpec((1, h, w), lambda s, pt, ln, qp: (s, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, h, d_v), lambda s, pt, ln, qp: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, n_blk, page_size, w), flat.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((s_n,), jnp.int32),     # next live row
            pltpu.SMEM((1,), jnp.int32),       # buffer the next block reads
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_n, h, d_v), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # a step starts the copies the next one waits for
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="latent_decode_attention",
    )(page_table.reshape(-1), lengths, jnp.asarray(qpos, jnp.int32), q,
      flat.reshape(n_flat // page_size, page_size, w))
