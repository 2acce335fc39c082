"""Pallas TPU kernel: single-token decode attention over a paged KV pool.

The serve engine's paged cache (ops/attention.py:PagedKV) stores KV as
flat token rows `(n_flat, Hkv, D)` in a shared pool with per-sequence
page tables. This kernel reads a sequence's pages where they lie and
does work only for the pages the sequence has:

  * the grid is one step a sequence. The pools stay in HBM
    (`memory_space=ANY`); inside a step a loop walks the row's live
    pages in blocks of `n` pages (`choose_pages_per_block`), each page
    one contiguous DMA into a double-buffered VMEM block. The copies of
    the next block (the next live row's first block included) are
    started before the current block is computed, so an empty slot, the
    scratch row and the dead tail of a decode window cost no copy and
    no product: time follows `ceil(min(len, qpos + 1) / page_size)`
    summed over rows, not rows x window;
  * the page table, lengths and query positions ride in SMEM (scalar
    prefetch); the table is flat so that SMEM pads nothing;
  * the pool is viewed as `(pages, page_size * Hkv, D)`: a token's
    heads are consecutive rows, which on the chip is the layout the pool
    already has (Hkv a multiple of the 8-row tile and D of the 128
    lanes: a bitcast, no copy; another head count costs a copy of the
    pool a call). Heads narrower than 128 lanes come PACKED, `pack` of
    them side by side in a pool row of 128 lanes
    (ops/attention.py:packed_kv_shape), and the same plan runs with a
    pool row standing for a KV head: each query head is spread into
    the lanes of its KV head with zeros beside it, and its own lanes
    are cut out of the result. All heads go
    through ONE product a block: `q (Hq, D) x block (T * Hkv, D)^T`
    gives every query head against every (token, kv head) row and the
    mask keeps the columns of the head's own group; `P x V` is the
    same product the other way. The matrix unit is bound by loading the
    block, not by the rows of `q`, so the crossed terms are free and
    nothing is sliced, transposed or concatenated: GQA (`rep` 4) and
    MHA (`rep` 1) are the same code;
  * scores, running maximum, sum and accumulator are float32 (online
    softmax across blocks), operands keep the pool's dtype.

Decode is inference-only: no backward pass is defined (the training
path never runs paged attention).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# what one block may take of VMEM: two buffers each for K and V, and
# the float32 score tiles (scores, exponentials, mask, the cast copy)
VMEM_BUDGET_BYTES = 12 * 1024 * 1024
VMEM_LIMIT_BYTES = 32 * 1024 * 1024
# rows of (token, kv head) a block should hold before the fixed cost of
# a loop turn (DMA issue, semaphore waits, a rescale of the accumulator)
# stops showing
_PREFERRED_ROWS = 4096


def vmem_bytes(n: int, page_size: int, hq: int, hkv: int, d: int,
               itemsize: int) -> int:
    """VMEM one block of `n` pages needs (`d` a multiple of the 128
    lanes): K and V double-buffered plus four float32 (Hq, rows) tiles
    of scores."""
    rows = n * page_size * hkv
    return 4 * rows * d * itemsize + 4 * hq * rows * 4


def choose_pages_per_block(n_pages: int, page_size: int, hq: int, hkv: int,
                           d: int, dtype) -> int:
    """Pages a block holds: the power of two whose (token, kv head)
    rows come nearest `_PREFERRED_ROWS` from below, cut to the window
    and halved until `vmem_bytes` fits VMEM_BUDGET_BYTES. A pure
    function of what the call can observe; no knob."""
    itemsize = jnp.dtype(dtype).itemsize
    n = 1
    while 2 * n * page_size * hkv <= _PREFERRED_ROWS and 2 * n <= n_pages:
        n *= 2
    while n > 1 and vmem_bytes(n, page_size, hq, hkv, d,
                               itemsize) > VMEM_BUDGET_BYTES:
        n //= 2
    return n


def _cdiv(a, b: int):
    return jax.lax.div(a + (b - 1), jnp.int32(b))


def _decode_kernel(pt_ref, len_ref, qpos_ref, q_ref, k_hbm, v_hbm, o_ref,
                   kbuf, vbuf, sems, nxt_ref, slot_ref, *,
                   scale: float, page_size: int, n_kv: int, rep: int,
                   n_blk: int, n_rows: int, n_table: int):
    s = pl.program_id(0)
    blk_tokens = n_blk * page_size
    hq, d = q_ref.shape[1:]

    def seq_len(r):
        # causal bound: keys at positions <= the query's own position
        # AND < the sequence length — identical masking to
        # _attend_cached, so a replay query at an EARLIER position
        # (positions < lengths-1, e.g. speculative-decode verification)
        # can't see future keys
        # (and inside the window: the table has no page beyond it)
        return jnp.minimum(jnp.minimum(len_ref[r], qpos_ref[r] + 1),
                           n_table * page_size)

    def block_copies(r, b, slot, act):
        """`act` (start or wait) on the copies of block `b` of row `r`:
        its live pages only, K and V, into buffer `slot`."""
        live = jnp.minimum(_cdiv(seq_len(r), page_size) - b * n_blk, n_blk)

        def page(i, carry):
            src = pt_ref[r * n_table + b * n_blk + i]
            for hbm, buf, j in ((k_hbm, kbuf, 0), (v_hbm, vbuf, 1)):
                act(pltpu.make_async_copy(
                    hbm.at[src], buf.at[slot, i], sems.at[j, slot]))
            return carry
        jax.lax.fori_loop(0, live, page, 0)

    @pl.when(s == 0)
    def _first():
        # stale rows of a block are masked out of the scores, but in
        # P x V a masked 0 times a NaN left in VMEM is a NaN
        vbuf[...] = jnp.zeros_like(vbuf)

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

    # a column of a block is (token t, kv head h) at t * n_kv + h; a
    # query head sees the columns of its own group
    shape = (hq, blk_tokens * n_kv)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    own = jax.lax.rem(col, jnp.int32(n_kv)) == jax.lax.div(
        row, jnp.int32(rep))

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

        q = q_ref[0]                                    # (Hq, D)
        k = kbuf[slot].reshape(shape[1], d)             # (T * Hkv, D)
        v = vbuf[slot].reshape(shape[1], d)
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        live = (length - b * blk_tokens) * n_kv         # columns < length
        scores = jnp.where(own & (col < live), scores, NEG_INF)
        m_cur = jnp.max(scores, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        pexp = jnp.exp(scores - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = corr * l_prev + jnp.sum(pexp, axis=1, keepdims=True)
        pv = jax.lax.dot_general(
            pexp.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)         # (Hq, D)
        return m_new, l_new, acc * corr + pv

    _m, l, acc = jax.lax.fori_loop(
        0, n_blocks, block,
        (jnp.full((hq, 1), NEG_INF, jnp.float32),
         jnp.zeros((hq, 1), jnp.float32),
         jnp.zeros((hq, d), jnp.float32)))
    # a row with no key (an empty slot, the scratch row) gives zeros
    o_ref[0] = (acc / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


# jitted so that a model's layers trace the kernel once a program, not
# once a layer: 16 traces were 3.5 s of a decode program's first call on
# the chip's host (PERF.md, PR 29)
@functools.partial(jax.jit, static_argnames=(
    "page_size", "scale", "interpret", "pages_per_block"))
def paged_decode_attention(q, k_flat, v_flat, page_table, lengths,
                           page_size: int,
                           qpos=None,
                           scale: "float | None" = None,
                           interpret: "bool | None" = None,
                           pages_per_block: "int | None" = None):
    """q: (S, Hq, D) one decode token per sequence (cache already holds
    its KV); k_flat/v_flat: (N_flat, Hkv, D) page pools; page_table:
    (S, P) int32; lengths: (S,) int32 — keys valid at positions
    < lengths. qpos: (S,) int32 query positions (causal bound: keys at
    positions <= qpos attend; default lengths-1, the decode-at-end
    case). interpret defaults to True only on the CPU backend.
    pages_per_block is for the microbenchmark and the tests; callers
    leave it to `choose_pages_per_block`. Returns (S, Hq, D)."""
    s_n, hq, d_model = q.shape
    n_flat, hkv, d_pool = k_flat.shape
    assert n_flat % page_size == 0, (n_flat, page_size)
    if scale is None:
        scale = d_model ** -0.5
    # a packed pool (ops/attention.py:packed_kv_shape): a pool row is
    # 128 lanes holding `pack` narrow heads side by side, and the plan
    # below runs unchanged with a pool row standing for a KV head: a
    # query head lies in the lanes of its KV head with zeros beside it,
    # so its scores are exact (the matrix unit is bound by loading the
    # block, the zeros' products are free), and of the result's lanes
    # its own are taken
    pack = d_pool // d_model
    if pack > 1:
        q = _spread_over_lanes(q, hkv, pack)
    rep = hq // hkv
    # an unpacked pool of heads under 128 wide (a caller of its own:
    # the engine's pools are packed): Mosaic refuses a DMA out of an
    # array narrower than a lane tile, so q, K and V are padded with
    # zeros, which costs a copy of the pool a call
    d = -(-d_pool // 128) * 128
    if d != d_pool:
        pad = ((0, 0), (0, 0), (0, d - d_pool))
        q, k_flat, v_flat = (jnp.pad(x, pad) for x in (q, k_flat, v_flat))
    P = page_table.shape[1]
    if qpos is None:
        qpos = lengths - 1
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    n_blk = pages_per_block or choose_pages_per_block(
        P, page_size, hq, hkv, d, k_flat.dtype)
    n_pages, page_rows = n_flat // page_size, page_size * hkv

    kernel = functools.partial(
        _decode_kernel, scale=scale, page_size=page_size, n_kv=hkv,
        rep=rep, n_blk=n_blk, n_rows=s_n, n_table=P)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,             # page_table, lengths, qpos
        grid=(s_n,),
        in_specs=[
            pl.BlockSpec((1, hq, d), lambda s, pt, ln, qp: (s, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, hq, d), lambda s, pt, ln, qp: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, n_blk, page_rows, d), k_flat.dtype),
            pltpu.VMEM((2, n_blk, page_rows, d), v_flat.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((s_n,), jnp.int32),     # next live row
            pltpu.SMEM((1,), jnp.int32),       # buffer the next block reads
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_n, hq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # a step starts the copies the next one waits for
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(page_table.reshape(-1), lengths, jnp.asarray(qpos, jnp.int32), q,
      k_flat.reshape(n_pages, page_rows, d),
      v_flat.reshape(n_pages, page_rows, d))
    if pack > 1:
        return _own_lanes(out, hkv, pack)
    return out[..., :d_model]


def _spread_over_lanes(q, rows: int, pack: int):
    """q (S, Hq, D) -> (S, Hq, pack * D) for a packed pool of `rows`
    rows a token: the query heads of KV head h (row h // pack, place
    h % pack) lie at lanes [place * D, (place + 1) * D), zeros
    elsewhere."""
    s_n, hq, d = q.shape
    q = q.reshape(s_n, rows, pack, hq // (rows * pack), 1, d)
    place = jnp.eye(pack, dtype=q.dtype)[:, None, :, None]
    return (q * place).reshape(s_n, hq, pack * d)


def _own_lanes(out, rows: int, pack: int):
    """The inverse cut of `_spread_over_lanes` on the kernel's result
    (S, Hq, pack * D): each head's own D lanes."""
    s_n, hq, wide = out.shape
    d = wide // pack
    out = out.reshape(s_n, rows, pack, hq // (rows * pack), pack, d)
    return jnp.einsum("srjgjd->srjgd", out).reshape(s_n, hq, d)
