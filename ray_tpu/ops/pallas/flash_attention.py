"""Flash attention for TPU in Pallas — forward AND backward kernels.

Online-softmax tiled attention: Q/K/V blocks stream HBM -> VMEM, logits
never materialize in HBM, accumulators live in VMEM scratch across the
innermost grid dimension — the standard TPU flash schedule.

Forward emits the per-row logsumexp; backward is two Pallas kernels
(FlashAttention-2 style): a dQ kernel accumulating over key blocks and a
dK/dV kernel accumulating over query blocks, with
delta = rowsum(dO * O) precomputed in XLA. Logits are rebuilt in VMEM
from the saved logsumexp, so the backward is O(S) HBM like the forward.
The kernels auto-run in interpret mode on CPU so tests exercise the same
code path.

What one grid step holds is chosen from the shape (`choose_blocks`): a
grid step costs ~0.35 us on a v5e whatever is inside it, and the
forward rescales its accumulator once a step, so a tile has to hold
hundreds of MFLOP for the MXU to set the pace. Under a causal
mask the K/V (in dK/dV: the Q/dO/row) index maps stop at the last block
a row needs, so steps above the diagonal fetch nothing and skip their
body, and only tiles the diagonal crosses (or that hold padding) build
a mask. K and V keep their own head count: query head h reads KV head
h // rep through the index map, and dK/dV sums its group's `rep` query
heads in the kernel.

Replaces the reference's flash-attn/CUDA dependency (torch
scaled_dot_product_attention in its model stacks, e.g.
python/ray/train/torch/train_loop_utils.py models).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..attention import ATTN_RESIDUALS

NEG_INF = -1e30
LANES = 128
# What Mosaic may use of a core's VMEM for one kernel (a v5e has 128 MiB,
# the default scoped limit is 16 MiB and refuses the dK/dV kernel at
# 1 024 x 1 024), and the share of it the chooser plans with: its
# estimate counts the pipeline's buffers, the scratch and the float32
# tiles, not the compiler's own temporaries.
VMEM_LIMIT_BYTES = 64 * 2 ** 20
VMEM_BUDGET_BYTES = VMEM_LIMIT_BYTES // 2
# The (block_q, block_k) a long sequence gets: the fastest of the
# one-chip sweep for all three kernels at 4 096 x 128 bf16 (PERF.md,
# PR 27). Smaller tiles pay the grid step and the forward's per-step
# rescaling more often, larger ones waste more of the diagonal's tiles.
_PREFERRED = (1024, 1024)

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_NN = (((1,), (0,)), ((), ()))      # a @ b


class FlashBlocks(NamedTuple):
    """(block_q, block_k) of the forward, dQ and dK/dV kernels."""
    fwd: Tuple[int, int]
    dq: Tuple[int, int]
    dkv: Tuple[int, int]


def vmem_bytes(kernel: str, block_q: int, block_k: int, d: int,
               itemsize: int) -> int:
    """VMEM one grid step of `kernel` ("fwd" | "dq" | "dkv") keeps live:
    double-buffered operand and result blocks, accumulators, and the
    (block_q, block_k) tiles (float32 s/p[/dp/ds] and their casts to the
    operand dtype for the MXU)."""
    row, col, tile = block_q * d, block_k * d, block_q * block_k
    if kernel == "fwd":         # q | k v | o, lse | acc m l | s p, p cast
        return (2 * itemsize * (2 * row + 2 * col) + 2 * 4 * block_q
                + 4 * (row + 2 * block_q * LANES) + tile * (8 + itemsize))
    if kernel == "dq":          # q do | k v | dq, lse delta | acc | s p dp ds
        return (2 * itemsize * (3 * row + 2 * col) + 4 * 4 * block_q
                + 4 * row + tile * (16 + itemsize))
    if kernel == "dkv":         # q do | k v | dk dv | 2 acc | s p dp ds, 2 casts
        return (2 * itemsize * (2 * row + 4 * col) + 4 * 4 * block_q
                + 8 * col + tile * (16 + 2 * itemsize))
    raise ValueError(kernel)


def _fit(s: int, preferred: int, sublane: int) -> int:
    """The block of a sequence of length `s`: as few blocks as
    `preferred` allows, each a multiple of the lane width and no longer
    than it has to be (2 304 -> 3 x 768, not 3 x 1 024); a sequence
    under one lane tile is one block of its own (sublane-rounded)
    length."""
    if s <= LANES:
        return -(-s // sublane) * sublane
    per = -(-s // -(-s // preferred))
    return -(-per // LANES) * LANES


def choose_blocks(sq: int, sk: int, d: int, dtype) -> FlashBlocks:
    """Tiles for a (sq, sk) attention at head width `d`: the preferred
    tile cut to the sequence, halved (the longer side first) until
    `vmem_bytes` fits VMEM_BUDGET_BYTES. A pure function of what the
    call can observe; no knob."""
    itemsize = jnp.dtype(dtype).itemsize
    sublane = 8 * max(1, 4 // itemsize)

    def pick(kernel):
        pq, pk = _PREFERRED
        while True:
            bq, bk = _fit(sq, pq, sublane), _fit(sk, pk, sublane)
            if vmem_bytes(kernel, bq, bk, d, itemsize) <= VMEM_BUDGET_BYTES \
                    or max(pq, pk) <= LANES:
                return bq, bk
            if pq >= pk:
                pq //= 2
            else:
                pk //= 2

    return FlashBlocks(pick("fwd"), pick("dq"), pick("dkv"))


def _div(a, b: int):
    return jax.lax.div(a, jnp.int32(b))


def _last_k_block(i, block_q: int, block_k: int, nk: int):
    """Last key block query block `i` needs under the causal mask."""
    return jnp.minimum(_div((i + 1) * block_q - 1, block_k), nk - 1)


def _first_q_block(i, block_q: int, block_k: int, nq: int):
    """First query block that sees key block `i` under the causal mask."""
    return jnp.minimum(_div(i * block_k, block_q), nq - 1)


def _for_tile(q_start, k_start, has_pad, *, causal: bool, block_q: int,
              block_k: int, sk: int, body):
    """Run `body(mask)` for the (q_start, k_start) tile if the causal
    mask leaves anything of it; `mask` is None on a tile wholly below
    the diagonal with no padded key, else a function of the tile's
    layout (scores as (q, k), or transposed) that says which scores are
    real."""
    padded = sk % block_k != 0
    run = k_start <= q_start + block_q - 1 if causal else True
    needs = []          # why this tile would want a mask
    if causal:
        needs.append(k_start + block_k - 1 > q_start)
    if padded:
        needs.append(has_pad)

    def mask(transposed: bool = False):
        shape = (block_k, block_q) if transposed else (block_q, block_k)
        q_axis, k_axis = (1, 0) if transposed else (0, 1)
        col = jax.lax.broadcasted_iota(jnp.int32, shape, k_axis)
        keep = None
        if causal:      # k_pos <= q_pos
            row = jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
            keep = col - row <= q_start - k_start
        if padded:
            real = col < sk - k_start
            keep = real if keep is None else jnp.logical_and(keep, real)
        return keep

    if not needs:
        pl.when(run)(lambda: body(None))
        return
    need = functools.reduce(jnp.logical_or, needs)
    pl.when(jnp.logical_and(run, need))(lambda: body(mask))
    pl.when(jnp.logical_and(run, jnp.logical_not(need)))(
        lambda: body(None))


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale: float, causal: bool, block_q: int, block_k: int,
                sk: int):
    iq, jk, nk = pl.program_id(2), pl.program_id(3), pl.num_programs(3)

    @pl.when(jk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def tile(mask):
        q, k, v = q_ref[...], k_ref[...], v_ref[...]
        s = jax.lax.dot_general(
            q, k, _NT, preferred_element_type=jnp.float32) * scale
        if mask is not None:
            s = jnp.where(mask(), s, NEG_INF)
        m_prev = m_ref[:, :1]                               # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                              # (bq, bk)
        correction = jnp.exp(m_prev - m_new)                # (bq, 1)
        l_new = correction * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * correction + jax.lax.dot_general(
            p.astype(v.dtype), v, _NN, preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    _for_tile(iq * block_q, jk * block_k, jk == nk - 1, causal=causal,
              block_q=block_q, block_k=block_k, sk=sk, body=tile)

    @pl.when(jk == nk - 1)
    def _finalize():
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0] = (m_ref[:, 0] + jnp.log(safe_l[:, 0]))


def _pad_seq(x, s_pad: int):
    """Zero-pad axis 2 of (B, H, S, ...) to s_pad."""
    if x.shape[2] == s_pad:
        return x
    pad = [(0, 0)] * x.ndim
    pad[2] = (0, s_pad - x.shape[2])
    return jnp.pad(x, pad)


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _row_major_specs(bq: int, bk: int, d: int, rep: int, nk: int,
                     causal: bool):
    """Block specs of the kernels whose grid is (b, hq, nq, nk): the
    forward and dQ. Returns (q-like, k-like, per-row) specs; under the
    causal mask the K/V index stops at the row's last block, so the
    steps past it re-name the block already in VMEM and copy nothing."""
    def kv_index(b, h, i, j):
        if causal:
            j = jnp.minimum(j, _last_k_block(i, bq, bk, nk))
        return b, h if rep == 1 else _div(h, rep), j, 0

    return (pl.BlockSpec((None, None, bq, d), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((None, None, bk, d), kv_index),
            # per-row tensors ride as (B, H, 1, S): the block's second-
            # minor dim (1) then equals the array's, which Mosaic's
            # (8, 128) tiling rule permits; an (S,)-minor array with a
            # (1, bq) block does NOT lower on real TPU.
            pl.BlockSpec((None, None, 1, bq), lambda b, h, i, j: (b, h, 0, i)))


def _flash_fwd(q, k, v, scale: float, causal: bool, block: Tuple[int, int],
               interpret: bool):
    """q: (B, Hq, S, D); k/v: (B, Hkv, Sk, D), Hq a multiple of Hkv.
    Returns (out (B, Hq, S, D), lse (B, Hq, S) fp32)."""
    b, hq, s, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    bq, bk = block
    nq, nk = pl.cdiv(s, bq), pl.cdiv(sk, bk)
    q, k, v = _pad_seq(q, nq * bq), _pad_seq(k, nk * bk), _pad_seq(v, nk * bk)
    qspec, kspec, rowspec = _row_major_specs(bq, bk, d, hq // hkv, nk, causal)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, sk=sk),
        grid=(b, hq, nq, nk),
        in_specs=[qspec, kspec, kspec],
        out_specs=[qspec, rowspec],
        out_shape=[
            jax.ShapeDtypeStruct((b, hq, nq * bq, d), q.dtype),
            jax.ShapeDtypeStruct((b, hq, 1, nq * bq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :s], lse[:, :, 0, :s]


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc, *, scale, causal, block_q, block_k, sk):
    iq, jk, nk = pl.program_id(2), pl.program_id(3), pl.num_programs(3)

    @pl.when(jk == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def tile(mask):
        q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
        s = jax.lax.dot_general(
            q, k, _NT, preferred_element_type=jnp.float32) * scale
        # p = exp(s - lse): already normalized. Padded query rows are
        # zeros with lse 0 and dO 0, padded keys are zeros: neither can
        # form an inf, and what they add to dq is exactly 0.
        p = jnp.exp(s - lse_ref[0][:, None])                 # (bq, bk)
        if mask is not None:
            p = jnp.where(mask(), p, 0.0)
        dp = jax.lax.dot_general(
            do, v, _NT, preferred_element_type=jnp.float32)  # (bq, bk)
        ds = p * (dp - delta_ref[0][:, None]) * scale
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, _NN, preferred_element_type=jnp.float32)

    _for_tile(iq * block_q, jk * block_k, jk == nk - 1, causal=causal,
              block_q=block_q, block_k=block_k, sk=sk, body=tile)

    @pl.when(jk == nk - 1)
    def _finalize():
        dq_ref[...] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_operands(q, k, v, do, lse, delta, s_pad: int, sk_pad: int):
    """The six inputs of both backward kernels, zero-padded to whole
    blocks; lse/delta as (B, H, 1, S) rows."""
    return (_pad_seq(q, s_pad), _pad_seq(k, sk_pad), _pad_seq(v, sk_pad),
            _pad_seq(do, s_pad), _pad_seq(lse, s_pad)[:, :, None],
            _pad_seq(delta, s_pad)[:, :, None])


def _flash_dq(q, k, v, do, lse, delta, scale, causal, block, interpret):
    """q/do: (B, Hq, S, D); k/v: (B, Hkv, Sk, D); lse/delta (B, Hq, S)."""
    b, hq, s, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    bq, bk = block
    nq, nk = pl.cdiv(s, bq), pl.cdiv(sk, bk)
    s_pad = nq * bq
    qspec, kspec, rowspec = _row_major_specs(bq, bk, d, hq // hkv, nk, causal)
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, sk=sk),
        grid=(b, hq, nq, nk),
        in_specs=[qspec, kspec, kspec, qspec, rowspec, rowspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((b, hq, s_pad, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*_bwd_operands(q, k, v, do, lse, delta, s_pad, nk * bk))
    return dq[:, :, :s]


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                    block_q, block_k, sk, nq):
    """One key block against the query blocks of its group's `rep`
    query heads (inner grid extent rep * nq). The scores are built
    transposed, (bk, bq): every product is a plain or b-transposed
    matmul and lse/delta broadcast along sublanes as the rows they
    arrive as."""
    ik, t, nt = pl.program_id(2), pl.program_id(3), pl.num_programs(3)
    jq = jax.lax.rem(t, jnp.int32(nq))

    @pl.when(t == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def tile(mask):
        q, k, v, do = q_ref[...], k_ref[...], v_ref[...], do_ref[...]
        st = jax.lax.dot_general(
            k, q, _NT, preferred_element_type=jnp.float32) * scale
        pt = jnp.exp(st - lse_ref[...])                      # (bk, bq)
        if mask is not None:
            pt = jnp.where(mask(transposed=True), pt, 0.0)
        dpt = jax.lax.dot_general(
            v, do, _NT, preferred_element_type=jnp.float32)  # (bk, bq)
        dst = pt * (dpt - delta_ref[...]) * scale
        dv_acc[:] += jax.lax.dot_general(
            pt.astype(do.dtype), do, _NN,
            preferred_element_type=jnp.float32)              # (bk, d)
        dk_acc[:] += jax.lax.dot_general(
            dst.astype(q.dtype), q, _NN,
            preferred_element_type=jnp.float32)              # (bk, d)

    _for_tile(jq * block_q, ik * block_k, ik == pl.num_programs(2) - 1,
              causal=causal, block_q=block_q, block_k=block_k, sk=sk,
              body=tile)

    @pl.when(t == nt - 1)
    def _finalize():
        dk_ref[...] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[:].astype(dv_ref.dtype)


def _flash_dkv(q, k, v, do, lse, delta, scale, causal, block, interpret):
    """As `_flash_dq`; returns (dk, dv) of k's and v's own shape."""
    b, hq, s, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    rep = hq // hkv
    bq, bk = block
    nq, nk = pl.cdiv(s, bq), pl.cdiv(sk, bk)
    s_pad, sk_pad = nq * bq, nk * bk

    def q_index(b, h, i, t):
        j = jax.lax.rem(t, jnp.int32(nq))
        if causal:      # blocks before the first that sees key block i
            j = jnp.maximum(j, _first_q_block(i, bq, bk, nq))
        return b, h * rep + _div(t, nq), j

    def row_index(*grid):
        b, h, j = q_index(*grid)
        return b, h, 0, j

    qspec = pl.BlockSpec((None, None, bq, d), lambda *g: (*q_index(*g), 0))
    rowspec = pl.BlockSpec((None, None, 1, bq), row_index)
    kspec = pl.BlockSpec((None, None, bk, d), lambda b, h, i, t: (b, h, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, sk=sk, nq=nq),
        grid=(b, hkv, nk, rep * nq),
        in_specs=[qspec, kspec, kspec, qspec, rowspec, rowspec],
        out_specs=[kspec, kspec],
        out_shape=[jax.ShapeDtypeStruct((b, hkv, sk_pad, d), k.dtype),
                   jax.ShapeDtypeStruct((b, hkv, sk_pad, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*_bwd_operands(q, k, v, do, lse, delta, s_pad, sk_pad))
    return dk[:, :, :sk], dv[:, :, :sk]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, scale, causal, blocks, interpret):
    out, _ = _flash_fwd(q, k, v, scale, causal, blocks.fwd, interpret)
    return out


def _flash_vjp_fwd(q, k, v, scale, causal, blocks, interpret):
    out, lse = _flash_fwd(q, k, v, scale, causal, blocks.fwd, interpret)
    # the backward's residuals carry ops/attention.py's names, so that a
    # jax.checkpoint policy can keep them (save_only_these_names) and
    # the rematted forward does not run this kernel a second time;
    # identities under any other policy and outside jax.checkpoint
    q, k, v, out, lse = (checkpoint_name(x, name) for x, name in
                         zip((q, k, v, out, lse), ATTN_RESIDUALS))
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(scale, causal, blocks, interpret, res, g):
    q, k, v, out, lse = res
    do = g.astype(q.dtype)
    # delta_i = sum_j dO_ij * O_ij  (fp32, one cheap XLA pass)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                  # (B, Hq, S)
    dq = _flash_dq(q, k, v, do, lse, delta, scale, causal, blocks.dq,
                   interpret)
    dk, dv = _flash_dkv(q, k, v, do, lse, delta, scale, causal, blocks.dkv,
                        interpret)
    return dq, dk, dv


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """q: (B, Sq, Hq, D); k/v: (B, Sk, Hkv, D). Returns (B, Sq, Hq, D).

    GQA is read in place: K and V are never expanded, and dk/dv come
    back summed over each group's query heads. `causal` with Sq != Sk
    aligns the mask at position 0 (k_pos <= q_pos).

    The tiles come from `choose_blocks` (shape, head width, dtype, VMEM
    budget). `block_q` / `block_k` pin one tile for all three kernels
    instead: tests use small ones to cover many tiles in interpret
    mode, `bench.py --phase flash-ab` sweeps them.
    """
    b, sq, hq, d = q.shape
    sk = k.shape[1]
    if scale is None:
        scale = d ** -0.5
    blocks = choose_blocks(sq, sk, d, q.dtype)
    if block_q is not None or block_k is not None:
        pin = (min(int(block_q), sq) if block_q else blocks.fwd[0],
               min(int(block_k), sk) if block_k else blocks.fwd[1])
        blocks = FlashBlocks(pin, pin, pin)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    out = _flash(*(x.transpose(0, 2, 1, 3) for x in (q, k, v)),
                 float(scale), bool(causal), blocks, bool(interpret))
    return out.transpose(0, 2, 1, 3)
