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

Replaces the reference's flash-attn/CUDA dependency (torch
scaled_dot_product_attention in its model stacks, e.g.
python/ray/train/torch/train_loop_utils.py models).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...util import knobs

NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale: float, causal: bool, block_q: int, block_k: int,
                seq_len: int):
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(jk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    q_start = iq * block_q
    k_start = jk * block_k

    run = True
    if causal:
        # Skip blocks entirely in the future of this q block.
        run = k_start <= q_start + block_q - 1

    @pl.when(run)
    def _compute():
        q = q_ref[0]                      # (block_q, d)
        k = k_ref[0]                      # (block_k, d)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        # causal + padding masks
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
        mask = k_pos < seq_len
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[:, :1]                               # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)           # (bq, 1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                              # (bq, bk)
        correction = jnp.exp(m_prev - m_new)                # (bq, 1)
        l_new = correction * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * correction + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[:] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(jk == nk - 1)
    def _finalize():
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_ref[:, 0] + jnp.log(safe_l[:, 0]))


def _flash_fwd(q, k, v, scale: float, causal: bool,
               block_q: int, block_k: int, interpret: bool):
    """q,k,v: (BH, S, D) with identical head counts (GQA pre-expanded).
    Returns (out (BH, S, D), lse (BH, S) fp32)."""
    bh, s, d = q.shape
    sk = k.shape[1]
    bq = min(block_q, s)
    bk = min(block_k, sk)
    nq = pl.cdiv(s, bq)
    nk = pl.cdiv(sk, bk)
    # pad sequence dims to block multiples
    s_pad, sk_pad = nq * bq, nk * bk
    if s_pad != s:
        q = jnp.pad(q, ((0, 0), (0, s_pad - s), (0, 0)))
    if sk_pad != sk:
        k = jnp.pad(k, ((0, 0), (0, sk_pad - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, sk_pad - sk), (0, 0)))

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
        seq_len=sk)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            # lse rides in a (bh, 1, s_pad) layout: the block's second-minor
            # dim (1) then equals the full array dim, which Mosaic's
            # (8, 128) tiling rule permits — a 2-D (bh, s_pad) array with a
            # (1, bq) block does NOT lower on real TPU (sublane dim 1 is
            # neither a multiple of 8 nor the array dim).
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s_pad, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, s_pad), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :s, :], lse[:, 0, :s]


def _bwd_p_ds(q, k, v, do, lse, delta, q_start, k_start, *, scale,
              causal, sq, sk, block_q, block_k):
    """Shared VMEM math for both backward kernels: rebuild the normalized
    probabilities p from the saved logsumexp and form
    ds = p * (dO V^T - delta) * scale. Returns (p, ds) in fp32."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale          # (bq, bk)
    q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                               (block_q, block_k), 0)
    k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                               (block_q, block_k), 1)
    mask = jnp.logical_and(k_pos < sk, q_pos < sq)
    if causal:
        mask = jnp.logical_and(mask, k_pos <= q_pos)
    # p = exp(s - lse): already normalized. Padded/fully-masked rows have
    # lse == 0 from re-padding; their dO rows are 0 so contributions die,
    # but mask them anyway so no inf/nan can form.
    p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)      # (bq, bk)
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                  # (bq, bk)
    ds = p * (dp - delta[:, None]) * scale
    return p, ds


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_acc, *, scale, causal, block_q, block_k,
                   sq, sk):
    iq = pl.program_id(1)
    jk = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(jk == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q_start = iq * block_q
    k_start = jk * block_k
    run = True
    if causal:
        run = k_start <= q_start + block_q - 1

    @pl.when(run)
    def _compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        _, ds = _bwd_p_ds(q, k, v, do, lse_ref[0, 0], delta_ref[0, 0],
                          q_start, k_start, scale=scale, causal=causal,
                          sq=sq, sk=sk, block_q=block_q, block_k=block_k)
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(jk == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                    block_q, block_k, sq, sk):
    ik = pl.program_id(1)
    jq = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(jq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    k_start = ik * block_k
    q_start = jq * block_q
    run = True
    if causal:
        # Skip q blocks entirely before this k block (they can't see it).
        run = q_start + block_q - 1 >= k_start

    @pl.when(run)
    def _compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        p, ds = _bwd_p_ds(q, k, v, do, lse_ref[0, 0], delta_ref[0, 0],
                          q_start, k_start, scale=scale, causal=causal,
                          sq=sq, sk=sk, block_q=block_q, block_k=block_k)
        dv_acc[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (bk, d)
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)              # (bk, d)

    @pl.when(jq == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, out, lse, do, scale, causal,
               block_q, block_k, interpret):
    """Pallas backward. q/out/do: (BH, S, D); k/v: (BH, Sk, D)."""
    bh, s, d = q.shape
    sk = k.shape[1]
    bq = min(block_q, s)
    bk = min(block_k, sk)
    nq = pl.cdiv(s, bq)
    nk = pl.cdiv(sk, bk)
    s_pad, sk_pad = nq * bq, nk * bk

    # delta_i = sum_j dO_ij * O_ij  (fp32, one cheap XLA pass)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                  # (BH, S)
    if s_pad != s:
        pad = ((0, 0), (0, s_pad - s), (0, 0))
        q, do = jnp.pad(q, pad), jnp.pad(do, pad)
        lse = jnp.pad(lse, ((0, 0), (0, s_pad - s)))
        delta = jnp.pad(delta, ((0, 0), (0, s_pad - s)))
    if sk_pad != sk:
        pad = ((0, 0), (0, sk_pad - sk), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    # Per-row tensors travel as (BH, 1, S): see the fwd lse out_spec for why
    # a 2-D (BH, S) layout cannot tile on real TPU.
    lse = lse.reshape(bh, 1, s_pad)
    delta = delta.reshape(bh, 1, s_pad)

    common = dict(scale=scale, causal=causal, block_q=bq, block_k=bk,
                  sq=s, sk=sk)
    qspec = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0))
    kspec = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0))
    rowspec = pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **common),
        grid=(bh, nq, nk),
        in_specs=[qspec, kspec, kspec, qspec, rowspec, rowspec],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s_pad, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dk/dv: swap loop order — k blocks in the grid, q blocks innermost.
    qspec2 = pl.BlockSpec((1, bq, d), lambda b, i, j: (b, j, 0))
    kspec2 = pl.BlockSpec((1, bk, d), lambda b, i, j: (b, i, 0))
    rowspec2 = pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, j))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **common),
        grid=(bh, nk, nq),
        in_specs=[qspec2, kspec2, kspec2, qspec2, rowspec2, rowspec2],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk_pad, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk_pad, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq[:, :s, :], dk[:, :sk, :], dv[:, :sk, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, scale, causal, block_q, block_k, interpret):
    out, _ = _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret)
    return out


def _flash_vjp_fwd(q, k, v, scale, causal, block_q, block_k, interpret):
    out, lse = _flash_fwd(q, k, v, scale, causal, block_q, block_k,
                          interpret)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(scale, causal, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    return _flash_bwd(q, k, v, out, lse, g.astype(q.dtype), scale, causal,
                      block_q, block_k, interpret)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """q: (B, Sq, Hq, D); k/v: (B, Sk, Hkv, D). Returns (B, Sq, Hq, D).

    GQA is handled by expanding kv heads before the kernel (the extra HBM
    reads are amortized by the block streaming).

    Block sizes default to 128x128; RAY_TPU_FLASH_BLOCK_Q/K override for
    on-chip tuning sweeps (bench.py --phase flash-ab).
    """
    if block_q is None:
        block_q = knobs.get_int("RAY_TPU_FLASH_BLOCK_Q")
    if block_k is None:
        block_k = knobs.get_int("RAY_TPU_FLASH_BLOCK_K")
    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    if scale is None:
        scale = d ** -0.5
    if hq != hkv:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    def flat(x):
        return x.transpose(0, 2, 1, 3).reshape(b * hq, x.shape[1], d)

    out = _flash(flat(q), flat(k), flat(v), float(scale), bool(causal),
                 int(block_q), int(block_k), bool(interpret))
    return out.reshape(b, hq, sq, d).transpose(0, 2, 1, 3)
