"""Pallas TPU kernel: single-token decode attention over a paged KV pool.

The serve engine's paged cache (ops/attention.py:PagedKV) stores KV as
flat token rows in a shared pool with per-sequence page tables. The
XLA gather path gathers each sequence's pages into a contiguous
(S, L, Hkv, D) view per layer per decode step — correct, but it
materializes L*page_size rows of temp HBM traffic per layer even when
sequences are short. This kernel reads the pages DIRECTLY:

  * the page table and lengths ride in SMEM via scalar prefetch
    (pltpu.PrefetchScalarGridSpec), so each (sequence, page) grid step's
    BlockSpec index_map picks the physical page — the indirection costs
    an SMEM read, not an HBM gather;
  * grid (S, P) accumulates flash-style (online softmax) across the
    page dimension; pages past the sequence length are skipped whole
    (pl.when), so work scales with the ACTUAL tokens, not the max;
  * GQA is handled in-kernel (q reshaped to (Hkv, rep, D)) — the pool
    is never head-expanded.

Decode is inference-only: no backward pass is defined (the training
path never runs paged attention).

Same vLLM-PagedAttention capability as the reference's GPU serving
path, re-designed for Mosaic's tiling rules (blocks keep the pool's
(page_size, Hkv, D) layout; the second-minor block dim equals the full
array dim, which the (8, 128) tiling rule permits).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(pt_ref, len_ref, qpos_ref, q_ref, k_ref, v_ref,
                   o_ref, acc_ref, m_ref, l_ref, *,
                   scale: float, page_size: int, n_kv: int, rep: int):
    s = pl.program_id(0)
    p = pl.program_id(1)
    np_ = pl.num_programs(1)

    @pl.when(p == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # causal bound: keys at positions <= the query's own position AND
    # < the sequence length — identical masking to _attend_cached, so
    # a replay query at an EARLIER position (positions < lengths-1,
    # e.g. speculative-decode verification) can't see future keys
    seq_len = jnp.minimum(len_ref[s], qpos_ref[s] + 1)
    run = p * page_size < seq_len

    @pl.when(run)
    def _compute():
        q = q_ref[0]                       # (Hq, D)
        k = k_ref[0]                       # (ps, Hkv, D)
        v = v_ref[0]
        hq, d = q.shape
        qg = q.reshape(n_kv, rep, d)
        # per-kv-head scores: (rep, ps) each; stacked -> (Hq, ps)
        parts = []
        for h in range(n_kv):
            sh = jax.lax.dot_general(
                qg[h], k[:, h, :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            parts.append(sh)               # (rep, ps)
        scores = jnp.concatenate(parts, axis=0)        # (Hq, ps)
        pos = p * page_size + jax.lax.broadcasted_iota(
            jnp.int32, scores.shape, 1)
        scores = jnp.where(pos < seq_len, scores, NEG_INF)

        m_prev = m_ref[:, :1]                           # (Hq, 1)
        m_cur = jnp.max(scores, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        pexp = jnp.exp(scores - m_new)                  # (Hq, ps)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:] = jnp.broadcast_to(
            corr * l_ref[:, :1]
            + jnp.sum(pexp, axis=1, keepdims=True), l_ref.shape)
        pv_parts = []
        pg = pexp.reshape(n_kv, rep, page_size)
        for h in range(n_kv):
            pv = jax.lax.dot_general(
                pg[h].astype(v.dtype), v[:, h, :],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)     # (rep, D)
            pv_parts.append(pv)
        acc_ref[:] = (acc_ref[:] * corr
                      + jnp.concatenate(pv_parts, axis=0))
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(p == np_ - 1)
    def _finalize():
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)


def paged_decode_attention(q, k_flat, v_flat, page_table, lengths,
                           page_size: int,
                           qpos=None,
                           scale: "float | None" = None,
                           interpret: "bool | None" = None):
    """q: (S, Hq, D) one decode token per sequence (cache already holds
    its KV); k_flat/v_flat: (N_flat, Hkv, D) page pools; page_table:
    (S, P) int32; lengths: (S,) int32 — keys valid at positions
    < lengths. qpos: (S,) int32 query positions (causal bound: keys at
    positions <= qpos attend; default lengths-1, the decode-at-end
    case). interpret defaults to True only on the CPU backend.
    Returns (S, Hq, D)."""
    s_n, hq, d = q.shape
    n_flat, hkv, _ = k_flat.shape
    assert n_flat % page_size == 0, (n_flat, page_size)
    rep = hq // hkv
    if scale is None:
        scale = d ** -0.5
    n_pages = n_flat // page_size
    kp = k_flat.reshape(n_pages, page_size, hkv, d)
    vp = v_flat.reshape(n_pages, page_size, hkv, d)
    P = page_table.shape[1]
    if qpos is None:
        qpos = lengths - 1
    if interpret is None:
        interpret = jax.default_backend() == "cpu"

    kernel = functools.partial(
        _decode_kernel, scale=scale, page_size=page_size,
        n_kv=hkv, rep=rep)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,             # page_table, lengths, qpos
        grid=(s_n, P),
        in_specs=[
            pl.BlockSpec((1, hq, d),
                         lambda s, p, pt, ln, qp: (s, 0, 0)),
            pl.BlockSpec((1, page_size, hkv, d),
                         lambda s, p, pt, ln, qp: (pt[s, p], 0, 0, 0)),
            pl.BlockSpec((1, page_size, hkv, d),
                         lambda s, p, pt, ln, qp: (pt[s, p], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, hq, d),
                               lambda s, p, pt, ln, qp: (s, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hq, d), jnp.float32),
            pltpu.VMEM((hq, 128), jnp.float32),
            pltpu.VMEM((hq, 128), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s_n, hq, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(page_table, lengths, jnp.asarray(qpos, jnp.int32), q, kp, vp)

