"""Ring attention: sequence/context parallelism over the `sp` mesh axis.

Replaces the reference's context-parallel path (torch sequence parallelism /
ring-flash-attn integrations under python/ray/train) with a TPU-native
design: q/k/v are sharded over sequence on the `sp` axis; each device holds
one sequence chunk and the k/v chunks rotate around the ring with
`lax.ppermute` (nearest-neighbor ICI hops), while a running online-softmax
(m, l, acc) accumulates the attention output. After `sp` steps every q chunk
has attended over the full sequence without any device ever materializing
the (S, S) score matrix — HBM stays O(S/sp * S/sp) per step and the
ppermute overlaps with the per-chunk matmuls.

Causality is handled by global position masking, so chunk boundaries never
leak future tokens. GQA (n_kv_heads < n_heads) is supported by repeating kv
heads before the ring starts.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


_NEG_BIG = -0.7 * float(jnp.finfo(jnp.float32).max)  # finite: avoids inf-inf


def _online_chunk(q, k, v, m, l, acc, q_offset, k_offset, scale, causal):
    """One block of online-softmax attention, grouped-query layout.

    q: (B, Sq, Hkv, R, D) local query chunk at global offset q_offset —
       R = Hq // Hkv query heads per kv head, so kv stays un-repeated
    k/v: (B, Sk, Hkv, D) visiting kv chunk at global offset k_offset
    m/l: (B, Hkv, R, Sq) running max / denominator;
    acc: (B, Sq, Hkv, R, D) running numerator. All fp32.
    """
    logits = jnp.einsum("bqhrd,bkhd->bhrqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        qpos = q_offset + jnp.arange(sq)[:, None]
        kpos = k_offset + jnp.arange(sk)[None, :]
        logits = jnp.where((qpos >= kpos)[None, None, None],
                           logits, _NEG_BIG)
    new_m = jnp.maximum(m, logits.max(axis=-1))
    correction = jnp.exp(m - new_m)
    p = jnp.exp(logits - new_m[..., None])          # (B,Hkv,R,Sq,Sk)
    new_l = l * correction + p.sum(axis=-1)
    pv = jnp.einsum("bhrqk,bkhd->bqhrd", p, v.astype(jnp.float32))
    new_acc = (acc * correction.transpose(0, 3, 1, 2)[..., None] + pv)
    return new_m, new_l, new_acc


def _ring_attention_local(q, k, v, *, axis_name: str, n_chunks: int,
                          causal: bool, scale: float):
    """Per-device body under shard_map. q: local (B, S/n, Hq, D);
    k/v: local (B, S/n, Hkv, D). kv rides the ring at Hkv width — GQA's
    bandwidth saving applies to the ppermute traffic too."""
    idx = jax.lax.axis_index(axis_name)
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    rep = hq // hkv
    q32 = q.astype(jnp.float32).reshape(b, sq, hkv, rep, d)
    m = jnp.full((b, hkv, rep, sq), _NEG_BIG, jnp.float32)
    l = jnp.zeros((b, hkv, rep, sq), jnp.float32)
    acc = jnp.zeros((b, sq, hkv, rep, d), jnp.float32)
    perm = [(i, (i + 1) % n_chunks) for i in range(n_chunks)]

    def body(s, carry):
        m, l, acc, k, v = carry
        # After s forward rotations device `idx` holds the chunk that
        # started on device (idx - s) % n.
        k_idx = (idx - s) % n_chunks
        m, l, acc = _online_chunk(
            q32, k.astype(jnp.float32), v.astype(jnp.float32), m, l, acc,
            q_offset=idx * sq, k_offset=k_idx * sq,
            scale=scale, causal=causal)
        k = jax.lax.ppermute(k, axis_name, perm)
        v = jax.lax.ppermute(v, axis_name, perm)
        return m, l, acc, k, v

    m, l, acc, _, _ = jax.lax.fori_loop(0, n_chunks, body,
                                        (m, l, acc, k, v))
    out = acc / l.transpose(0, 3, 1, 2)[..., None]
    return out.reshape(b, sq, hq, d).astype(q.dtype)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   mesh: Mesh, axis_name: str = "sp",
                   causal: bool = True,
                   scale: Optional[float] = None) -> jax.Array:
    """Context-parallel attention over `axis_name` of `mesh`.

    q: (B, S, Hq, D); k/v: (B, S, Hkv, D), Hq % Hkv == 0. The S dim is
    sharded over `axis_name` (S % axis_size == 0). Returns (B, S, Hq, D)
    with the same sequence sharding.
    """
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    if hq % hkv:
        raise ValueError(f"n_heads {hq} % n_kv_heads {hkv} != 0")
    if scale is None:
        scale = d ** -0.5
    n = mesh.shape.get(axis_name, 1)
    if n == 1:
        # Degenerate ring == dense attention; reuse the canonical impl.
        from .attention import multi_head_attention  # noqa: PLC0415
        return multi_head_attention(q, k, v, causal=causal, scale=scale)
    if s % n:
        raise ValueError(f"seq len {s} not divisible by {axis_name}={n}")

    spec = P(None, axis_name, None, None)
    fn = jax.shard_map(
        functools.partial(_ring_attention_local, axis_name=axis_name,
                          n_chunks=n, causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)
