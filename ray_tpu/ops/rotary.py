"""Rotary position embeddings (RoPE), Llama-3 style with NTK scaling hook."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def rope_frequencies(head_dim: int, max_len: int, theta: float = 500000.0,
                     dtype=jnp.float32):
    """Precompute cos/sin tables: shape (max_len, head_dim//2)."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                           dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def yarn_softmax_scale(head_dim: int, factor: float,
                       mscale_all_dim: float = 0.0) -> float:
    """The attention scale of a `deepseek_yarn` model: head_dim^-1/2 times
    m^2, m = 0.1 * mscale_all_dim * ln(factor) + 1 (1 where factor <= 1
    or mscale_all_dim is 0)."""
    m = (0.1 * mscale_all_dim * math.log(factor) + 1.0
         if factor > 1 and mscale_all_dim else 1.0)
    return head_dim ** -0.5 * m * m


def yarn_frequencies(head_dim: int, max_len: int, theta: float = 10000.0,
                     *, factor: float, original_max_len: int,
                     beta_fast: float = 32.0, beta_slow: float = 1.0,
                     dtype=jnp.float32):
    """cos/sin tables (max_len, head_dim//2) of YaRN (`deepseek_yarn`):
    per rotated pair a blend of the plain frequency theta^(-2i/d) and
    that over `factor`. Pairs that turn more than `beta_fast` times over
    the `original_max_len` positions keep the plain frequency, pairs
    that turn fewer than `beta_slow` times take the divided one, and a
    linear ramp between those two correction dimensions blends the rest.
    The tables carry no magnitude factor (a config whose `mscale` equals
    its `mscale_all_dim` has none); the softmax's share of YaRN is
    `yarn_softmax_scale`."""
    half = head_dim // 2

    def correction_dim(turns: float) -> float:
        return (head_dim * math.log(original_max_len
                                    / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), head_dim - 1)
    plain = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                        dtype=jnp.float32) / head_dim))
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    inv_freq = plain * (1.0 - ramp) + plain / factor * ramp
    freqs = jnp.outer(jnp.arange(max_len, dtype=jnp.float32), inv_freq)
    return jnp.cos(freqs).astype(dtype), jnp.sin(freqs).astype(dtype)


def apply_rotary(x: jax.Array, cos: jax.Array, sin: jax.Array,
                 positions: jax.Array | None = None) -> jax.Array:
    """Rotate pairs (x0,x1) -> (x0 cos - x1 sin, x0 sin + x1 cos).

    x: (..., seq, heads, head_dim). cos/sin: (max_len, head_dim//2).
    positions: optional (..., seq) int array for non-contiguous positions
    (decode steps, packed sequences).
    """
    seq = x.shape[-3]
    if positions is None:
        c = cos[:seq]
        s = sin[:seq]
        # broadcast over leading batch dims and heads
        c = c[None, :, None, :] if x.ndim == 4 else c[:, None, :]
        s = s[None, :, None, :] if x.ndim == 4 else s[:, None, :]
    else:
        c = jnp.take(cos, positions, axis=0)[..., :, None, :]
        s = jnp.take(sin, positions, axis=0)[..., :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    cdt = c.astype(x.dtype)
    sdt = s.astype(x.dtype)
    return jnp.concatenate([x1 * cdt - x2 * sdt,
                            x1 * sdt + x2 * cdt], axis=-1)
