"""Gated activations (fused by XLA into the surrounding matmuls)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    return jax.nn.silu(gate) * up


def geglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    return jax.nn.gelu(gate) * up


def relu2(x: jax.Array) -> jax.Array:
    """relu(x)^2: the activation of a feed-forward without a gate."""
    return jnp.square(jax.nn.relu(x))
