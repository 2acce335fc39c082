"""Pipeline parallelism: GPipe microbatch schedule over the `pp` mesh axis.

The reference pipelines via torch RPC / DeepSpeed-style stage processes with
explicit send/recv threads. TPU-first design instead: the layer stack is
split into `pp` stages whose parameters carry a leading stage axis sharded
over the mesh's `pp` dimension; one `shard_map` region runs the whole
schedule as a single XLA program. Each clock tick every stage applies its
block to its in-flight microbatch, then activations hop to the next stage
with `lax.ppermute` (one ICI neighbor hop). `lax.scan` drives the
M + pp - 1 ticks, so the schedule is compiled — no host round-trips between
micro-steps, and XLA overlaps the ppermute with the next tick's matmuls.

Constraints (by design, to stay static-shaped): stage_fn maps activations
(mb, ...) -> (mb, ...) with one pytree of per-stage params; token embedding
and the LM head live outside the pipelined region.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def stack_stage_params(params_list):
    """Stack per-stage param pytrees along a new leading stage axis."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=0), *params_list)


def pipeline_reference(stage_fn: Callable, stacked_params, x: jax.Array):
    """Sequential (no-mesh) semantics: stage_{n-1}(...stage_0(x))."""
    n = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    h = x
    for i in range(n):
        params_i = jax.tree_util.tree_map(lambda a: a[i], stacked_params)
        h = stage_fn(params_i, h)
    return h


def _pipeline_local(stacked_local, x_mb, *, stage_fn, axis_name, n_stages,
                    n_micro):
    """Per-device body. stacked_local: params with local stage axis of 1.
    x_mb: (M, mb, ...) microbatched input, replicated."""
    params = jax.tree_util.tree_map(lambda a: a[0], stacked_local)
    idx = jax.lax.axis_index(axis_name)
    n_ticks = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    zero = jnp.zeros_like(x_mb[0])

    def tick(prev_out, t):
        recv = jax.lax.ppermute(prev_out, axis_name, perm)
        inject = x_mb[jnp.clip(t, 0, n_micro - 1)]
        h_in = jnp.where(idx == 0, inject, recv)
        h_out = stage_fn(params, h_in)
        return h_out, h_out

    _, outs = jax.lax.scan(tick, zero, jnp.arange(n_ticks))
    # The last stage emits the final microbatch results on ticks
    # [n_stages-1, n_ticks); other stages contribute zeros to the psum.
    result = outs[n_stages - 1:]
    result = jnp.where(idx == n_stages - 1, result, 0)
    return jax.lax.psum(result, axis_name)


def pipeline_apply(stage_fn: Callable, stacked_params, x: jax.Array, *,
                   mesh: Mesh, axis_name: str = "pp",
                   n_microbatches: int) -> jax.Array:
    """Run x (B, ...) through the staged pipeline on `mesh`.

    stacked_params: per-stage params stacked on a leading axis of size
    pp (sharded over `axis_name`). B must divide into n_microbatches.
    Returns (B, ...) activations, replicated over the pp axis.
    """
    n = mesh.shape.get(axis_name, 1)
    b = x.shape[0]
    if b % n_microbatches:
        raise ValueError(f"batch {b} % n_microbatches {n_microbatches} != 0")
    n_stage_params = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if n > 1 and n_stage_params != n:
        raise ValueError(
            f"stacked stage axis is {n_stage_params} but mesh axis "
            f"'{axis_name}' has {n} devices; they must match (fold extra "
            f"layers inside stage_fn, e.g. a lax.scan over layers-per-stage)")
    if n == 1:
        return pipeline_reference(stage_fn, stacked_params, x)
    mb = b // n_microbatches
    x_mb = x.reshape(n_microbatches, mb, *x.shape[1:])

    # Microbatch rows shard over the data axes so dp/fsdp slices each run
    # their own pipeline on their own batch shard (no replicated compute).
    data_axes, prod = [], 1
    for a in ("dp", "fsdp"):
        sz = mesh.shape.get(a, 1)
        if sz > 1 and mb % (prod * sz) == 0:
            data_axes.append(a)
            prod *= sz
    batch_spec = P(None, tuple(data_axes) if data_axes else None)
    param_specs = jax.tree_util.tree_map(
        lambda a: P(axis_name), stacked_params)
    fn = jax.shard_map(
        functools.partial(_pipeline_local, stage_fn=stage_fn,
                          axis_name=axis_name, n_stages=n,
                          n_micro=n_microbatches),
        mesh=mesh, in_specs=(param_specs, batch_spec),
        out_specs=batch_spec, check_vma=False)
    out = fn(stacked_params, x_mb)
    return out.reshape(b, *out.shape[2:])
