"""The SPMD train step: one jitted function over the whole mesh.

This is the TPU replacement for the reference's entire DDP/FSDP/NCCL layer
(python/ray/train/torch/config.py:_setup_torch_process_group and the
per-step allreduce hooks): state lives sharded via NamedSharding, the step
is jitted with explicit in/out shardings, and XLA inserts psum over `dp`,
reduce-scatter/all-gather over `fsdp`, and tensor collectives over `tp`.
Nothing in the loop does explicit communication.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.mesh import MeshSpec, build_mesh
from ..parallel.sharding import (ShardingRules, sharding_tree, shard_pytree,
                                 batch_sharding, replicated)


@flax.struct.dataclass
class TrainState:
    step: jax.Array
    params: Any
    opt_state: Any

    @staticmethod
    def create(params, tx: optax.GradientTransformation) -> "TrainState":
        return TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          opt_state=tx.init(params))


def next_token_loss(apply_fn: Callable, params, batch: Dict[str, jax.Array]):
    """Causal LM loss. batch: {"tokens": (B,S)} or {"inputs","targets"}.
    Optional "loss_mask" zeroes out padding/prompt positions."""
    if "inputs" in batch:
        inputs, targets = batch["inputs"], batch["targets"]
    else:
        inputs, targets = batch["tokens"][:, :-1], batch["tokens"][:, 1:]
    out = apply_fn({"params": params}, inputs)
    logits = out[0] if isinstance(out, tuple) else out
    logits = logits.astype(jnp.float32)
    # fused cross-entropy: logit[target] - logsumexp instead of a full
    # (B,S,V) fp32 log_softmax + gather — at flagship shapes the logp
    # array alone is ~1 GB of HBM the MXU then waits on
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, targets[..., None],
                             axis=-1)[..., 0] - lse
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones_like(ll)
    else:
        mask = mask.astype(jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)
    loss = -(ll * mask).sum() / denom
    ntokens = denom
    return loss, {"loss": loss, "ntokens": ntokens,
                  "ppl": jnp.exp(jnp.minimum(loss, 20.0))}


@dataclasses.dataclass
class SpmdStep:
    """Compiled train step + the shardings it expects."""
    step_fn: Callable[[TrainState, Dict[str, jax.Array]],
                      Tuple[TrainState, Dict[str, jax.Array]]]
    mesh: Mesh
    state_shardings: Any
    batch_shardings: Any
    # what the last trace of step_fn counted (empty until it is traced)
    traced: Dict[str, int] = dataclasses.field(default_factory=dict)

    @property
    def tp_overlapped_matmuls(self) -> Optional[int]:
        """Projections of the traced step that run overlapped with their
        `tp` collective (parallel/collective_matmul.py): 7 a Llama layer
        where the mesh has `tp` > 1 and the sequence divides, else 0."""
        return self.traced.get("tp_overlapped_matmuls")

    @property
    def remat_saved_residuals(self) -> Optional[int]:
        """Values the traced step's rematted blocks keep by name for the
        backward instead of recomputing them: 6 a Llama layer under
        `remat_policy="attention"` where the flash kernel runs (q, k, v,
        its output and logsumexp, the residual stream after attention),
        5 on the XLA route, 0 under any other policy or without remat."""
        return self.traced.get("remat_saved_residuals")

    def __call__(self, state, batch):
        return self.step_fn(state, batch)


def make_train_step(model, tx: optax.GradientTransformation, mesh: Mesh,
                    *, loss_fn: Optional[Callable] = None,
                    rules: Optional[ShardingRules] = None,
                    donate_state: bool = True,
                    accum_steps: int = 1) -> Callable:
    """Build the jitted SPMD step for `model` on `mesh`.

    Returns init_fn; calling init_fn(rng, example_batch) produces
    (TrainState sharded onto the mesh, SpmdStep compiled step).

    accum_steps > 1 enables gradient accumulation INSIDE the jitted
    step: the batch's leading dim splits into `accum_steps`
    micro-batches run under lax.scan (activation memory scales with the
    micro-batch, the fit-big-models knob on one 16 GB chip); gradients
    accumulate in fp32 and one optimizer update applies at the end —
    numerically a large-batch step, not accum_steps small ones.
    """
    loss_fn = loss_fn or partial(next_token_loss, model.apply)

    def _value_and_grad(params, batch):
        return jax.value_and_grad(
            lambda p: loss_fn(p, batch), has_aux=True)(params)

    traced: Dict[str, int] = {}

    def raw_step(state: TrainState, batch):
        from ..parallel.sharding import activation_mesh  # noqa: PLC0415
        with activation_mesh(mesh) as active:
            if accum_steps <= 1:
                (_loss, metrics), grads = _value_and_grad(state.params,
                                                          batch)
            else:
                micro = jax.tree_util.tree_map(
                    lambda x: x.reshape(
                        (accum_steps, x.shape[0] // accum_steps)
                        + x.shape[1:]), batch)
                zeros = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32),
                    state.params)

                # Each micro-batch's loss is its own masked mean, so
                # micro-grads are weighted by TOKEN COUNT (ntokens) and
                # normalized once by the total — exactly the full-batch
                # masked mean even when mask counts differ across
                # micro-batches (r4 advice: equal weighting diverges).
                # Custom loss_fns without "ntokens" weight uniformly.
                def body(carry, mb):
                    gsum, toksum = carry
                    (_l, m), g = _value_and_grad(state.params, mb)
                    nt = m.get("ntokens", jnp.float32(1.0)) \
                        if isinstance(m, dict) else jnp.float32(1.0)
                    nt = jnp.asarray(nt, jnp.float32)
                    gsum = jax.tree_util.tree_map(
                        lambda a, b: a + b.astype(jnp.float32) * nt,
                        gsum, g)
                    return (gsum, toksum + nt), m

                (gsum, toksum), ms = jax.lax.scan(
                    body, (zeros, jnp.float32(0.0)), micro)
                grads = jax.tree_util.tree_map(
                    lambda g, p: (g / jnp.maximum(toksum, 1.0)
                                  ).astype(p.dtype),
                    gsum, state.params)
                # metrics: token-weighted means (ntokens itself sums);
                # ppl recomputed from the aggregated loss
                nts = ms.get("ntokens") if isinstance(ms, dict) else None
                w = (nts / jnp.maximum(nts.sum(), 1.0)
                     if nts is not None
                     else jnp.full((accum_steps,), 1.0 / accum_steps))

                def wmean(x):
                    # broadcast w over trailing dims: non-scalar metric
                    # leaves (e.g. a (C,) per-class vector) stack to
                    # (accum_steps, C) and need w as (accum_steps, 1)
                    wb = w.reshape((-1,) + (1,) * (x.ndim - 1))
                    return (x * wb).sum(axis=0)

                metrics = jax.tree_util.tree_map(wmean, ms)
                if isinstance(metrics, dict):
                    if nts is not None:
                        metrics["ntokens"] = nts.sum()
                    if "ppl" in metrics and "loss" in metrics:
                        metrics["ppl"] = jnp.exp(
                            jnp.minimum(metrics["loss"], 20.0))
        traced["tp_overlapped_matmuls"] = active.tp_overlapped_matmuls
        traced["remat_saved_residuals"] = active.remat_saved_residuals
        updates, new_opt = tx.update(grads, state.opt_state, state.params)
        new_params = optax.apply_updates(state.params, updates)
        metrics = dict(metrics)
        metrics["grad_norm"] = optax.global_norm(grads)
        return TrainState(step=state.step + 1, params=new_params,
                          opt_state=new_opt), metrics

    def init_fn(rng, example_batch) -> Tuple[TrainState, SpmdStep]:
        tokens = example_batch.get("tokens",
                                   example_batch.get("inputs"))
        # Abstract init -> shardings -> real sharded init (params are born
        # sharded; no host-side full copy of an 8B model).
        def _init(rng):
            params = model.init(rng, tokens[:1, :8])["params"]
            return TrainState.create(params, tx)

        abstract = jax.eval_shape(_init, rng)
        state_sh = jax.tree_util.tree_map_with_path(
            lambda path, leaf: _state_leaf_sharding(path, leaf, mesh, rules),
            abstract)
        # partitionable threefry makes the sharded init draw the SAME
        # bits as an unsharded one: with the legacy (non-partitionable)
        # impl, jit(out_shardings=...) lets the SPMD partitioner shard
        # the RNG computation and every mesh produces different initial
        # params — sharded-vs-single-device parity then fails at step 0
        old_tf = jax.config.jax_threefry_partitionable
        jax.config.update("jax_threefry_partitionable", True)
        try:
            with jax.transfer_guard("allow"):
                state = jax.jit(_init, out_shardings=state_sh)(rng)
        finally:
            jax.config.update("jax_threefry_partitionable", old_tf)

        bshard = jax.tree_util.tree_map(
            lambda x: batch_sharding(mesh), example_batch)
        metric_sh = None  # replicated scalars
        step_fn = jax.jit(
            raw_step,
            in_shardings=(state_sh, bshard),
            out_shardings=(state_sh, metric_sh),
            donate_argnums=(0,) if donate_state else ())
        return state, SpmdStep(step_fn, mesh, state_sh, bshard, traced)

    return init_fn


def _state_leaf_sharding(path, leaf, mesh: Mesh,
                         rules: Optional[ShardingRules]) -> NamedSharding:
    """Shard params AND their optimizer moments identically; scalars
    (step, schedule counters) replicate."""
    from ..parallel.sharding import path_str
    rules = rules or ShardingRules()
    if not getattr(leaf, "shape", ()):
        return replicated(mesh)
    spec = rules.spec_for(path_str(path), leaf.shape, mesh)
    return NamedSharding(mesh, spec)
