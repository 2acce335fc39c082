"""parallel/collective_matmul.py against the all-reduce it replaces, and
the sharded train step that takes its route (four virtual CPU devices).
"""
import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models import Llama, LlamaConfig
from ray_tpu.models.llama import LlamaBlock
from ray_tpu.ops import rope_frequencies
from ray_tpu.parallel import MeshSpec, build_mesh
from ray_tpu.parallel.collective_matmul import (allgather_matmul,
                                                matmul_reducescatter)
from ray_tpu.parallel.sharding import activation_mesh, tp_matmul_route
from ray_tpu.train.spmd import make_train_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _mesh(tp):
    return build_mesh(MeshSpec(fsdp=4 // tp, tp=tp),
                      devices=jax.devices()[:4])


def _ulps(a, b, dtype):
    """Largest distance between a and b in units of `dtype`'s spacing at
    b's largest element."""
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b))
                 / (float(jnp.finfo(dtype).eps) * np.max(np.abs(b))))


@pytest.mark.parametrize("wrap", ["plain", "checkpoint", "scan"])
@pytest.mark.parametrize("form", ["natural", "chunks"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("tp", [2, 4])
def test_the_ring_pair_is_the_matmuls_with_a_psum(tp, dtype, form, wrap):
    """silu(x @ w1) @ w2 through allgather_matmul / matmul_reducescatter
    against the same matmuls with a psum over `tp`: value and the three
    gradients; float32 to rounding of the `tp` terms' order (none at
    `tp` 2), bf16 within one ulp of the all-reduce form."""
    mesh = _mesh(tp)
    dtype = jnp.dtype(dtype)
    b, s, d, f = 4, 8 * tp, 16, 32
    ks = jax.random.split(jax.random.PRNGKey(tp), 4)
    x = jax.random.normal(ks[0], (b, s, d), dtype)
    w1 = (jax.random.normal(ks[1], (d, f)) / np.sqrt(d)).astype(dtype)
    w2 = (jax.random.normal(ks[2], (f, d)) / np.sqrt(f)).astype(dtype)
    cot = jax.random.normal(ks[3], (b, s, d), jnp.float32)
    x = jax.device_put(x, NamedSharding(mesh, P("fsdp", "tp")))
    w1 = jax.device_put(w1, NamedSharding(mesh, P("fsdp", "tp")))
    w2 = jax.device_put(w2, NamedSharding(mesh, P("tp", "fsdp")))

    def ring(x, w1, w2):
        (h,) = allgather_matmul(x, [w1], mesh, chunks=form == "chunks")
        return matmul_reducescatter(jax.tree.map(jax.nn.silu, h), w2, mesh)

    def psum(x, w1, w2):
        # the all-reduce form written out: every device's partial product,
        # summed in `dtype` (the CPU backend aborts on a bf16 psum inside
        # a shard_map, "Invalid binary instruction opcode copy")
        parts = [jax.nn.silu(x @ w1[:, j * f // tp:(j + 1) * f // tp])
                 @ w2[j * f // tp:(j + 1) * f // tp] for j in range(tp)]
        return functools.reduce(jnp.add, parts)

    def wrapped(fn):
        if wrap == "checkpoint":
            return jax.checkpoint(fn)
        if wrap == "scan":
            def scanned(x, w1, w2):
                # two micro-batches, as accum_steps > 1 runs the step
                xs = x.reshape((2, b // 2) + x.shape[1:])
                _, ys = jax.lax.scan(
                    lambda c, xi: (c, fn(xi, w1, w2)), 0, xs)
                return ys.reshape(x.shape)
            return scanned
        return fn

    def value_and_grads(fn):
        def loss(x, w1, w2):
            y = wrapped(fn)(x, w1, w2)
            return jnp.sum(y.astype(jnp.float32) * cot), y
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))(x, w1, w2)

    (_, y), grads = value_and_grads(ring)
    (_, y_ref), grads_ref = value_and_grads(psum)
    # the ring adds its `tp` terms one by one, the psum in its own order
    limit = 0.0 if tp == 2 else (1.0 if dtype == jnp.bfloat16 else 4.0)
    assert _ulps(y, y_ref, dtype) <= limit
    for g, g_ref in zip(grads, grads_ref):
        # the ring's weight gradient is a sum of `tp` rounded products,
        # one a chunk, where the psum form has one product
        assert _ulps(g, g_ref, dtype) <= max(limit, 2.0)


@pytest.mark.parametrize("spec,seq,routed", [
    (MeshSpec(fsdp=2, tp=2), 32, True),
    (MeshSpec(fsdp=2, tp=2), 31, False),     # the sequence does not divide
    (MeshSpec(tp=4), 32, True),
    (MeshSpec(tp=4), 30, False),
    (MeshSpec(fsdp=4), 32, False),           # no `tp`
    (MeshSpec(sp=2, tp=2), 32, False),       # the sequence is `sp`'s
    (None, 32, False),                       # no activation mesh: serve
], ids=["fsdp2tp2", "fsdp2tp2-odd", "tp4", "tp4-odd", "fsdp4", "sp2tp2",
        "no-mesh"])
def test_a_block_takes_the_route_from_mesh_and_shape(spec, seq, routed):
    """A LlamaBlock under an activation mesh against the same block with
    no mesh (plain nn.Dense): same values; the route is taken where the
    mesh has `tp` > 1 and the sequence divides, and falls back where
    not."""
    cfg = LlamaConfig.debug(dtype=jnp.float32)
    block = LlamaBlock(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (4, seq, cfg.d_model))
    cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len,
                                cfg.rope_theta)
    params = block.init(jax.random.PRNGKey(1), x, cos, sin)
    plain, _ = jax.jit(block.apply)(params, x, cos, sin)
    mesh = spec and build_mesh(spec, devices=jax.devices()[:4])
    with activation_mesh(mesh) as active:
        assert (tp_matmul_route(x.shape, 0) is not None) == routed
        out, _ = jax.jit(block.apply)(params, x, cos, sin)
    assert active.tp_overlapped_matmuls == (7 if routed else 0)
    np.testing.assert_allclose(out, plain, rtol=2e-5, atol=2e-5)


def _trainer_step(model, spec, tokens, **kw):
    """One SGD step: (the update it made, its metrics, the SpmdStep)."""
    mesh = build_mesh(spec, devices=jax.devices()[:spec.size])
    init = make_train_step(model, optax.sgd(0.1), mesh, **kw)
    state, step = init(jax.random.PRNGKey(3), {"tokens": tokens})
    before = jax.device_get(state.params)
    state, metrics = step(state, {"tokens": tokens})
    update = jax.tree.map(np.subtract, jax.device_get(state.params), before)
    return update, {k: float(v) for k, v in metrics.items()}, step


@pytest.mark.parametrize("how", ["full", "dots", "accum2"])
def test_trainer_on_fsdp2_tp2_takes_the_route_and_is_the_same_step(how):
    """The training cell's mesh at its configuration's `rehearse` widths:
    step 0 against the float32 reference within `rehearse.check`, the
    updated parameters those of the same step on `fsdp=4` (no `tp`, so
    the route is off) to bf16 rounding, 7 overlapped projections a layer
    against 0. Under `remat_policy="dots"` and `accum_steps=2` the step
    traces through the shard_map and gives the same update."""
    from benchmarks.harness import modelcfg
    from benchmarks.runners import train_spmd
    with open(os.path.join(
            ROOT, "benchmarks/configs/mistral-7b-v0.3-train-4chip.json")) as f:
        cfg = json.load(f)
    cfg = {**cfg, **cfg["rehearse"]}
    layers, seq = cfg["num_hidden_layers"], 64
    model = Llama(dataclasses.replace(
        modelcfg.llama_config(cfg, param_dtype=jnp.float32, remat=True,
                              max_seq_len=seq),
        remat_policy="dots" if how == "dots" else "full"))
    tokens = next(train_spmd.batches(11, cfg["vocab_size"], 4, seq))["tokens"]
    kw = {"accum_steps": 2} if how == "accum2" else {}
    update, metrics, step = _trainer_step(
        model, MeshSpec(fsdp=2, tp=2), jnp.asarray(tokens), **kw)
    update_off, metrics_off, step_off = _trainer_step(
        model, MeshSpec(fsdp=4), jnp.asarray(tokens), **kw)
    assert step.tp_overlapped_matmuls == 7 * layers
    assert step_off.tp_overlapped_matmuls == 0
    if how == "full":
        # the reference shards its parameters over every device there is
        ref_loss, ref_gnorm = train_spmd.reference_step0(
            model, MeshSpec(fsdp=len(jax.devices())), cfg,
            {"tokens": tokens}, 3)
        chk = cfg["check"]
        assert abs(metrics["loss"] - ref_loss) <= chk["loss_tol"]
        assert (abs(metrics["grad_norm"] - ref_gnorm) / ref_gnorm
                <= chk["grad_norm_tol_rel"])
    assert abs(metrics["loss"] - metrics_off["loss"]) < 5e-3
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(update),
                            jax.tree_util.tree_leaves(update_off)):
        # bf16 compute on both meshes: a leaf's update differs by the
        # rounding of its matmuls' terms, 2 ** -8 an element
        assert np.linalg.norm(a - b) <= 0.05 * np.linalg.norm(b), path
