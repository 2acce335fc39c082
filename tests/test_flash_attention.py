"""Pallas flash attention == dense XLA attention (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import multi_head_attention
from ray_tpu.ops.pallas import flash_attention


def _rand_qkv(rng, b, sq, sk, hq, hkv, d, dtype=jnp.float32):
    q = jnp.asarray(rng.randn(b, sq, hq, d), dtype) * 0.3
    k = jnp.asarray(rng.randn(b, sk, hkv, d), dtype) * 0.3
    v = jnp.asarray(rng.randn(b, sk, hkv, d), dtype) * 0.3
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_dense(causal):
    rng = np.random.RandomState(0)
    q, k, v = _rand_qkv(rng, 2, 64, 64, 4, 4, 32)
    ref = multi_head_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_gqa_and_ragged_blocks():
    rng = np.random.RandomState(1)
    # seq 80 not a multiple of 32-blocks; GQA 8q/2kv heads
    q, k, v = _rand_qkv(rng, 1, 80, 80, 8, 2, 16)
    ref = multi_head_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_gradients_match():
    rng = np.random.RandomState(2)
    q, k, v = _rand_qkv(rng, 1, 32, 32, 2, 2, 16)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=16,
                               block_k=16).sum()

    def loss_ref(q, k, v):
        return multi_head_attention(q, k, v, causal=True).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_gradients_ragged_gqa(causal):
    """Pallas backward (dq/dk/dv kernels) vs XLA grads on ragged blocks
    + GQA head expansion."""
    rng = np.random.RandomState(3)
    q, k, v = _rand_qkv(rng, 2, 80, 80, 4, 2, 16)
    g = jnp.asarray(rng.randn(2, 80, 4, 16), jnp.float32)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=causal, block_q=32,
                                block_k=32) * g).sum()

    def loss_ref(q, k, v):
        return (multi_head_attention(q, k, v, causal=causal,
                                     impl="xla") * g).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_flash_gradients_bf16_finite():
    rng = np.random.RandomState(4)
    q, k, v = _rand_qkv(rng, 1, 64, 64, 2, 2, 32, dtype=jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=32,
                               block_k=32).astype(jnp.float32).sum()

    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for a in grads:
        assert a.dtype == jnp.bfloat16
        assert np.isfinite(np.asarray(a, np.float32)).all()


def test_pallas_lowering_failure_surfaces(monkeypatch):
    """When the auto route picks the Pallas kernel and Mosaic refuses it,
    the error reaches the caller: no quiet XLA stand-in."""
    import importlib

    import ray_tpu.ops.attention as attn_mod
    fa_mod = importlib.import_module("ray_tpu.ops.pallas.flash_attention")

    monkeypatch.setattr(attn_mod.jax, "default_backend", lambda: "tpu")

    def boom(*a, **kw):
        raise RuntimeError("Mosaic lowering failed (simulated)")

    monkeypatch.setattr(fa_mod, "flash_attention", boom)

    rng = np.random.RandomState(5)
    # seq >= 2048: the only regime where "auto" prefers pallas
    q, k, v = _rand_qkv(rng, 1, 2048, 2048, 1, 1, 16)
    with pytest.raises(RuntimeError, match="Mosaic lowering failed"):
        attn_mod.multi_head_attention(q, k, v, causal=True, impl="auto")


def test_pallas_attention_inside_sharded_step_matches_xla():
    """Under an activation mesh the Pallas kernel runs inside shard_map
    (XLA refuses to partition a Mosaic call): batch over fsdp, heads over
    tp with GQA groups whole — values and gradients equal the XLA path."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.sharding import activation_mesh

    mesh = build_mesh(MeshSpec(fsdp=2, tp=2), devices=jax.devices()[:4])
    rng = np.random.RandomState(6)
    q, k, v = jax.device_put(
        _rand_qkv(rng, 4, 64, 64, 4, 2, 16),
        NamedSharding(mesh, P("fsdp", None, "tp", None)))

    def grads(impl):
        def loss(q, k, v):
            with activation_mesh(mesh):
                out = multi_head_attention(q, k, v, causal=True, impl=impl)
            return (out ** 2).sum(), out
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2),
                                          has_aux=True))(q, k, v)

    (_, out), g = grads("pallas")
    (_, ref), g_ref = grads("xla")
    assert out.sharding.spec == P("fsdp", None, "tp", None)
    for a, b in zip((out, *g), (ref, *g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.slow
def test_llama_pallas_impl_runs():
    from ray_tpu.models import Llama, LlamaConfig
    cfg = LlamaConfig.debug(attn_impl="pallas", dtype=jnp.float32)
    model = Llama(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    logits, _ = model.apply({"params": params},
                            jnp.zeros((1, 16), jnp.int32))
    assert np.isfinite(np.asarray(logits)).all()


# ---------- fused rmsnorm (pallas) ----------

def test_fused_rms_norm_matches_xla():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops.norms import rms_norm
    from ray_tpu.ops.pallas import fused_rms_norm

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(4, 33, 256), jnp.float32)   # ragged rows
    w = jnp.asarray(rng.randn(256), jnp.float32)
    ref = rms_norm(x, w)
    out = fused_rms_norm(x, w, block_rows=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_fused_rms_norm_grads_match():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops.norms import rms_norm
    from ray_tpu.ops.pallas import fused_rms_norm

    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(8, 64), jnp.float32)
    w = jnp.asarray(rng.randn(64), jnp.float32)

    def loss_p(x, w):
        return jnp.sum(fused_rms_norm(x, w) ** 2)

    def loss_x(x, w):
        return jnp.sum(rms_norm(x, w) ** 2)

    gp = jax.grad(loss_p, argnums=(0, 1))(x, w)
    gx = jax.grad(loss_x, argnums=(0, 1))(x, w)
    for a, b in zip(gp, gx):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_fused_rms_norm_bf16_roundtrip():
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops.pallas import fused_rms_norm
    x = jnp.ones((4, 128), jnp.bfloat16) * 3
    w = jnp.ones((128,), jnp.bfloat16)
    out = fused_rms_norm(x, w)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32), 1.0, atol=2e-2)
