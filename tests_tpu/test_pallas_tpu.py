"""Mosaic-lowering smoke tests: every Pallas kernel, interpret=False,
on the real chip, at the bench shapes (seq 1024, head_dim 64).

These exist because interpret-mode CI is structurally blind to TPU
tiling constraints (Mosaic's (8, 128) rule) — see the round-2 lse
BlockSpec failure. Parity is asserted against the XLA path.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.skipif(
    jax.default_backend() != "tpu",
    reason="requires a real TPU backend (Mosaic lowering)")

B, S, H, D = 2, 1024, 12, 64


def _qkv(hkv=H, dtype=jnp.bfloat16):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), dtype)
    k = jax.random.normal(ks[1], (B, S, hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, S, hkv, D), dtype)
    return q, k, v


def _max_err(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                 b.astype(jnp.float32))))


@pytest.mark.parametrize("causal", [True, False])
def test_flash_fwd_on_tpu(causal):
    from ray_tpu.ops.attention import multi_head_attention
    from ray_tpu.ops.pallas.flash_attention import flash_attention
    q, k, v = _qkv()
    out = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, interpret=False))(q, k, v)
    ref = jax.jit(lambda q, k, v: multi_head_attention(
        q, k, v, causal=causal, impl="xla"))(q, k, v)
    assert np.isfinite(np.asarray(out, dtype=np.float32)).all()
    assert _max_err(out, ref) < 0.05  # bf16 rounding


def test_flash_bwd_on_tpu():
    from ray_tpu.ops.attention import multi_head_attention
    from ray_tpu.ops.pallas.flash_attention import flash_attention
    q, k, v = _qkv()

    def grads(fn):
        def loss(q, k, v):
            return (fn(q, k, v).astype(jnp.float32) ** 2).sum()
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)

    gp = grads(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=False))
    gx = grads(lambda q, k, v: multi_head_attention(
        q, k, v, causal=True, impl="xla"))
    for name, a, b in zip(("dq", "dk", "dv"), gp, gx):
        scale = max(1.0, float(jnp.max(jnp.abs(b.astype(jnp.float32)))))
        assert _max_err(a, b) / scale < 0.05, name


def test_flash_gqa_on_tpu():
    from ray_tpu.ops.attention import multi_head_attention
    from ray_tpu.ops.pallas.flash_attention import flash_attention
    q, k, v = _qkv(hkv=4)
    out = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=False))(q, k, v)
    ref = jax.jit(lambda q, k, v: multi_head_attention(
        q, k, v, causal=True, impl="xla"))(q, k, v)
    assert _max_err(out, ref) < 0.05


def test_flash_ragged_seq_on_tpu():
    """Non-block-multiple sequence exercises the padding path."""
    from ray_tpu.ops.attention import multi_head_attention
    from ray_tpu.ops.pallas.flash_attention import flash_attention
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 1000, 4, 64), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, 1000, 4, 64), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, 1000, 4, 64), jnp.bfloat16)
    out = jax.jit(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=False))(q, k, v)
    ref = jax.jit(lambda q, k, v: multi_head_attention(
        q, k, v, causal=True, impl="xla"))(q, k, v)
    assert _max_err(out, ref) < 0.05


def test_rmsnorm_on_tpu():
    from ray_tpu.ops.norms import rms_norm
    from ray_tpu.ops.pallas.rmsnorm import fused_rms_norm
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 1024, 512),
                          jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(3), (512,), jnp.float32)
    out = jax.jit(lambda x, w: fused_rms_norm(x, w, interpret=False))(x, w)
    ref = jax.jit(rms_norm)(x, w)
    assert _max_err(out, ref) < 0.05


def test_attention_auto_resolves_to_working_kernel():
    """impl='auto' on TPU must compile and produce a finite result (a
    kernel Mosaic refuses fails here: there is no fallback)."""
    from ray_tpu.ops.attention import multi_head_attention
    q, k, v = _qkv()
    out = jax.jit(lambda q, k, v: multi_head_attention(
        q, k, v, causal=True))(q, k, v)
    assert np.isfinite(np.asarray(out, dtype=np.float32)).all()


def test_paged_decode_kernel_on_tpu(monkeypatch):
    """Mosaic lowering of the paged decode kernel (its own page DMAs;
    heads of 64 go through the lane pad) at engine-like shapes, vs the
    XLA gather path."""
    import numpy as np
    from ray_tpu.ops.attention import PagedKV, paged_cached_attention
    from ray_tpu.ops.pallas.paged_attention import paged_decode_attention

    S, P, ps, hq, hkv, d = 4, 4, 64, 8, 4, 64
    rng = np.random.RandomState(0)
    n_pages = S * P
    lengths = np.asarray([5, 64, 130, 255], np.int32)
    k_flat = jnp.asarray(rng.randn((n_pages + 1) * ps, hkv, d),
                         jnp.bfloat16)
    v_flat = jnp.asarray(rng.randn((n_pages + 1) * ps, hkv, d),
                         jnp.bfloat16)
    table = jnp.asarray(rng.permutation(n_pages).reshape(S, P),
                        jnp.int32)
    q = jnp.asarray(rng.randn(S, hq, d), jnp.bfloat16)
    new_lengths = jnp.asarray(lengths)

    out = jax.jit(lambda *a: paged_decode_attention(
        *a, page_size=ps))(q, k_flat, v_flat, table, new_lengths)

    # shared reference scaffold (single definition of the flat-row
    # formula + replay convention) from the CPU parity suite
    import sys
    sys.path.insert(0, str(__import__("pathlib").Path(
        __file__).resolve().parent.parent / "tests"))
    from test_paged_attention_kernel import gather_reference
    ref = gather_reference(q, k_flat, v_flat, table, new_lengths, ps,
                           monkeypatch)
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                - ref.astype(jnp.float32))))
    assert err < 0.05, f"paged kernel vs gather err={err}"
