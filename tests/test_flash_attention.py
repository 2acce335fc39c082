"""Pallas flash attention == dense XLA attention (interpret mode on CPU)."""
import importlib
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import multi_head_attention
from ray_tpu.ops.pallas import flash_attention

# the package re-exports the function under the module's name
fa = importlib.import_module("ray_tpu.ops.pallas.flash_attention")


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


# ---------- tiles from the shape, the causal grid, GQA in place ----------

@pytest.mark.parametrize("s,d,dtype", [
    (4096, 128, jnp.bfloat16), (2048, 64, jnp.bfloat16),
    (8192, 128, jnp.bfloat16), (300, 128, jnp.float32),
    (128, 128, jnp.bfloat16)])
def test_choose_blocks(s, d, dtype):
    """The chooser is a pure function of the shape: tiles Mosaic can
    tile on (lane multiples; one sublane-rounded tile for a short
    sequence), inside the VMEM budget it states, at least 512 where the
    sequence allows, and never more blocks than the sequence needs."""
    blocks = fa.choose_blocks(s, s, d, dtype)
    assert blocks == fa.choose_blocks(s, s, d, dtype)
    itemsize = jnp.dtype(dtype).itemsize
    for kernel, (bq, bk) in blocks._asdict().items():
        assert bq % fa.LANES == 0 and bk % fa.LANES == 0, (kernel, bq, bk)
        assert fa.vmem_bytes(kernel, bq, bk, d, itemsize) \
            <= fa.VMEM_BUDGET_BYTES < fa.VMEM_LIMIT_BYTES
        if s >= 512:
            assert min(bq, bk) >= 512, (kernel, bq, bk)
            assert s % bq == 0 and s % bk == 0
        else:       # below a tile: one block, padded by less than a lane tile
            assert s <= bq < s + fa.LANES and s <= bk < s + fa.LANES


def test_choose_blocks_halves_into_the_budget(monkeypatch):
    want = fa.choose_blocks(4096, 4096, 128, jnp.bfloat16)
    monkeypatch.setattr(fa, "VMEM_BUDGET_BYTES", 4 * 2 ** 20)
    got = fa.choose_blocks(4096, 4096, 128, jnp.bfloat16)
    for kernel, (bq, bk) in got._asdict().items():
        assert fa.vmem_bytes(kernel, bq, bk, 128, 2) <= 4 * 2 ** 20
        assert bq * bk < want._asdict()[kernel][0] * want._asdict()[kernel][1]
        assert bq % fa.LANES == 0 and bk % fa.LANES == 0


# (sq, sk, hq, hkv, block_q, block_k, causal): with 16/32-row tiles at
# sequence 64 a causal call has tiles above, on and wholly below the
# diagonal
_TILE_CASES = {
    "bq_lt_bk": (64, 64, 2, 2, 16, 32, True),
    "bq_gt_bk": (64, 64, 2, 2, 32, 16, True),
    "ragged": (80, 80, 2, 2, 32, 32, True),
    "ragged_bq_ne_bk": (72, 72, 2, 1, 16, 32, True),
    "rep4": (64, 64, 8, 2, 32, 32, True),
    "rep1": (64, 64, 4, 4, 32, 16, True),
    "rep4_noncausal": (64, 64, 4, 1, 32, 32, False),
    "noncausal_sq_ne_sk": (48, 80, 4, 2, 16, 32, False),
    "noncausal_sq_gt_sk": (96, 40, 2, 2, 32, 16, False),
}


@pytest.mark.parametrize("case", sorted(_TILE_CASES))
def test_flash_tiles_match_xla(case):
    """Forward and all three gradients against the XLA route. K and V
    go in unexpanded: dk/dv must equal the reference's sum over each
    group's query heads (the reference repeats K/V, so its gradient
    sums)."""
    sq, sk, hq, hkv, bq, bk, causal = _TILE_CASES[case]
    rng = np.random.RandomState(sum(map(ord, case)))
    q, k, v = _rand_qkv(rng, 2, sq, sk, hq, hkv, 16)
    g = jnp.asarray(rng.randn(2, sq, hq, 16), jnp.float32)

    def run(attn):
        out, vjp = jax.vjp(attn, q, k, v)
        return (out, *vjp(g))

    got = run(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, block_q=bq, block_k=bk))
    ref = run(lambda q, k, v: multi_head_attention(
        q, k, v, causal=causal, impl="xla"))
    assert got[2].shape == k.shape and got[3].shape == v.shape
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


def test_flash_default_tiles_match_xla():
    """No explicit tile: the chooser's own (one padded block here)."""
    rng = np.random.RandomState(7)
    q, k, v = _rand_qkv(rng, 1, 200, 200, 4, 2, 16)
    ref = multi_head_attention(q, k, v, causal=True, impl="xla")
    out = flash_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def _pallas_calls(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["grid_mapping"])
        for val in eqn.params.values():
            for sub in (val if isinstance(val, (list, tuple)) else [val]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _pallas_calls(inner, out)
    return out


@pytest.mark.parametrize("bq,bk", [(16, 16), (16, 32), (32, 16)])
def test_causal_grid_fetches_nothing_above_the_diagonal(bq, bk):
    """Every grid step of the three causal kernels, through the index
    maps the pallas_calls were built with: no step names a K/V block
    (forward, dQ) or a Q/dO/lse/delta block (dK/dV) that lies wholly
    above the diagonal, so the pipeline copies none; and each row's
    needed blocks are all still visited."""
    s, hq, hkv = 64, 4, 2
    q = jnp.zeros((1, s, hq, 16), jnp.float32)
    kv = jnp.zeros((1, s, hkv, 16), jnp.float32)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=bq,
                               block_k=bk).sum()

    calls = _pallas_calls(
        jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, kv, kv).jaxpr,
        [])
    assert sorted((c.num_inputs, c.num_outputs) for c in calls) \
        == [(3, 2), (6, 1), (6, 2)]

    def block(mapping, *grid):
        jp = mapping.index_map_jaxpr
        return [int(x) for x in jax.core.eval_jaxpr(jp.jaxpr, jp.consts,
                                                    *grid)]

    for call in calls:
        seen = set()
        for grid in itertools.product(*(range(n) for n in call.grid)):
            if call.num_outputs == 2 and call.num_inputs == 6:   # dK/dV
                ik = grid[2]
                for n in (0, 3):
                    jq = block(call.block_mappings[n], *grid)[2]
                    assert (jq + 1) * bq - 1 >= ik * bk, (grid, jq)
                for n in (4, 5):
                    jq = block(call.block_mappings[n], *grid)[3]
                    assert (jq + 1) * bq - 1 >= ik * bk, (grid, jq)
                seen.add((jq, ik))
                # GQA in place: K/V of the group's own head, never expanded
                assert block(call.block_mappings[1], *grid)[1] == grid[1]
                assert block(call.block_mappings[0], *grid)[1] // (hq // hkv) \
                    == grid[1]
            else:                                       # forward, dQ
                iq = grid[2]
                for n in (1, 2):
                    _, h, jk, _ = block(call.block_mappings[n], *grid)
                    assert jk * bk <= (iq + 1) * bq - 1, (grid, jk)
                    assert h == grid[1] // (hq // hkv)
                seen.add((iq, jk))
        need = {(i, j) for i in range(s // bq) for j in range(s // bk)
                if j * bk <= (i + 1) * bq - 1}
        assert seen == need


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
