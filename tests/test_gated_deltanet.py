"""ops/gated_deltanet.py and ops/pallas/gdn_decode.py: the chunkwise
form, the one-token step and the fused step kernel (interpreted) against
the recurrence token by token, and the layer of models/hybrid.py over a
per-slot state: padded == unpadded, prefill then steps == one prefill."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.hybrid import GatedDeltaNet, HybridConfig
from ray_tpu.ops import gated_deltanet as gdn
from ray_tpu.ops.attention import SlotState
from ray_tpu.ops.pallas.gdn_decode import gdn_decode_step, heads_per_group


def _inputs(b, s, h, dk, dv, seed=0, with_state=True):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = gdn.l2norm(jax.random.normal(ks[0], (b, s, h, dk))) * dk ** -0.5
    k = gdn.l2norm(jax.random.normal(ks[1], (b, s, h, dk)))
    v = jax.random.normal(ks[2], (b, s, h, dv))
    g = -jax.random.uniform(ks[3], (b, s, h)) * 0.5
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    state = jax.random.normal(ks[5], (b, dk, h * dv)) if with_state else None
    return q, k, v, g, beta, state


@pytest.mark.parametrize("chunk", [64, 32, 7, 150, 256])
def test_chunkwise_is_the_recurrence(chunk):
    """Chunk sizes that divide the 150 positions, that do not, and one
    longer than the sequence; from a carried state."""
    q, k, v, g, beta, s0 = _inputs(2, 150, 3, 8, 16)
    want_o, want_s = gdn.recurrent(q, k, v, g, beta, s0)
    got_o, got_s = gdn.chunk_scan(q, k, v, g, beta, s0, chunk=chunk)
    np.testing.assert_allclose(got_o, want_o, atol=2e-5)
    np.testing.assert_allclose(got_s, want_s, atol=2e-5)


@pytest.mark.parametrize("chunk", [64, 48])
def test_chunkwise_holds_for_keys_that_are_alike(chunk):
    """Keys after a SiLU all point one way (cosine ~0.6 between any
    two) and beta sits near 2: the strict part of the chunk's system has
    entries near 1 of one sign. The result still is the recurrence's to
    float32 rounding (a power series of that matrix overflows)."""
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    b, s, h, dk, dv = 1, 192, 2, 96, 32
    q = gdn.l2norm(jax.nn.silu(jax.random.normal(ks[0], (b, s, h, dk)) + 1))
    k = gdn.l2norm(jax.nn.silu(jax.random.normal(ks[1], (b, s, h, dk)) + 1))
    assert 0.5 < float(jnp.einsum("hk,hk->h", k[0, 0], k[0, 1]).mean())
    v = jax.random.normal(ks[2], (b, s, h, dv))
    g = -jax.random.uniform(ks[3], (b, s, h)) * 0.05
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)) + 3)
    want_o, want_s = gdn.recurrent(q, k, v, g, beta)
    got_o, got_s = gdn.chunk_scan(q, k, v, g, beta, chunk=chunk)
    scale = float(jnp.abs(want_o).max())
    assert bool(jnp.isfinite(got_o).all())
    np.testing.assert_allclose(got_o, want_o, atol=2e-4 * scale)
    np.testing.assert_allclose(got_s, want_s,
                               atol=2e-4 * float(jnp.abs(want_s).max()))


def test_chunkwise_from_nothing_and_the_step_are_the_recurrence():
    q, k, v, g, beta, _ = _inputs(2, 40, 2, 8, 16, seed=1, with_state=False)
    want_o, want_s = gdn.recurrent(q, k, v, g, beta)
    got_o, got_s = gdn.chunk_scan(q, k, v, g, beta, chunk=16)
    np.testing.assert_allclose(got_o, want_o, atol=2e-5)
    np.testing.assert_allclose(got_s, want_s, atol=2e-5)
    state = jnp.zeros_like(want_s)
    for t in range(6):
        o, state = gdn.step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t],
                            state)
        np.testing.assert_allclose(o, want_o[:, t], atol=2e-6)


@pytest.mark.parametrize("n", [2, 5, 64, 100])
def test_unit_lower_inverse(n):
    strict = jnp.tril(jax.random.normal(jax.random.PRNGKey(n), (n, n)), -1) \
        * 0.3
    inv = gdn._unit_lower_inverse(strict)
    np.testing.assert_allclose(inv @ (jnp.eye(n) + strict), jnp.eye(n),
                               atol=1e-4)


@pytest.mark.parametrize("allow,top", [(True, 2.0), (False, 1.0)])
def test_beta_reaches_above_one_only_under_allow_neg_eigval(allow, top):
    b = jnp.linspace(-8, 8, 33)[:, None]
    g, beta = gdn.gates(jnp.zeros_like(b), b, jnp.zeros((1,)),
                        jnp.zeros((1,)), allow)
    assert float(beta.max()) == pytest.approx(top, abs=1e-3)
    assert (float(beta.max()) > 1.0) is allow and float(beta.min()) > 0
    assert float(g.max()) < 0            # alpha in (0, 1)


@pytest.mark.parametrize("b,h,dk,dv", [(5, 4, 8, 16), (3, 2, 96, 192),
                                       (2, 3, 16, 64)])
def test_the_step_kernel_interpreted_is_the_step(b, h, dk, dv):
    """Every other row frozen (g = 0, beta = 0): the kernel writes it
    through unchanged, bit for bit."""
    q, k, v, g, beta, s0 = _inputs(b, 1, h, dk, dv, seed=b)
    q, k, v, g, beta = (x[:, 0] for x in (q, k, v, g, beta))
    live = jnp.arange(b) % 2 == 0
    g, beta = gdn.freeze(g, beta, live)
    want_o, want_s = gdn.step(q, k, v, g, beta, s0)
    got_o, got_s = gdn_decode_step(q, k, v, g, beta, s0, interpret=True)
    np.testing.assert_allclose(got_o, want_o, atol=2e-6)
    np.testing.assert_allclose(got_s, want_s, atol=2e-6)
    assert bool((got_s[1] == s0[1]).all())
    assert (heads_per_group(h, dv) * dv) % 128 == 0 \
        or heads_per_group(h, dv) == h


def _layer(**kw):
    cfg = HybridConfig.debug(dtype=jnp.float32, **kw)
    layer = GatedDeltaNet(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, cfg.d_model))
    params = layer.init(jax.random.PRNGKey(4), x)["params"]
    return cfg, layer, params, x


def _pool(cfg, slots):
    return (jnp.zeros((slots, cfg.linear_key_dim,
                       cfg.linear_n_heads * cfg.linear_value_dim)),
            jnp.zeros((slots, cfg.linear_conv_kernel - 1, cfg.conv_width)))


@pytest.mark.parametrize("true_len", [19, 5, 32])
def test_a_padded_row_is_the_unpadded_row(true_len):
    """A 32-wide bucket over `true_len` real positions: outputs of the
    real positions, the state and the convolution's tail are those of
    the unpadded row; and the other row, with no real position at all,
    keeps what its slot held."""
    cfg, layer, params, x = _layer()
    held = tuple(a + 1.0 for a in _pool(cfg, 3))
    padded = SlotState(*held, jnp.asarray([0, 2]),
                       jnp.asarray([true_len, 0]),
                       jnp.asarray([True, False]))
    y_pad, new = layer.apply({"params": params}, x, padded)
    exact = SlotState(*_pool(cfg, 1), jnp.asarray([0]),
                      jnp.asarray([true_len]), None, fresh=True)
    y, want = layer.apply({"params": params}, x[:1, :true_len], exact)
    np.testing.assert_allclose(y_pad[0, :true_len], y[0], atol=2e-5)
    for got, ref, was in zip(new.arrays, want.arrays, held):
        np.testing.assert_allclose(got[0], ref[0], atol=2e-5)
        np.testing.assert_array_equal(got[2], was[2])
        np.testing.assert_array_equal(got[1], was[1])    # not in the call


@pytest.mark.parametrize("impl", ["gather", "pallas"])
def test_prefill_then_steps_is_one_prefill_of_the_whole(impl, monkeypatch):
    """24 positions through the chunkwise form into the slots, then 8
    one-token steps over every row of the pool (the XLA step and the
    interpreted kernel), against the plain forward over all 32."""
    monkeypatch.setenv("RAY_TPU_PAGED_ATTN_IMPL", impl)
    cfg, layer, params, x = _layer()
    want, _ = layer.apply({"params": params}, x)
    entry = SlotState(*_pool(cfg, 2), jnp.asarray([0, 1]),
                      jnp.asarray([24, 24]), None, fresh=True)
    y, entry = layer.apply({"params": params}, x[:, :24], entry)
    np.testing.assert_allclose(y, want[:, :24], atol=2e-5)
    for t in range(24, 32):
        entry = SlotState(*entry.arrays, None, jnp.asarray([1, 1]), None)
        y, entry = layer.apply({"params": params}, x[:, t:t + 1], entry)
        np.testing.assert_allclose(y[:, 0], want[:, t], atol=2e-5)


def test_chunks_carry_the_state_and_restart_clears_it():
    """Two chunks of 16 through one slot == one call of 32; the first
    chunk `restart`s from zero whatever the slot held."""
    cfg, layer, params, x = _layer()
    want, _ = layer.apply({"params": params}, x[:1])
    dirty = tuple(a + 3.0 for a in _pool(cfg, 2))
    slot = jnp.asarray([1])
    y0, e = layer.apply({"params": params}, x[:1, :16], SlotState(
        *dirty, slot, jnp.asarray([16]), jnp.asarray([True])))
    y1, e = layer.apply({"params": params}, x[:1, 16:], SlotState(
        *e.arrays, slot, jnp.asarray([16]), jnp.asarray([False])))
    np.testing.assert_allclose(jnp.concatenate([y0, y1], 1), want, atol=2e-5)
    np.testing.assert_array_equal(e.arrays[0][0], dirty[0][0])
