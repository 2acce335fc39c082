"""ops/gated_deltanet.py, ops/pallas/gdn_decode.py and
ops/pallas/kda_prefill.py: the chunkwise form, the one-token step, the
fused step kernel and the fused chunk kernel (both interpreted) against
the recurrence token by token, and the layers of models/hybrid.py over
a per-slot state: padded == unpadded, prefill then steps == one
prefill; and the short convolution (`causal_conv`: the tail a row a
sequence) against the form it had with the tail on the token axis."""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.experimental.pallas import tpu as pltpu

from ray_tpu.models.hybrid import (GatedDeltaNet, HybridConfig,
                                   KimiDeltaAttention)
from ray_tpu.ops import gated_deltanet as gdn
from ray_tpu.ops.attention import SlotState
from ray_tpu.ops.pallas.gdn_decode import gdn_decode_step, heads_per_group
from ray_tpu.ops.pallas.kda_prefill import kda_chunk_scan


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


def _channel_inputs(b, s, h, dk, dv, rate, seed=0, constant=False):
    """Keys after a SiLU, as the layer makes them, and a rate a key
    channel drawn up to `rate` a token (or `constant`: every channel at
    it)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = gdn.l2norm(jax.nn.silu(jax.random.normal(ks[0], (b, s, h, dk)))) \
        * dk ** -0.5
    k = gdn.l2norm(jax.nn.silu(jax.random.normal(ks[1], (b, s, h, dk))))
    v = jax.random.normal(ks[2], (b, s, h, dv))
    g = -rate * (jnp.ones((b, s, h, dk)) if constant
                 else jax.random.uniform(ks[3], (b, s, h, dk)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    state = jax.random.normal(ks[5], (b, dk, h * dv))
    return q, k, v, g, beta, state


@pytest.mark.parametrize("s,chunk,lens,carried,rate,constant", [
    (150, 64, None, True, 0.5, False),      # no multiple of the chunk
    (37, 16, None, True, 0.5, False),       # nor of the sub-chunk
    (100, 64, None, False, 0.5, False),     # from nothing
    (128, 32, (128, 45), True, 0.5, False),  # two rows, two true lengths
    (96, 64, (70, 96), True, 1.6, True),    # a fast channel
], ids=["past_a_chunk", "past_a_sub_chunk", "no_state", "two_lengths",
        "fast_channel"])
def test_the_chunk_kernel_interpreted_is_the_recurrence(
        s, chunk, lens, carried, rate, constant):
    """ops/pallas/kda_prefill.py against the recurrence token by token
    and against the plain chunkwise form it replaces on the TPU, at the
    real positions (a row's outputs past its true length are the
    caller's to drop; past its last live chunk they are zero)."""
    b, h, dk, dv = 2, 3, 16, 8
    q, k, v, g, beta, state = _channel_inputs(b, s, h, dk, dv, rate,
                                              constant=constant)
    n_new = None if lens is None else jnp.asarray(lens, jnp.int32)
    real = jnp.ones((b, s), bool) if lens is None \
        else jnp.arange(s)[None, :] < n_new[:, None]
    g, beta = gdn.freeze(g, beta, real)
    state = state if carried else None
    want_o, want_s = gdn.recurrent(q, k, v, g, beta, state)
    plain_o, plain_s = gdn.chunk_scan(q, k, v, g, beta, state, chunk=chunk)
    got_o, got_s = kda_chunk_scan(q, k, v, g, beta, state, n_new,
                                  chunk=chunk, interpret=True)
    assert got_o.shape == want_o.shape and got_s.shape == want_s.shape
    assert bool(jnp.isfinite(got_o).all())
    mask = real[..., None, None]
    for ref_o, ref_s in ((want_o, want_s), (plain_o, plain_s)):
        np.testing.assert_allclose(got_o * mask, ref_o * mask, atol=1e-5)
        np.testing.assert_allclose(got_s, ref_s, atol=2e-5)
    if lens is not None:
        c = min(chunk, s)
        dead = jnp.arange(s)[None, :] >= -(-n_new[:, None] // c) * c
        assert bool((jnp.where(dead[..., None, None], got_o, 0.0)
                     == 0.0).all())


def test_the_chunk_kernel_never_reads_a_dead_chunk():
    """NaNs planted in q, k, v of the chunks past a row's true length
    reach neither the state nor a real output row, and the bounded grid
    gives the full loop's result bit for bit: the state after the last
    live chunk, the same outputs where the row is real."""
    b, s, h, dk, dv, chunk = 2, 128, 2, 16, 8, 32
    q, k, v, g, beta, state = _channel_inputs(b, s, h, dk, dv, 0.5, seed=3)
    n_new = jnp.asarray([40, 97], jnp.int32)
    real = jnp.arange(s)[None, :] < n_new[:, None]
    g, beta = gdn.freeze(g, beta, real)
    whole_o, whole_s = kda_chunk_scan(q, k, v, g, beta, state, None,
                                      chunk=chunk, interpret=True)
    dead = (jnp.arange(s)[None, :]
            >= -(-n_new[:, None] // chunk) * chunk)[..., None, None]
    assert int(dead.sum()) == 64         # the first row's last two chunks
    nan = lambda x: jnp.where(dead, jnp.nan, x)              # noqa: E731
    got_o, got_s = kda_chunk_scan(nan(q), nan(k), nan(v), g, beta, state,
                                  n_new, chunk=chunk, interpret=True)
    assert bool(jnp.isfinite(got_o).all()) and bool(
        jnp.isfinite(got_s).all())
    np.testing.assert_array_equal(got_s, whole_s)
    np.testing.assert_array_equal(
        jnp.where(real[..., None, None], got_o, 0.0),
        jnp.where(real[..., None, None], whole_o, 0.0))
    # and the padded row is the unpadded row bit for bit
    for r, n in enumerate([40, 97]):
        cut_o, cut_s = kda_chunk_scan(
            *(x[r:r + 1, :n] for x in (q, k, v, g, beta)), state[r:r + 1],
            None, chunk=chunk, interpret=True)
        np.testing.assert_array_equal(cut_o[0], got_o[r, :n])
        np.testing.assert_array_equal(cut_s[0], got_s[r])


def _layer(kind=GatedDeltaNet, preset=HybridConfig.debug, **kw):
    cfg = preset(dtype=jnp.float32, **kw)
    layer = kind(cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, cfg.d_model))
    params = layer.init(jax.random.PRNGKey(4), x)["params"]
    return cfg, layer, params, x


def _pool(cfg, slots):
    return (jnp.zeros((slots, cfg.linear_key_dim,
                       cfg.linear_n_heads * cfg.linear_value_dim)),
            jnp.zeros((slots,
                       (cfg.linear_conv_kernel - 1) * cfg.conv_width)))


@pytest.mark.parametrize("route", ["plain", "kernel"])
@pytest.mark.parametrize("true_len", [19, 5, 32])
def test_a_padded_row_is_the_unpadded_row(true_len, route, monkeypatch):
    """A 32-wide bucket over `true_len` real positions: outputs of the
    real positions, the state and the convolution's tail are those of
    the unpadded row; and the other row, with no real position at all,
    keeps what its slot held. `kernel`: the layer with a decay a key
    channel on the route a TPU takes (models/hybrid.py:_scan), the
    chunk kernel interpreted: chunks of 16, so a row of 5 or 19 leaves
    one or none of the bucket's two to the bound."""
    if route == "kernel":
        cfg, layer, params, x = _layer(KimiDeltaAttention,
                                       HybridConfig.solar_debug)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        interpreted = pltpu.force_tpu_interpret_mode()
    else:
        cfg, layer, params, x = _layer()
        interpreted = contextlib.nullcontext()
    held = tuple(a + 1.0 for a in _pool(cfg, 3))
    padded = SlotState(*held, jnp.asarray([0, 2]),
                       jnp.asarray([true_len, 0]),
                       jnp.asarray([True, False]))
    exact = SlotState(*_pool(cfg, 1), jnp.asarray([0]),
                      jnp.asarray([true_len]), None, fresh=True)
    with interpreted:
        y_pad, new = layer.apply({"params": params}, x, padded)
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


# ---- the short convolution ------------------------------------------------

def token_axis_conv(u, w, tail, n_new, activation):
    """`ops/gated_deltanet.py:causal_conv` as it stood until PR 55, the
    reference here and the other side of `tools/kda_microbench.py --what
    conv` on the chip: tail (B, K - 1, C) (None: zeros) concatenated in
    front of u (B, S, C) on the TOKEN axis, slices of that axis summed,
    the new tail gathered from it at each row's true length."""
    b, s, c = u.shape
    k = w.shape[0]
    if tail is None:
        tail = jnp.zeros((b, k - 1, c), u.dtype)
    cat = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
    wf = w.astype(jnp.float32)
    out = sum(wf[j] * cat[:, k - 1 - j:k - 1 - j + s].astype(jnp.float32)
              for j in range(k))
    if n_new is None:
        new_tail = cat[:, s:]
    else:
        idx = n_new[:, None] + jnp.arange(k - 1)[None, :]
        new_tail = jnp.take_along_axis(cat, idx[:, :, None], axis=1)
    if activation is not None:
        out = activation(out)
    return out.astype(u.dtype), new_tail


def _rows_conv(u, w, tail, n_new=None, activation=jax.nn.silu):
    """The reference behind `causal_conv`'s signature: the tail a row a
    sequence in, a row a sequence out."""
    b, _, c = u.shape
    out, new = token_axis_conv(
        u, w, None if tail is None else tail.reshape(b, -1, c), n_new,
        activation)
    return out, new.reshape(b, -1)


# true lengths of the five rows of a call of `s` new positions under a
# kernel of `k` taps (one token: 1 or 0, whatever the name asks for)
N_NEW = {"none": lambda s, k: None,
         "zero": lambda s, k: [0] * 5,
         "under_the_tail": lambda s, k: [min(s, i % (k - 1)) for i in range(5)],
         "partial": lambda s, k: [min(s, k - 1 + i) for i in range(5)],
         "full": lambda s, k: [s] * 5}


@pytest.mark.parametrize("tail", ["fresh", "carried"])
@pytest.mark.parametrize("activation", [None, jax.nn.silu],
                         ids=["plain", "silu"])
@pytest.mark.parametrize("k", [3, 4])
@pytest.mark.parametrize("n_new", sorted(N_NEW))
@pytest.mark.parametrize("s", [1, 2, 9])
def test_causal_conv_is_the_token_axis_form_bit_for_bit(s, n_new, k,
                                                        activation, tail):
    """One token, fewer tokens than the tail holds, and a prompt: the
    result and the new tail are the parent's to the bit (the float32
    products and sums in its order), operation by operation as written
    (a compiler may contract a product and a sum of either form)."""
    rng = np.random.default_rng(s * 100 + k)
    c = 256
    u = jnp.asarray(rng.normal(size=(5, s, c)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(k, c)), jnp.float32)
    held = None if tail == "fresh" else jnp.asarray(
        rng.normal(size=(5, (k - 1) * c)), jnp.bfloat16)
    n = N_NEW[n_new](s, k)
    n = None if n is None else jnp.asarray(n, jnp.int32)
    want_o, want_t = _rows_conv(u, w, held, n, activation)
    got_o, got_t = gdn.causal_conv(u, w, held, n, activation)
    assert (got_o.shape, got_o.dtype) == (want_o.shape, want_o.dtype)
    assert (got_t.shape, got_t.dtype) == ((5, (k - 1) * c), want_t.dtype)
    np.testing.assert_array_equal(got_o, want_o)
    np.testing.assert_array_equal(got_t, want_t)


@pytest.mark.parametrize("family", ["hybrid-debug", "lfm2-moe-debug",
                                    "solar-debug"])
def test_engine_tokens_and_tails_are_the_token_axis_forms(family,
                                                          monkeypatch):
    """The engine's own step functions: two prompts of unequal length
    prefilled as one group into two of three slots, then three decode
    steps over the pool with the third slot idle. The tokens and every
    conv-carrying layer's tail pool are those of the same functions
    over the reference form. The first such layer's tail to the bit (no
    convolution stands before its input); behind it to float32
    rounding: a compiled program contracts a product and a sum where
    its fusions let it, and the two forms fuse differently."""
    from ray_tpu.models import get_model
    from ray_tpu.serve.llm import LLMEngine, LLMEngineConfig
    model = get_model(family, dtype=jnp.float32)
    params = model.init_params(jax.random.PRNGKey(0))
    prompts = [np.arange(3, 14), np.arange(40, 47)]
    tokens = np.zeros((2, 16), np.int32)
    for row, prompt in zip(tokens, prompts):
        row[:len(prompt)] = prompt
    lens = jnp.asarray([len(p) for p in prompts], jnp.int32)

    def serve():
        eng = LLMEngine(model, params, LLMEngineConfig(
            max_slots=3, max_seq_len=64, prefill_buckets=(16,),
            kv_page_size=8, max_prefill_batch=2))
        try:
            for slot, prompt in enumerate(prompts):
                assert eng._pages.reserve(slot, len(prompt) + 4)
            table = jnp.asarray(eng._pages.rows())
            rows, key = table.shape[0], jax.random.PRNGKey(0)
            toks, _, pools, lengths, *_ = jax.jit(
                eng._prefill_paged_impl, static_argnames=("pad_len",))(
                params, eng._pools, table, eng._state.lengths,
                jnp.asarray(tokens), jnp.arange(2), lens, jnp.zeros((2,)),
                jnp.ones((2,)), key, pad_len=16, n_real=jnp.int32(2))
            step = jax.jit(eng._decode_paged_impl)
            live = jnp.arange(rows) < 2
            last = jnp.zeros((rows,), jnp.int32).at[:2].set(toks)
            out = [last[:2]]
            for _ in range(3):
                last, _, pools, lengths, *_ = step(
                    params, pools, table, lengths, last, live,
                    jnp.zeros((rows,)), jnp.ones((rows,)), key)
                out.append(last[:2])
            tails = [np.asarray(pools[i][-1])
                     for i, c in enumerate(eng._pages.spec) if c.by_slot]
        finally:
            eng.shutdown()
        return np.stack(out, 1), tails
    got_tokens, got_tails = serve()
    monkeypatch.setattr(gdn, "causal_conv", _rows_conv)
    want_tokens, want_tails = serve()
    np.testing.assert_array_equal(got_tokens, want_tokens)
    assert got_tokens.shape == (2, 4)
    assert len(got_tails) == sum(
        kind != "full_attention" for kind in model.cfg.layer_types)
    np.testing.assert_array_equal(got_tails[0], want_tails[0])
    for got, want in zip(got_tails, want_tails):
        assert got.shape == want.shape and got.ndim == 2
        # two slots hold a tail, the idle one and the scratch row none
        assert list(np.abs(want).sum(1) > 0) == [True, True, False, False]
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
