"""Manifold-constrained hyper-connections (ops/hyper_connections.py): the
plain form against a loop over one token's matrix written here, and both
Pallas kernels (interpret mode) against the plain form."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import hyper_connections as hc
from ray_tpu.ops.pallas.hyper_connections import (hc_mix_in, hc_mix_out,
                                                  row_tile)


def _hp(n, iters=20, clamp=(-30.0, 30.0)):
    return hc.HCParams(n, iters, 1e-6, 1e-6, clamp)


def _draw(seed, rows, n, c, dtype=jnp.bfloat16, res_spread=1.5, a_res=1.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (rows, n * c), jnp.float32).astype(dtype)
    phi = (jax.random.normal(ks[1], (hc.n_maps(n), n * c), jnp.float32)
           / math.sqrt(n * c)).astype(dtype)
    spread = jnp.concatenate([jnp.full((2 * n,), 0.5),
                              jnp.full((n * n,), res_spread)])
    b = jax.random.normal(ks[2], (hc.n_maps(n),)) * spread
    a = jnp.asarray([1.0, 1.0, a_res], jnp.float32)
    y = jax.random.normal(ks[3], (rows, c), jnp.float32).astype(dtype)
    return x, phi, b, a, y


def _one_token(x, phi, b, a, hp):
    """The equations for ONE token in numpy float64 loops: nothing
    shared with the code under test."""
    n = hp.n
    x = np.asarray(x, np.float64)
    phi = np.asarray(phi, np.float64)
    m = phi @ x / math.sqrt(np.mean(x * x) + hp.norm_eps)
    b = np.asarray(b, np.float64)
    pre = [1 / (1 + math.exp(-(a[0] * m[i] + b[i]))) for i in range(n)]
    post = [2 / (1 + math.exp(-(a[1] * m[n + i] + b[n + i])))
            for i in range(n)]
    mat = np.zeros((n, n))
    for i in range(n):
        for j in range(n):
            k = 2 * n + i * n + j
            z = min(max(a[2] * m[k] + b[k], hp.clamp[0]), hp.clamp[1])
            mat[i, j] = math.exp(z)
    for _ in range(hp.iters):
        for j in range(n):
            s = sum(mat[i, j] for i in range(n)) + hp.eps
            for i in range(n):
                mat[i, j] /= s
        for i in range(n):
            s = sum(mat[i, j] for j in range(n)) + hp.eps
            for j in range(n):
                mat[i, j] /= s
    return np.asarray(pre), np.asarray(post), mat


@pytest.mark.parametrize("n", [4, 2])
def test_mappings_match_a_loop_over_one_token(n):
    hp = _hp(n)
    x, phi, b, a, _ = _draw(1, 6, n, 32)
    pre, post, res = hc.mappings(x, phi, b, a, hp)
    assert pre.dtype == post.dtype == res.dtype == jnp.float32
    af = [float(v) for v in a]
    for t in range(x.shape[0]):
        want = _one_token(x[t].astype(jnp.float32),
                          phi.astype(jnp.float32), b, af, hp)
        for got, ref in zip((pre[t], post[t], res[t]), want):
            np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-5,
                                       atol=2e-6)
    assert float(post.min()) > 0.0 and float(post.max()) < 2.0
    assert float(pre.min()) > 0.0 and float(pre.max()) < 1.0


@pytest.mark.parametrize("n", [4, 2])
def test_sinkhorn_is_doubly_stochastic_after_20_and_not_after_1(n):
    # a narrow draw converges everywhere in 20 iterations; the wide one a
    # served model draws (replica_xing.py) is the next test's
    x, phi, b, a, _ = _draw(2, 64, n, 32, res_spread=0.3)
    _, _, res = hc.mappings(x, phi, b, a, _hp(n, 20))
    far = lambda r: float(jnp.maximum(                       # noqa: E731
        jnp.abs(r.sum(-1) - 1).max(), jnp.abs(r.sum(-2) - 1).max()))
    assert far(res) < 1e-4
    _, _, once = hc.mappings(x, phi, b, a, _hp(n, 1))
    assert far(once) > 1e-2
    # at the width a served model draws, two iterations are not yet
    # converged: what the benchmark's control rests on
    x, phi, b, a, _ = _draw(2, 64, n, 32, res_spread=1.5)
    _, _, res = hc.mappings(x, phi, b, a, _hp(n, 20))
    _, _, twice = hc.mappings(x, phi, b, a, _hp(n, 2))
    assert float(jnp.abs(twice - res).max(axis=(-1, -2)).mean()) > 1e-2


def test_clamp_bounds_the_logits_and_is_counted():
    n = 4
    x, phi, b, a, _ = _draw(3, 32, n, 32)
    b = b.at[2 * n + 1].set(50.0)       # one entry of Hres far over
    hp = _hp(n, clamp=(-30.0, 30.0))
    maps = hc.packed_mappings(x, phi, b, a, hp)
    assert bool((maps[:, -2] == 1.0).all())
    assert np.isfinite(np.asarray(maps)).all()
    # the clamped entry is exp(30) before the iterations, not exp(50):
    # the same as a bias of exactly 30 less the token's own logit
    open_ = hc.packed_mappings(x, phi, b, a, _hp(n, clamp=(-80.0, 80.0)))
    assert bool((open_[:, -2] == 0.0).all())
    stats = hc.counters(maps[None], jnp.arange(32)[None] < 20)
    assert stats.dtype == jnp.int32
    assert [int(v) for v in stats[:2]] == [20, 20]
    free = hc.packed_mappings(x, phi, b.at[2 * n + 1].set(0.0), a, hp)
    assert int(hc.counters(free[None])[1]) == 0


def test_unconverged_rows_are_counted():
    n = 4
    x, phi, b, a, _ = _draw(4, 64, n, 32, res_spread=4.0, a_res=3.0)
    maps20 = hc.packed_mappings(x, phi, b, a, _hp(n, 20))
    maps1 = hc.packed_mappings(x, phi, b, a, _hp(n, 1))
    _, _, res = hc.unpack(maps20, n)
    off = np.maximum(np.abs(np.asarray(res).sum(-1) - 1).max(-1),
                     np.abs(np.asarray(res).sum(-2) - 1).max(-1))
    assert int(hc.counters(maps20)[2]) == int((off > 1e-3).sum()) > 0
    assert int(hc.counters(maps1)[2]) > int(hc.counters(maps20)[2])


@pytest.mark.parametrize("n,rows,c,tile", [
    (4, 9, 128, None),          # one tile: a decode step of few rows
    (4, 48, 128, 16),           # three whole tiles
    (2, 33, 256, None),         # no tile divides 33: one block
    (4, 24, 64, None),          # debug width: streams inside one lane tile
])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_kernels_match_the_plain_form(n, rows, c, tile, dtype):
    hp = _hp(n)
    x, phi, b, a, y = _draw(5, rows, n, c, dtype)
    want_maps = hc.packed_mappings(x, phi, b, a, hp)
    pre, post, res = hc.unpack(want_maps, n)
    h, maps = hc_mix_in(x, phi, b, a, hp, interpret=True, tile=tile)
    assert h.dtype == dtype and maps.shape == (rows, hc.packed_width(n))
    np.testing.assert_allclose(np.asarray(maps), np.asarray(want_maps),
                               rtol=2e-4, atol=2e-5)
    tol = dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(h, np.float32),
        np.asarray(hc.mix_in(x, pre), np.float32), **tol)
    out = hc_mix_out(x, y, want_maps, n, interpret=True, tile=tile)
    assert out.dtype == dtype and out.shape == x.shape
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(hc.mix_out(x, y, post, res), np.float32), **tol)


def test_float32_phi_beside_a_bfloat16_stream():
    n = 4
    hp = _hp(n)
    x, phi, b, a, _ = _draw(6, 16, n, 128)
    phi32 = phi.astype(jnp.float32) * 1.001     # no longer bf16 values
    want = hc.packed_mappings(x, phi32, b, a, hp)
    _, maps = hc_mix_in(x, phi32, b, a, hp, interpret=True)
    np.testing.assert_allclose(np.asarray(maps), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_read_write_take_the_plain_form_off_the_tpu():
    n = 4
    hp = _hp(n)
    x, phi, b, a, y = _draw(7, 12, n, 32)
    x3, y3 = x.reshape(2, 6, -1), y.reshape(2, 6, -1)
    h, maps = hc.read(x3, phi, b, a, hp)
    assert h.shape == (2, 6, 32) and maps.shape == (2, 6, 26)
    out = hc.write(x3, y3, maps, n)
    pre, post, res = hc.mappings(x3, phi, b, a, hp)
    np.testing.assert_array_equal(np.asarray(h, np.float32),
                                  np.asarray(hc.mix_in(x3, pre), np.float32))
    np.testing.assert_array_equal(
        np.asarray(out, np.float32),
        np.asarray(hc.mix_out(x3, y3, post, res), np.float32))
    def sub_layer(x):
        h, maps = hc.read(x, phi, b, a, hp)
        return hc.write(x, h, maps, n)
    text = jax.jit(sub_layer).lower(x3).as_text(debug_info=True)
    for scope in ("hc.mappings", "hc.mix_in", "hc.mix_out"):
        assert scope in text


@pytest.mark.parametrize("rows,want", [(129, 129), (4096, 128), (9, 9),
                                       (128, 128), (2048, 128), (257, 257),
                                       (96, 96), (160, 80), (520, 520)])
def test_row_tile_divides_the_rows_or_takes_them_all(rows, want):
    assert row_tile(rows) == want


def test_rows_no_tile_divides_and_one_block_cannot_hold_are_padded():
    """520 rows (no multiple of 16 divides them, too many for one
    block): the call runs 640 and hands back 520."""
    from ray_tpu.ops.pallas.hyper_connections import _padded
    assert [_padded(r) for r in (129, 257, 384, 520, 4096)] == [
        129, 257, 384, 640, 4096]
    n, rows = 2, 520
    hp = _hp(n)
    x, phi, b, a, y = _draw(8, rows, n, 128)
    want = hc.packed_mappings(x, phi, b, a, hp)
    h, maps = hc_mix_in(x, phi, b, a, hp, interpret=True)
    assert h.shape == (rows, 128) and maps.shape == want.shape
    np.testing.assert_allclose(np.asarray(maps), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    out = hc_mix_out(x, y, want, n, interpret=True)
    _, post, res = hc.unpack(want, n)
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(hc.mix_out(x, y, post, res), np.float32),
        rtol=2e-2, atol=2e-2)
