"""Ring attention, MoE dispatch, pipeline parallel (SURVEY §2.2 P4/P5/P6)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.parallel import MeshSpec, build_mesh
from ray_tpu.parallel.pipeline import (pipeline_apply, pipeline_reference,
                                       stack_stage_params)
from ray_tpu.ops import (ring_attention, multi_head_attention,
                         moe_dispatch_combine, expert_capacity)
from ray_tpu.ops.moe import moe_dropless, route


@pytest.fixture(scope="module")
def rng():
    return np.random.RandomState(0)


class TestRingAttention:
    def test_matches_dense_causal(self, rng):
        mesh = build_mesh(MeshSpec(sp=8))
        q = jnp.asarray(rng.randn(2, 64, 4, 16), jnp.float32)
        k = jnp.asarray(rng.randn(2, 64, 2, 16), jnp.float32)
        v = jnp.asarray(rng.randn(2, 64, 2, 16), jnp.float32)
        ref = multi_head_attention(q, k, v, causal=True)
        out = ring_attention(q, k, v, mesh=mesh)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_matches_dense_non_causal(self, rng):
        mesh = build_mesh(MeshSpec(sp=4, dp=2))
        q = jnp.asarray(rng.randn(2, 32, 4, 8), jnp.float32)
        k = jnp.asarray(rng.randn(2, 32, 4, 8), jnp.float32)
        v = jnp.asarray(rng.randn(2, 32, 4, 8), jnp.float32)
        ref = multi_head_attention(q, k, v, causal=False)
        out = ring_attention(q, k, v, mesh=mesh, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_sp1_degenerate(self, rng):
        mesh = build_mesh(MeshSpec(sp=1), devices=jax.devices()[:1])
        q = jnp.asarray(rng.randn(1, 16, 2, 8), jnp.float32)
        ref = multi_head_attention(q, q, q, causal=True)
        out = ring_attention(q, q, q, mesh=mesh)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)

    def test_grad_flows(self, rng):
        mesh = build_mesh(MeshSpec(sp=8))
        q = jnp.asarray(rng.randn(1, 32, 2, 8), jnp.float32)

        def loss(q):
            return ring_attention(q, q, q, mesh=mesh).sum()

        g = jax.jit(jax.grad(loss))(q)
        gref = jax.grad(
            lambda q: multi_head_attention(q, q, q, causal=True).sum())(q)
        np.testing.assert_allclose(np.asarray(g), np.asarray(gref),
                                   atol=1e-4)


class TestMoE:
    def test_identity_experts_reconstruct(self, rng):
        x = jnp.asarray(rng.randn(64, 16), jnp.float32)
        logits = jnp.asarray(rng.randn(64, 4), jnp.float32)
        out, aux = moe_dispatch_combine(x, logits, lambda e: e, k=2,
                                        capacity=128)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x),
                                   atol=1e-5)
        assert abs(float(aux.expert_load.sum()) - 2.0) < 1e-5

    def test_capacity_drops_are_finite(self, rng):
        x = jnp.asarray(rng.randn(64, 16), jnp.float32)
        logits = jnp.asarray(rng.randn(64, 4), jnp.float32)
        out, aux = moe_dispatch_combine(x, logits, lambda e: e, k=2,
                                        capacity=1)
        assert bool(jnp.isfinite(out).all())
        assert float(aux.load_balance_loss) > 0

    def test_dispatch_mass_conserved(self, rng):
        # every token kept under generous capacity: ||out|| > 0 rows for all
        x = jnp.ones((32, 8), jnp.float32)
        logits = jnp.asarray(rng.randn(32, 4), jnp.float32)
        out, _ = moe_dispatch_combine(x, logits, lambda e: e * 2.0, k=1,
                                      capacity=64)
        np.testing.assert_allclose(np.asarray(out), 2.0 * np.ones((32, 8)),
                                   atol=1e-5)

    def test_expert_capacity_formula(self):
        assert expert_capacity(64, 4, 2, 1.25) == 40
        assert expert_capacity(4, 64, 1, 1.0) == 1

    def test_ep_sharded_matches_single(self, rng):
        """Same dispatch math under jit with experts sharded over ep."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        mesh = build_mesh(MeshSpec(ep=4, dp=2))
        E, C, D = 4, 32, 16
        x = jnp.asarray(rng.randn(64, D), jnp.float32)
        logits = jnp.asarray(rng.randn(64, E), jnp.float32)
        w = jnp.asarray(rng.randn(E, D, D) * 0.1, jnp.float32)

        def expert_fn(batch):   # (E, C, D) @ per-expert weight
            return jnp.einsum("ecd,edf->ecf", batch, w)

        ref, _ = moe_dispatch_combine(x, logits, expert_fn, k=2, capacity=C)

        ws = jax.device_put(w, NamedSharding(mesh, P("ep", None, None)))

        @jax.jit
        def run(x, logits, w):
            def fn(batch):
                return jnp.einsum("ecd,edf->ecf", batch, w)
            out, _ = moe_dispatch_combine(x, logits, fn, k=2, capacity=C)
            return out

        out = run(x, logits, ws)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4)


class TestMoEDropless:
    """Dropless twins of TestMoE: the sorted, grouped path."""

    @staticmethod
    def _weights(rng, e=4, d=16, f=8):
        return [jnp.asarray(rng.randn(*shape) * 0.3, jnp.float32)
                for shape in ((e, d, f), (e, d, f), (e, f, d))]

    @staticmethod
    def _expert_fn(wg, wu, wd):
        def fn(batch):
            gate = jnp.einsum("ecd,edf->ecf", batch, wg)
            up = jnp.einsum("ecd,edf->ecf", batch, wu)
            return jnp.einsum("ecf,efd->ecd", jax.nn.silu(gate) * up, wd)
        return fn

    @pytest.mark.parametrize("routing", ["topk_softmax", "softmax_topk"])
    def test_matches_capacity_path_when_nothing_overflows(self, rng,
                                                          routing):
        x = jnp.asarray(rng.randn(64, 16), jnp.float32)
        logits = jnp.asarray(rng.randn(64, 4), jnp.float32)
        wg, wu, wd = self._weights(rng)
        ref, _ = moe_dispatch_combine(
            x, logits, self._expert_fn(wg, wu, wd), k=2, capacity=128,
            routing=routing)
        weights, idx = route(logits, 2, routing)
        out, stats = moe_dropless(x, weights, idx, wg, wu, wd)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)
        assert stats[0] == 128 and stats[1] == 64 and stats[2] == 0

    def test_masked_rows_are_given_to_no_expert(self, rng):
        x = jnp.asarray(rng.randn(32, 16), jnp.float32)
        logits = jnp.asarray(rng.randn(32, 4), jnp.float32)
        wg, wu, wd = self._weights(rng)
        weights, idx = route(logits, 2)
        mask = jnp.arange(32) < 20
        full, _ = moe_dropless(x, weights, idx, wg, wu, wd)
        out, stats = moe_dropless(x, weights, idx, wg, wu, wd, mask)
        np.testing.assert_array_equal(np.asarray(out[:20]),
                                      np.asarray(full[:20]))
        assert not np.asarray(out[20:]).any()
        assert stats.tolist()[:3] == [40, 20, 12]

    @pytest.mark.parametrize("k,n,sizes", [
        (64, 32, [100, 0, 57, 43]),
        (1536, 2048, [0, 77]),
        (2048, 1536, [91, 0]),
    ], ids=["toy", "lfm2_down", "lfm2_up"])
    def test_tpu_kernel_matches_the_reference_lowering(self, rng, k, n,
                                                       sizes):
        """The TPU branch of grouped_matmul (megablox) in interpret
        mode against jax.lax.ragged_dot, on the rows that belong to a
        group; what lies behind the last group is nobody's. At LFM2's
        expert widths the tiles are `gmm_tiling`'s, one of them 1 536
        wide."""
        from ray_tpu.ops.moe import grouped_matmul
        live = sum(sizes)
        xs = jnp.asarray(rng.randn(-(-live // 128) * 128, k), jnp.float32)
        w = jnp.asarray(rng.randn(len(sizes), k, n), jnp.float32)
        sizes = jnp.asarray(sizes, jnp.int32)
        want = grouped_matmul(xs, w, sizes)
        got = grouped_matmul(xs, w, sizes, interpret=True)
        np.testing.assert_allclose(np.asarray(got[:live]),
                                   np.asarray(want[:live]),
                                   atol=1e-4 * (k / 64) ** 0.5)

    @pytest.mark.parametrize("k,n,tiles", [
        # a multiple of 1 024 is cut at 1 024, as before PR 41: OLMoE's
        # and sarvam-105b's programs are the programs they were
        (2048, 1024, (1024, 1024)), (1024, 2048, (1024, 1024)),
        (4096, 2048, (1024, 1024)), (2048, 4096, (1024, 1024)),
        # LFM2's 1 536: one whole tile, no ragged or masked one
        (2048, 1536, (1024, 1536)), (1536, 2048, (1536, 1024)),
        # the largest divisor that is a multiple of 128, up to 1 536
        (2560, 1408, (1280, 1408)), (11776, 2048, (512, 1024)),
        # up to 1 024 a side is one tile; no such divisor: the old cut
        (64, 32, (64, 32)), (768, 1000, (768, 1000)),
        (1100, 2048, (1024, 1024)), (2048, 1100, (1024, 1024)),
    ])
    def test_gmm_tiles_follow_the_shape(self, k, n, tiles):
        from ray_tpu.ops.moe import gmm_tiling
        assert gmm_tiling(k, n) == (128, *tiles)

    @pytest.mark.parametrize("side,want", [
        (1536, [512, 768, 1024, 1536]), (2048, [1024, 2048]),
        (1024, [1024]), (64, [64]), (1100, [1024, 1100]),
    ])
    def test_microbenchmark_tries_the_cut_the_divisors_and_the_whole(
            self, side, want):
        from tools.gmm_microbench import candidate_tiles
        assert candidate_tiles(side, [512, 768, 1536]) == want

    def test_microbenchmark_draws_distinct_experts_a_row(self):
        from tools.gmm_microbench import draw_group_sizes
        sizes = draw_group_sizes(np.random.RandomState(0), 128, 64, 4, 0.25)
        assert sizes.sum() == 128 * 4 and sizes.max() <= 128
        every = draw_group_sizes(np.random.RandomState(0), 16, 4, 4, 3.0)
        assert every.tolist() == [16] * 4

    def test_one_program_for_any_routing(self, rng):
        """Static shapes: the same compiled program serves an even
        routing and every row on the same two experts."""
        wg, wu, wd = self._weights(rng)
        x = jnp.asarray(rng.randn(32, 16), jnp.float32)

        @jax.jit
        def run(x, logits):
            weights, idx = route(logits, 2)
            return moe_dropless(x, weights, idx, wg, wu, wd)

        _, even = run(x, jnp.asarray(rng.randn(32, 4), jnp.float32))
        _, skew = run(x, jnp.tile(jnp.asarray([[5., 4., 0., 0.]]),
                                  (32, 1)))
        assert run._cache_size() == 1
        assert skew.tolist() == [64, 32, 0, 32, 2, 64]
        assert even[3] < 32 and even[4] == 4


class TestPipeline:
    def _stages(self, rng, n, d):
        return [
            {"w": jnp.asarray(rng.randn(d, d) * 0.1, jnp.float32),
             "b": jnp.asarray(rng.randn(d) * 0.1, jnp.float32)}
            for _ in range(n)
        ]

    @staticmethod
    def _stage_fn(p, h):
        return jnp.tanh(h @ p["w"] + p["b"])

    def test_matches_sequential(self, rng):
        mesh = build_mesh(MeshSpec(pp=4, dp=2))
        stacked = stack_stage_params(self._stages(rng, 4, 16))
        x = jnp.asarray(rng.randn(16, 16), jnp.float32)
        ref = pipeline_reference(self._stage_fn, stacked, x)
        out = pipeline_apply(self._stage_fn, stacked, x, mesh=mesh,
                             n_microbatches=8)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    def test_grad_matches(self, rng):
        mesh = build_mesh(MeshSpec(pp=8))
        stacked = stack_stage_params(self._stages(rng, 8, 8))
        x = jnp.asarray(rng.randn(8, 8), jnp.float32)

        def loss(p):
            return pipeline_apply(self._stage_fn, p, x, mesh=mesh,
                                  n_microbatches=4).sum()

        g = jax.jit(jax.grad(loss))(stacked)
        gref = jax.grad(lambda p: pipeline_reference(
            self._stage_fn, p, x).sum())(stacked)
        for a, b in zip(jax.tree_util.tree_leaves(g),
                        jax.tree_util.tree_leaves(gref)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4)

    def test_pp1_fallback(self, rng):
        mesh = build_mesh(MeshSpec(pp=1), devices=jax.devices()[:1])
        stacked = stack_stage_params(self._stages(rng, 3, 8))
        x = jnp.asarray(rng.randn(4, 8), jnp.float32)
        out = pipeline_apply(self._stage_fn, stacked, x, mesh=mesh,
                             n_microbatches=2)
        ref = pipeline_reference(self._stage_fn, stacked, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref))

    def test_bad_microbatch_raises(self, rng):
        mesh = build_mesh(MeshSpec(pp=4, dp=2))
        stacked = stack_stage_params(self._stages(rng, 4, 8))
        x = jnp.asarray(rng.randn(6, 8), jnp.float32)
        with pytest.raises(ValueError):
            pipeline_apply(self._stage_fn, stacked, x, mesh=mesh,
                           n_microbatches=4)
