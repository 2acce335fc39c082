"""The tiny LFM2-MoE decoder (models/hybrid.py: gated short convolutions
with their last inputs a slot, full attention over a packed pool of
narrow heads, a dense feed-forward then routed experts) by itself,
through LLMEngine against the benchmark's plain reference, and the
packed page pool."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import reference_lfm2moe, replica_lfm2moe
from ray_tpu.models import get_model
from ray_tpu.ops import moe
from ray_tpu.ops.attention import (PagedKV, SlotState, kv_cache_spec,
                                   packed_kv_shape, paged_cached_attention)
from ray_tpu.ops.gated_deltanet import causal_conv
from ray_tpu.serve.llm.engine import LLMEngine, LLMEngineConfig

BUCKETS = (16, 32, 64)
CONTROLS = reference_lfm2moe.CONTROLS
TIGHT = {"logit_tol_rel": 1e-3, "logit_mean_tol_rel": 1e-3,
         "logit_decode_mean_tol_rel": 1e-3, "argmax_tol_rel": 1e-3,
         "tie_margin_rel": 1e-4, "busy_new_tokens": 11}


@pytest.fixture(scope="module")
def tiny():
    model = get_model("lfm2-moe-debug", param_dtype=jnp.float32,
                      dtype=jnp.float32)
    params = model.init_params(jax.random.PRNGKey(0))
    # norm weights off their initial ones: at ones a rotation before
    # the per-head norm and one after it are the same function
    params = jax.tree_util.tree_map(
        lambda a: a * (1 + 0.3 * jax.random.normal(
            jax.random.PRNGKey(a.size), a.shape, a.dtype))
        if a.ndim == 1 else a, params)
    return model, params


def _engine(tiny, **kw):
    model, params = tiny
    return LLMEngine(model, params, LLMEngineConfig(**{**dict(
        max_slots=3, max_seq_len=128, prefill_buckets=BUCKETS,
        kv_page_size=8, pipeline_depth=3, max_prefill_batch=2), **kw}))


def _section(cfg):
    return dict(
        hidden_size=cfg.d_model, num_hidden_layers=cfg.n_layers,
        num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, intermediate_size=cfg.d_ff,
        moe_intermediate_size=cfg.d_expert, num_experts=cfg.n_experts,
        num_experts_per_tok=cfg.experts_per_token,
        num_dense_layers=cfg.n_dense_layers, norm_topk_prob=True,
        routed_scaling_factor=cfg.routed_scaling, use_expert_bias=True,
        conv_L_cache=cfg.conv_kernel, layer_types=list(cfg.layer_types),
        rope_theta=cfg.rope_theta, norm_eps=cfg.norm_eps,
        vocab_size=cfg.vocab_size)


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(1, 256, n) for n in (2, 19, 33, 1, 40, 16, 9)]


@pytest.fixture(scope="module")
def one_at_a_time(tiny, prompts):
    """Every prompt's 6 greedy tokens, one request at a time."""
    eng = _engine(tiny)
    try:
        return [eng.generate_sync(p, max_new_tokens=6) for p in prompts]
    finally:
        eng.shutdown()


def test_the_family_is_a_preset_of_the_hybrid_decoder(tiny):
    model, _ = tiny
    cfg = model.cfg
    assert cfg.layer_types == ("conv", "conv", "full_attention", "conv",
                               "conv")
    assert [cfg.dense_ff(i) for i in range(5)] == [True] + [False] * 4
    assert cfg.pre_norm and cfg.tie_embeddings and cfg.qk_norm == "head"
    assert model.step_stats == moe.MOE_STATS
    assert get_model("hybrid-debug").step_stats == ()
    big = get_model("lfm2-24b-a2b").cfg
    assert (big.n_layers, big.d_model, big.head_dim) == (40, 2048, 64)
    assert [i for i, k in enumerate(big.layer_types)
            if k == "full_attention"] == list(range(2, 40, 4))
    assert (big.d_ff, big.d_expert, big.n_experts, big.experts_per_token,
            big.n_dense_layers) == (11776, 1536, 64, 4, 2)
    with pytest.raises(ValueError, match="'linear_attention', "
                       "'full_attention' or 'conv'"):
        get_model("lfm2-moe-debug", layer_types=("conv",) * 4 + ("mamba",))


def test_the_cache_is_declared_a_layer(tiny):
    spec = kv_cache_spec(tiny[0])
    assert [c.by_slot for c in spec] == [True, True, False, True, True]
    assert [c.entry for c in spec] == [SlotState] * 2 + [PagedKV] \
        + [SlotState] * 2
    # a conv layer's tail is one row a slot: K - 1 = 2 inputs of 64
    assert spec[0].shapes == ((2 * 64,),)
    # 2 KV heads of 16 laid out as 8 (a whole tile of rows), packed
    # eight to a 128-lane row
    assert spec[2].shapes == ((1, 128), (1, 128))
    # the cut the benchmark serves: 4 096 B of K and V a token as
    # published (2 full layers x 2 x 8 heads x 64 x bf16), 57 344 B of
    # conv state a slot (7 layers x 2 x 2 048 x bf16)
    cut = kv_cache_spec(get_model("lfm2-24b-a2b", n_layers=9))

    def row_bytes(by_slot):
        return sum(int(np.prod(t)) * jnp.dtype(d).itemsize
                   for c in cut if c.by_slot == by_slot
                   for t, d in zip(c.shapes, c.dtypes))
    assert row_bytes(False) == 4096 and row_bytes(True) == 57344
    assert cut[2].shapes == ((4, 128), (4, 128))


@pytest.mark.parametrize("heads,dim,shape", [
    (8, 64, (4, 128)), (8, 128, (8, 128)), (32, 128, (32, 128)),
    (3, 32, (1, 128)), (5, 64, (3, 128)), (2, 256, (2, 256)),
    (4, 48, (4, 48))])
def test_packed_kv_shape(heads, dim, shape):
    assert packed_kv_shape(heads, dim) == shape


def test_plain_forward_against_the_reference(tiny):
    model, params = tiny
    tokens = np.random.default_rng(1).integers(1, 256, (1, 37))
    with jax.default_matmul_precision("highest"):
        logits, _ = model.apply({"params": params}, jnp.asarray(tokens))
    ref, records = reference_lfm2moe.forward(params, tokens[0],
                                             _section(model.cfg))
    assert len(records) == 4
    assert float(jnp.abs(logits[0] - ref).max()) < 1e-4 * float(ref.std())


@pytest.mark.parametrize("prompt_len", [2, 21, 32])
def test_engine_logits_against_the_reference(tiny, prompt_len):
    """Prefill (a prompt shorter than the convolution's window, one
    inside its bucket, one that fills it) into the packed pool and the
    slot state, then decode, as the benchmark's check drives the
    engine: its own step programs handing out logits and the experts
    they chose, every slot live, the request in a slot another has
    left. Every control of the reference fails that comparison."""
    eng = _engine(tiny)
    try:
        prompt = np.random.default_rng(prompt_len).integers(1, 256,
                                                            prompt_len)
        answer = eng.generate_sync(prompt, max_new_tokens=6)
        with jax.default_matmul_precision("highest"):
            out = replica_lfm2moe.serve_check(eng, {
                "model": _section(tiny[0].cfg), "prompt": prompt.tolist(),
                "generated": answer, "check": TIGHT,
                "controls": list(CONTROLS)})
        assert not {"_dispatch_prefill", "_dispatch_decode",
                    "_apply_counted"} & set(vars(eng))
        assert eng.model is tiny[0]
        assert len(eng._free_slots) == 3 and not eng._active
        pages = eng._pages.kv_pages()
        assert pages["free"] == pages["total"] and not pages["in_use"]
        assert eng.generate_sync(prompt, max_new_tokens=6) == answer
        stats = eng.get_stats()
    finally:
        eng.shutdown()
    assert out["ok"], out
    assert out["positions"] == prompt_len + 10 and out["new_tokens"] == 11
    assert out["not_followed"] == 0 and out["same_experts_pair_share"] > 0.9
    assert (out["slots"], out["requests_beside"]) == (3, 6)
    assert out["tokens_as_idle"] and out["tokens_with_logits_as_timed"]
    passed = [n for n, c in out["controls"].items() if c["ok"]]
    # running the convolution to the bucket's end is wrong exactly
    # where there is padding to run over
    assert passed == (["conv_state_to_bucket_end"] if prompt_len == 32
                      else []), passed
    # counters of both kinds in one model: the four conv layers' state
    # rows and the four expert layers' assignments
    assert stats["kv_bytes_per_token"] == 2 * 128 * 4
    assert stats["state_bytes_per_slot"] == 4 * 2 * 64 * 4
    assert 0 < stats["decode_state_rows_live"] \
        <= stats["decode_state_rows_window"]
    assert stats["moe_assignments"] == 2 * stats["moe_rows"] > 0
    assert stats["moe_routed_assignments"] == stats["moe_assignments"]


def test_a_used_slot_answers_as_a_fresh_engine(tiny, prompts,
                                               one_at_a_time):
    """Seven requests through three slots: every slot is taken again
    after another sequence left its conv state there; tokens equal
    one-at-a-time."""
    eng = _engine(tiny)
    try:
        rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
        got = [list(eng.stream(r)) for r in rids]
    finally:
        eng.shutdown()
    assert got == one_at_a_time


@pytest.mark.parametrize("chunk", [16, 8])
def test_chunked_prefill_carries_the_convolution(tiny, prompts,
                                                 one_at_a_time, chunk):
    eng = _engine(tiny, prefill_chunk=chunk)
    try:
        rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
        got = [list(eng.stream(r)) for r in rids]
        assert eng._chunk_paged_jit._cache_size() > 0
    finally:
        eng.shutdown()
    assert got == one_at_a_time


def test_an_idle_rows_conv_state_is_unchanged_by_a_decode_step(tiny):
    """One request decodes in one of three slots: the other rows of
    every conv layer's pool, the scratch row too, hold what they held
    (a second request's prefill wrote the scratch row, not a step)."""
    eng = _engine(tiny, max_prefill_batch=1)
    try:
        eng.generate_sync(np.arange(1, 20), max_new_tokens=4)
        conv = [i for i, c in enumerate(eng._pages.spec) if c.by_slot]
        before = [np.asarray(eng._pools[i][0]) for i in conv]
        eng.generate_sync(np.arange(5, 30), max_new_tokens=9)
        after = [np.asarray(eng._pools[i][0]) for i in conv]
    finally:
        eng.shutdown()
    changed = [sorted(set(np.nonzero((a != b).any(1))[0]))
               for a, b in zip(before, after)]
    # exactly one row a layer moved: the slot the second request took
    assert all(len(rows) == 1 for rows in changed), changed
    assert len({rows[0] for rows in changed}) == 1


def test_causal_conv_is_one_function_for_both_layer_kinds():
    """With and without its activation; the tail is the last K - 1
    inputs at each row's true length, and a row with no real token
    keeps its own."""
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.normal(size=(2, 6, 4)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 4)), jnp.float32)
    tail = jnp.asarray(rng.normal(size=(2, 2, 4)), jnp.float32)
    # the tail a row a sequence: its two inputs side by side
    plain, t1 = causal_conv(u, w, tail.reshape(2, 8), jnp.asarray([4, 0]),
                            activation=None)
    act, t2 = causal_conv(u, w, tail.reshape(2, 8), jnp.asarray([4, 0]))
    cat = np.concatenate([tail, u], axis=1)
    want = sum(np.asarray(w)[j] * cat[:, 2 - j:8 - j] for j in range(3))
    np.testing.assert_allclose(plain, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(act, jax.nn.silu(want), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(t1, t2)
    np.testing.assert_array_equal(t1[0], u[0, 2:4].reshape(8))
    np.testing.assert_array_equal(t1[1], tail[1].reshape(8))


def test_routing_selects_by_score_plus_bias_and_weighs_by_score():
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]])
    bias = jnp.asarray([0.0, -1.0, 0.0, 0.6])
    s = np.asarray(jax.nn.sigmoid(logits))[0]
    w, idx = moe.route(logits, 2, "sigmoid_bias", True, select_bias=bias,
                       scale=1.0, norm_eps=1e-6)
    # without the bias experts 0 and 1; with it 0 and 3
    assert sorted(np.asarray(idx)[0].tolist()) == [0, 3]
    order = np.asarray(idx)[0]
    np.testing.assert_allclose(
        np.asarray(w)[0], s[order] / (s[0] + s[3] + 1e-6), rtol=1e-6)
    w0, idx0 = moe.route(logits, 2, "sigmoid_bias", True)
    assert sorted(np.asarray(idx0)[0].tolist()) == [0, 1]
    np.testing.assert_allclose(np.asarray(w0).sum(), 1.0, rtol=1e-6)


@pytest.mark.parametrize("hq,hkv,d", [(8, 4, 64), (4, 2, 16), (6, 3, 32),
                                      (4, 4, 128)])
@pytest.mark.parametrize("impl", ["gather", "pallas"])
def test_a_packed_pool_answers_as_an_unpacked_one(monkeypatch, hq, hkv, d,
                                                  impl):
    """A prompt's positions and three decode steps through
    `paged_cached_attention` over a pool of `packed_kv_shape` rows (the
    gather route, and the kernel interpreted) against a pool of a head a
    row through the gather route; heads of 128 lie a head a row in
    both."""
    def run(packed, impl):
        monkeypatch.setenv("RAY_TPU_PAGED_ATTN_IMPL", impl)
        rng = np.random.default_rng(0)
        b, ps, pages, n0 = 3, 8, 4, 13
        shape = packed_kv_shape(hkv, d) if packed else (hkv, d)
        pool = jnp.zeros(((b * pages + 1) * ps, *shape), jnp.float32)
        table = jnp.asarray(rng.permutation(b * pages).reshape(b, pages),
                            jnp.int32)
        cache = PagedKV(pool, pool, table, jnp.zeros((b,), jnp.int32), ps)
        outs = []
        for s, start in [(n0, 0), (1, n0), (1, n0 + 1), (1, n0 + 2)]:
            q, k, v = (jnp.asarray(rng.normal(size=(b, s, h, d)),
                                   jnp.float32) for h in (hq, hkv, hkv))
            pos = jnp.broadcast_to(start + jnp.arange(s)[None], (b, s))
            out, cache = paged_cached_attention(q, k, v, cache, pos)
            outs.append(out)
        return outs, cache.k_flat.shape
    want, _ = run(False, "gather")
    got, pool_shape = run(True, impl)
    assert pool_shape[1:] == packed_kv_shape(hkv, d)
    for a, b in zip(want, got):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-6)
