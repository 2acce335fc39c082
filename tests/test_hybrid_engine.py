"""The tiny hybrid decoder (models/hybrid.py: Gated DeltaNet layers with
a recurrent state a slot, full-attention layers over pages) through
LLMEngine: against the plain reference, through used slots, chunked,
with finishes while steps are in flight; what a slot-state model
refuses; and what it counts."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import get_model
from ray_tpu.ops.attention import PagedKV, SlotState, kv_cache_spec
from ray_tpu.serve.llm.engine import LLMEngine, LLMEngineConfig

BUCKETS = (16, 32, 64)


@pytest.fixture(scope="module")
def tiny():
    model = get_model("hybrid-debug", param_dtype=jnp.float32,
                      dtype=jnp.float32)
    params = model.init_params(jax.random.PRNGKey(0))
    # norm weights and gates off their initial ones and zeros
    params = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(
            jax.random.PRNGKey(a.size), a.shape, a.dtype)
        if a.ndim == 1 else a, params)
    return model, params


def _engine(tiny, **kw):
    model, params = tiny
    return LLMEngine(model, params, LLMEngineConfig(**{**dict(
        max_slots=3, max_seq_len=128, prefill_buckets=BUCKETS,
        kv_page_size=8, pipeline_depth=3, max_prefill_batch=2), **kw}))


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    return [rng.integers(1, 256, n) for n in (5, 19, 33, 21, 40, 16, 9)]


@pytest.fixture(scope="module")
def one_at_a_time(tiny, prompts):
    """Every prompt's 6 greedy tokens, one request at a time."""
    eng = _engine(tiny)
    try:
        return [eng.generate_sync(p, max_new_tokens=6) for p in prompts]
    finally:
        eng.shutdown()


def _section(cfg):
    return dict(
        hidden_size=cfg.d_model, num_hidden_layers=cfg.n_layers,
        num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, intermediate_size=cfg.d_ff,
        vocab_size=cfg.vocab_size, rms_norm_eps=cfg.norm_eps,
        layer_types=list(cfg.layer_types),
        linear_num_key_heads=cfg.linear_n_heads,
        linear_num_value_heads=cfg.linear_n_heads,
        linear_key_head_dim=cfg.linear_key_dim,
        linear_value_head_dim=cfg.linear_value_dim,
        linear_conv_kernel_dim=cfg.linear_conv_kernel,
        linear_allow_neg_eigval=cfg.linear_allow_neg_eigval)


def test_the_cache_is_declared_a_layer(tiny):
    model, _ = tiny
    spec = kv_cache_spec(model)
    assert [c.by_slot for c in spec] == [True, True, True, False]
    assert [c.entry for c in spec] == [SlotState] * 3 + [PagedKV]
    state, tail = spec[0].shapes
    # the tail a row a slot: K - 1 = 3 inputs of q | k | v side by side
    assert state == (8, 4 * 16) and tail == (3 * 4 * (8 + 8 + 16),)
    assert spec[0].dtypes[0] == jnp.float32
    big = kv_cache_spec(get_model("olmo-hybrid-7b", n_layers=4))
    assert big[0].shapes == ((96, 5760), (3 * 11520,))
    # 30 KV heads fill no whole 8-row tile: the pool is laid out for 32
    assert big[3].shapes == ((32, 128), (32, 128))


@pytest.mark.parametrize("prompt_len", [21, 32])
def test_engine_logits_against_the_reference(tiny, prompt_len):
    """Prefill (a prompt inside its bucket, and one that fills it) into
    pages and slot state, then decode, as the benchmark's check drives
    the engine: the engine's own step programs handing out their logits,
    on its own pools, the request between six others through slots they
    have left and with every slot live, more steps in flight than it
    has tokens left; logits of every position against the plain float32
    reference."""
    from benchmarks.harness import replica_olmohybrid
    eng = _engine(tiny)
    try:
        prompt = np.random.default_rng(prompt_len).integers(1, 256,
                                                            prompt_len)
        answer = eng.generate_sync(prompt, max_new_tokens=6)
        with jax.default_matmul_precision("highest"):
            out = replica_olmohybrid.serve_check(eng, {
                "model": _section(tiny[0].cfg), "prompt": prompt.tolist(),
                "generated": answer,
                "check": {"logit_tol_rel": 1e-3, "logit_mean_tol_rel": 1e-3,
                          "logit_decode_mean_tol_rel": 1e-3,
                          "argmax_tol_rel": 1e-3, "busy_new_tokens": 11},
                "controls": ["state_to_bucket_end", "beta_without_2"]})
        # the engine is left as it was found: its own dispatch, every
        # slot and page free again
        assert not {"_dispatch_prefill", "_dispatch_decode",
                    "_apply_counted"} & set(vars(eng))
        assert len(eng._free_slots) == 3 and not eng._active
        pages = eng._pages.kv_pages()
        assert pages["free"] == pages["total"] and not pages["in_use"]
        assert eng.generate_sync(prompt, max_new_tokens=6) == answer
    finally:
        eng.shutdown()
    assert out["ok"], out
    assert out["positions"] == prompt_len + 10
    assert out["tokens_as_idle"] and out["new_tokens"] == 11
    assert out["tokens_with_logits_as_timed"]
    assert (out["slots"], out["requests_beside"]) == (3, 6)
    assert out["prefill_bucket"] == 32
    assert not out["controls"]["beta_without_2"]["ok"]
    # running the state to the bucket's end is wrong exactly where
    # there is padding to run over
    assert out["controls"]["state_to_bucket_end"]["ok"] \
        is (prompt_len == 32)


def test_a_used_slot_answers_as_a_fresh_engine(tiny, prompts,
                                               one_at_a_time):
    """Seven requests through three slots: every slot is taken again
    after another sequence left its state there, and while
    pipeline_depth steps are in flight; tokens equal one-at-a-time."""
    eng = _engine(tiny)
    try:
        rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
        got = [list(eng.stream(r)) for r in rids]
        stats = eng.get_stats()
    finally:
        eng.shutdown()
    assert got == one_at_a_time
    # K and V of the one full layer, its 4 heads laid out as 8
    assert stats["kv_bytes_per_token"] == 2 * 8 * 16 * 4
    assert stats["state_bytes_per_slot"] == 3 * (8 * 64 * 4 + 3 * 128 * 4)
    assert 0 < stats["decode_state_rows_live"] \
        <= stats["decode_state_rows_window"]
    # a dispatch's window is every row of the pool (3 slots and the
    # scratch slot) in every linear layer; the last dispatches may not
    # have been drained into decode_steps
    assert stats["decode_state_rows_window"] % (4 * 3) == 0
    assert stats["decode_state_rows_window"] >= \
        stats["decode_steps"] * 4 * 3


@pytest.mark.parametrize("chunk", [16, 8])
def test_chunked_prefill_is_whole_prefill(tiny, prompts, one_at_a_time,
                                          chunk):
    eng = _engine(tiny, prefill_chunk=chunk)
    try:
        rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
        got = [list(eng.stream(r)) for r in rids]
        assert eng._chunk_paged_jit._cache_size() > 0
    finally:
        eng.shutdown()
    assert got == one_at_a_time


def test_finishes_while_steps_are_in_flight(tiny, prompts, one_at_a_time):
    """Answers of different lengths, so that rows leave and slots are
    refilled at every depth of the pipeline."""
    eng = _engine(tiny, pipeline_depth=4)
    try:
        lens = [1, 6, 3, 2, 5, 4, 6]
        rids = [eng.submit(p, max_new_tokens=n)
                for p, n in zip(prompts, lens)]
        got = [list(eng.stream(r)) for r in rids]
    finally:
        eng.shutdown()
    assert got == [w[:n] for w, n in zip(one_at_a_time, lens)]


@pytest.mark.parametrize("kw,match", [
    (dict(max_prefixes=2), "prefix caching.*state snapshot"),
    (dict(ngram_speculation=3), "speculation.*rolled back")])
def test_what_a_slot_state_model_refuses_at_construction(tiny, kw, match):
    with pytest.raises(ValueError, match=match):
        _engine(tiny, **kw)


def test_a_model_that_pages_no_layer_is_refused_by_name():
    model = get_model("hybrid-debug", layer_types=("linear_attention",) * 4,
                      param_dtype=jnp.float32, dtype=jnp.float32)
    with pytest.raises(ValueError, match="none\\s+pages"):
        _engine((model, model.init_params(jax.random.PRNGKey(0))))


def test_register_prefix_is_refused_by_name(tiny):
    eng = _engine(tiny)
    try:
        with pytest.raises(ValueError, match="register_prefix.*snapshot"):
            eng.register_prefix([1, 2, 3])
    finally:
        eng.shutdown()


def test_precompile_leaves_nothing_to_compile(tiny):
    """Two buckets and one decode window (two pages a slot): what
    precompile ran is what requests run, entry classes, static flags
    and absent fields included."""
    eng = _engine(tiny, kv_page_size=16, max_seq_len=32,
                  prefill_buckets=(16, 32), max_prefill_batch=1,
                  precompile=True)
    try:
        jits = (eng._prefill_paged_jit, eng._decode_paged_jit)
        before = [j._cache_size() for j in jits]
        assert before == [2, 1]
        for n in (16, 20, 30):
            assert len(eng.generate_sync(np.ones((n,), np.int32),
                                         max_new_tokens=2)) == 2
        assert [j._cache_size() for j in jits] == before
    finally:
        eng.shutdown()


def test_a_model_without_slot_state_counts_and_calls_as_before():
    """No per-slot pool, no state counters, and the page pools as they
    were: one K and one V a layer."""
    model = get_model("llama-debug", param_dtype=jnp.float32,
                      dtype=jnp.float32)
    eng = LLMEngine(model, model.init_params(jax.random.PRNGKey(0)),
                    LLMEngineConfig(max_slots=2, max_seq_len=64,
                                    prefill_buckets=(16,), kv_page_size=8))
    try:
        eng.generate_sync(np.ones((5,), np.int32), max_new_tokens=3)
        stats = eng.get_stats()
    finally:
        eng.shutdown()
    assert stats["state_bytes_per_slot"] == 0
    assert "decode_state_rows_live" not in stats
    assert "decode_state_rows_window" not in stats
    assert all(len(layer) == 2 and layer[0].shape[0] == (2 * 8 + 1) * 8
               for layer in eng._pools)
