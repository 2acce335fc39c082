"""Paged KV cache of the serve engine.

The engine keeps KV in a shared page pool + per-slot page tables
(ops/attention.py:paged_cached_attention — static shapes, one decode
program per page window). These tests pin: token-identical output vs a
plain greedy loop over the model's own (k, v, lengths) cache, >2x
concurrent sequences in the KV budget of max_slots x max_seq_len with
mixed-length requests, and page-pool stats. Prefix caching runs ON
pages: full pages shared by reference, only the partial tail page
copied.
"""
import threading
import time

import numpy as np
import pytest


@pytest.fixture(scope="module")
def tiny_llm():
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import Llama, LlamaConfig
    cfg = LlamaConfig(vocab_size=128, d_model=32, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_ff=64, max_seq_len=256, remat=False,
                      dtype=jnp.float32)
    model = Llama(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    return model, params


def _engine(tiny_llm, **overrides):
    from ray_tpu.serve.llm import LLMEngine, LLMEngineConfig
    model, params = tiny_llm
    base = dict(max_slots=4, max_seq_len=128, prefill_buckets=(16, 32),
                max_prefill_batch=1)
    base.update(overrides)
    return LLMEngine(model, params, LLMEngineConfig(**base))


def _model_cache_greedy(tiny_llm, prompt, n_new, max_len=128):
    """The reference, independent of the engine: a greedy loop over the
    model's own cache (Model.empty_cache + cached_attention), one
    sequence, the prompt unpadded and in one pass."""
    import jax.numpy as jnp
    model, params = tiny_llm
    cache = model.empty_cache(1, max_len, dtype=jnp.float32)
    toks = jnp.asarray(prompt, jnp.int32)[None, :]
    pos = jnp.arange(toks.shape[1])[None, :]
    out = []
    for _ in range(n_new):
        logits, cache = model.apply({"params": params}, toks, cache=cache,
                                    positions=pos)
        out.append(int(jnp.argmax(logits[0, -1])))
        toks = jnp.asarray([[out[-1]]], jnp.int32)
        pos = pos[:, -1:] + 1
    return out


def test_paged_tokens_identical_to_model_cache(tiny_llm):
    """Same prompts, greedy: the engine must emit token-for-token what
    a plain loop over the model's own cache emits (attention math is
    identical after the page gather)."""
    prompts = [np.arange(1 + i, 6 + i * 3) % 128 for i in range(5)]
    want = [_model_cache_greedy(tiny_llm, p, 8) for p in prompts]
    paged = _engine(tiny_llm, kv_page_size=16)
    got = [paged.generate_sync(p, max_new_tokens=8) for p in prompts]
    stats = paged.get_stats()
    paged.shutdown()
    assert got == want
    assert stats["kv_pages"]["page_size"] == 16
    assert stats["kv_pages"]["free"] == stats["kv_pages"]["total"]


def test_paged_concurrent_interleaved(tiny_llm):
    """Concurrent mixed-length requests through the continuous-batching
    loop produce the same tokens as sequential runs."""
    prompts = [np.arange(2, 2 + n) % 128 for n in (3, 9, 14, 5, 11, 7)]
    eng = _engine(tiny_llm, kv_page_size=16, max_slots=4)
    want = [eng.generate_sync(p, max_new_tokens=6) for p in prompts]
    rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
    got = [list(eng.stream(r)) for r in rids]
    eng.shutdown()
    assert got == want


def test_paged_over2x_concurrency_same_budget(tiny_llm):
    """A KV token budget must hold >2x the sequences that per-slot
    max_seq_len reservations would: 512 tokens are 4 x max_seq_len 128.
    In a 512-token pool of 16-token pages a 16-token short request
    reserves 1 page, so 16+ can hold slots."""
    eng = _engine(tiny_llm, kv_page_size=16, max_slots=16,
                  kv_pool_tokens=512, max_new_tokens_default=8)
    n_req = 16
    starts = threading.Barrier(n_req + 1)
    peak = []

    def one(i):
        rid = eng.submit(np.arange(2, 10) % 128, max_new_tokens=8)
        starts.wait()
        toks = list(eng.stream(rid))
        assert len(toks) == 8

    threads = [threading.Thread(target=one, args=(i,))
               for i in range(n_req)]
    for t in threads:
        t.start()
    starts.wait()
    t_end = time.time() + 10
    while time.time() < t_end:
        peak.append(eng.get_stats()["active"])
        if not any(t.is_alive() for t in threads):
            break
        time.sleep(0.005)
    for t in threads:
        t.join()
    stats = eng.get_stats()
    eng.shutdown()
    # 8-token prompt + 8-token budget = 1 page each: all 16 fit at once
    # in a budget of only 4 x max_seq_len (>2x = assert >8)
    assert max(peak) > 8, f"peak concurrency {max(peak)}"
    assert stats["kv_pages"]["peak_in_use"] <= stats["kv_pages"]["total"]
    assert stats["kv_pages"]["free"] == stats["kv_pages"]["total"]


def test_paged_admission_waits_for_pages_not_slots(tiny_llm):
    """With plenty of slots but a tiny pool, admission is gated by free
    pages; requests queue and complete as pages free up."""
    eng = _engine(tiny_llm, kv_page_size=16, max_slots=8,
                  kv_pool_tokens=128)  # 8 pages
    # each needs ceil((8+24)/16) = 2 pages -> only 4 fit concurrently
    rids = [eng.submit(np.arange(2, 10) % 128, max_new_tokens=24)
            for _ in range(8)]
    outs = [list(eng.stream(r)) for r in rids]
    stats = eng.get_stats()
    eng.shutdown()
    assert all(len(t) == 24 for t in outs)
    assert stats["kv_pages"]["peak_in_use"] <= 8
    assert stats["kv_pages"]["free"] == stats["kv_pages"]["total"]


def test_paged_prefix_shares_pages(tiny_llm):
    """A registered prefix pins its pages once; adopters share the full
    pages by reference (no full-length dedicated buffers) and generate
    the same tokens as re-prefilling the whole prompt."""
    prefix = (np.arange(2, 2 + 40) % 128)   # 40 tokens: 2.5 pages
    suffix = (np.arange(50, 58) % 128)
    eng = _engine(tiny_llm, kv_page_size=16, max_slots=4,
                  max_prefixes=2, prefill_chunk=16)
    full = eng.generate_sync(np.concatenate([prefix, suffix]),
                             max_new_tokens=6)
    pid = eng.register_prefix(prefix)
    stats = eng.get_stats()
    assert stats["kv_pages"]["pinned_prefix"] == 3  # ceil(40/16)
    got = eng.generate_sync(suffix, max_new_tokens=6, prefix_id=pid)
    assert got == full
    # adoption saved the prefix prefill
    assert eng.stats["prefix_tokens_saved"] >= prefix.size
    # shared pages stay pinned after release; exclusive pages returned
    stats = eng.get_stats()
    assert stats["kv_pages"]["in_use"] == 3
    eng.shutdown()


def test_paged_decode_block_and_pipeline_match_model_cache(tiny_llm):
    """decode_block>1 (lax.scan fused steps) + pipelined dispatch over
    the paged cache with windowed decode: token-identical to the
    model's own cache loop."""
    prompts = [np.arange(1 + i, 7 + i * 2) % 128 for i in range(4)]
    want = [_model_cache_greedy(tiny_llm, p, 9) for p in prompts]
    paged = _engine(tiny_llm, kv_page_size=16, decode_block=3,
                    pipeline_depth=4)
    got = [paged.generate_sync(p, max_new_tokens=9) for p in prompts]
    paged.shutdown()
    assert got == want


def test_paged_chunked_prefill_matches_model_cache(tiny_llm):
    """A long prompt through chunked prefill matches the model's own
    cache loop, which takes the prompt in one pass, token-for-token."""
    prompt = np.arange(3, 3 + 30) % 128
    want = _model_cache_greedy(tiny_llm, prompt, 6)
    paged = _engine(tiny_llm, kv_page_size=16, prefill_chunk=8)
    got = paged.generate_sync(prompt, max_new_tokens=6)
    paged.shutdown()
    assert got == want


def test_paged_rejects_unservable_request(tiny_llm):
    eng = _engine(tiny_llm, kv_page_size=16, kv_pool_tokens=64)
    with pytest.raises(ValueError, match="never be admitted"):
        eng.submit(np.arange(2, 30) % 128, max_new_tokens=60)
    eng.shutdown()


def test_paged_pinned_prefix_cannot_livelock_admission(tiny_llm):
    """A request whose exclusive-page need exceeds what pinning leaves
    free must error its own stream — not park in _pending_head and
    head-of-line-block every later request forever."""
    eng = _engine(tiny_llm, kv_page_size=16, kv_pool_tokens=128,
                  max_prefixes=2)  # 8 pages
    eng.register_prefix(np.arange(2, 2 + 70) % 128)  # pins 5 pages
    # needs ceil((20+60)/16)=5 exclusive pages; only 3 can ever be free
    doomed = eng.submit(np.arange(2, 22) % 128, max_new_tokens=60)
    with pytest.raises(ValueError, match="pinned by prefixes"):
        list(eng.stream(doomed))
    # the queue keeps moving for servable requests behind it
    ok = eng.generate_sync(np.arange(2, 10) % 128, max_new_tokens=8)
    assert len(ok) == 8
    eng.shutdown()


@pytest.mark.parametrize("page", [0, -16])
def test_page_size_must_be_positive(tiny_llm, page):
    """The page pool is the engine's one KV layout: a page size <= 0
    is refused at construction."""
    with pytest.raises(ValueError, match="kv_page_size must be > 0"):
        _engine(tiny_llm, kv_page_size=page)


def test_default_config_is_paged_with_scratch_slot(tiny_llm):
    """An engine built with the default configuration keeps its KV in
    pages of 64 tokens, pool = max_slots * max_seq_len, and has the
    scratch slot behind the admissible ones."""
    from ray_tpu.serve.llm import LLMEngine, LLMEngineConfig
    model, params = tiny_llm
    eng = LLMEngine(model, params, LLMEngineConfig(
        max_seq_len=128, prefill_buckets=(16, 32)))
    try:
        pages = eng.get_stats()["kv_pages"]
        assert pages["page_size"] == 64
        assert pages["total"] == pages["free"] == 8 * 128 // 64
        assert eng._n_slots == eng.cfg.max_slots + 1
        assert eng._scratch_slot == eng.cfg.max_slots
        assert len(eng.generate_sync(np.arange(2, 10), 4)) == 4
    finally:
        eng.shutdown()


def test_the_dispatched_lengths_the_benchmark_reads_are_the_allocators(
        tiny_llm):
    """`LLMEngine._disp_len` is what benchmarks/harness/replica.py reads
    (from its sampler's thread) for the rooflines' live context: a
    live, read-only view of the page allocator's own mirror."""
    eng = _engine(tiny_llm)
    seen = []

    def dispatch_decode(*args):
        type(eng)._dispatch_decode(eng, *args)
        seen.append(dict(getattr(eng, "_disp_len", {}) or {}))
    try:
        assert dict(eng._disp_len) == {}
        slot = eng._free_slots[-1]
        eng._dispatch_decode = dispatch_decode
        eng.generate_sync(np.arange(2, 12) % 128, max_new_tokens=8)
        # the prompt's 10, and one more with every decode dispatched
        # (the loop may have dispatched past the request's last token)
        assert seen[:7] == [{slot: 11 + i} for i in range(7)]
        assert dict(eng._disp_len) == {}
        assert eng._disp_len is eng._pages.dispatched_lengths
        with pytest.raises(TypeError):
            eng._disp_len[0] = 0
    finally:
        eng.shutdown()
