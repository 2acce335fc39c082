"""n-gram (prompt-lookup) speculative decoding: token-identical greedy
output with multi-token emission per dispatch (engine.ngram_speculation).
Reference: the draft-free speculation family the fork's vLLM-style
serving path targets (prompt-lookup / n-gram speculation)."""
import numpy as np
import pytest

import jax

from ray_tpu.models import Llama, LlamaConfig
from ray_tpu.serve.llm import LLMEngine, LLMEngineConfig

EOS = 0


@pytest.fixture(scope="module")
def model_params():
    cfg = LlamaConfig(vocab_size=128, d_model=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_ff=128, max_seq_len=160)
    model = Llama(cfg)
    return model, model.init_params(jax.random.PRNGKey(0))


def make_engine(model_params, spec=0, **kw):
    model, params = model_params
    base = dict(max_slots=4, max_seq_len=160, prefill_buckets=(16, 32),
                eos_token_id=EOS, ngram_speculation=spec)
    base.update(kw)
    return LLMEngine(model, params, LLMEngineConfig(**base))


# a prompt with strong bigram structure so lookups actually hit
REPETITIVE = np.array([5, 6, 7, 8, 5, 6, 7, 8, 5, 6, 7, 8, 5, 6, 7, 8],
                      np.int32)
PLAIN = np.arange(1, 13)


def _baseline(model_params, prompt, n, **kw):
    eng = make_engine(model_params, spec=0, **kw)
    try:
        return eng.generate_sync(prompt, max_new_tokens=n)
    finally:
        eng.shutdown()


@pytest.mark.parametrize("kv", [
    pytest.param({}, id="default_pool"),
    pytest.param({"kv_page_size": 16, "kv_pool_tokens": 1024},
                 id="page16_pool1024", marks=pytest.mark.slow),
])
def test_spec_token_identical(model_params, kv):
    want = _baseline(model_params, REPETITIVE, 24, **kv)
    eng = make_engine(model_params, spec=4, **kv)
    try:
        got = eng.generate_sync(REPETITIVE, max_new_tokens=24)
        assert got == want, (got, want)
        st = eng.get_stats()
        # timing-independent correctness: speculation engaged (token
        # identity asserted above); the dispatch-count payoff bound is
        # load-sensitive and lives in the slow/perf-marked test below
        assert st.get("spec_steps", 0) > 0
    finally:
        eng.shutdown()


@pytest.mark.slow
def test_spec_fewer_dispatches_than_tokens(model_params):
    """Perf property: speculation must actually pay — fewer decode
    dispatches than emitted tokens. Dispatch counts wobble under CI
    load (the host loop may drain conservatively), so this bound is
    perf-marked and kept out of the fast suite."""
    eng = make_engine(model_params, spec=4)
    try:
        eng.generate_sync(REPETITIVE, max_new_tokens=24)
        st = eng.get_stats()
        assert st.get("spec_steps", 0) > 0
        assert st["decode_steps"] < 24, st
    finally:
        eng.shutdown()


def test_spec_nonrepetitive_still_identical(model_params):
    """Plain prompts (few lookup hits) must stay correct too."""
    want = _baseline(model_params, PLAIN, 16)
    eng = make_engine(model_params, spec=4)
    try:
        got = eng.generate_sync(PLAIN, max_new_tokens=16)
        assert got == want, (got, want)
    finally:
        eng.shutdown()


def test_spec_concurrent_and_mixed_sampling(model_params):
    """Greedy speculating requests and a sampled (non-spec) request
    decode concurrently; each greedy output matches the non-spec
    engine."""
    wants = [_baseline(model_params, REPETITIVE + i, 16)
             for i in range(2)]
    eng = make_engine(model_params, spec=4)
    try:
        rids = [eng.submit(REPETITIVE + i, max_new_tokens=16)
                for i in range(2)]
        rid_s = eng.submit(PLAIN, max_new_tokens=12, temperature=0.8)
        outs = [list(eng.stream(r)) for r in rids]
        sampled = list(eng.stream(rid_s))
        for got, want in zip(outs, wants):
            assert got == want, (got, want)
        assert len(sampled) <= 12 and len(sampled) >= 1
    finally:
        eng.shutdown()


def test_spec_stop_token_mid_acceptance(model_params):
    """A stop token appearing inside an accepted run truncates the
    output exactly like plain decode."""
    want = _baseline(model_params, REPETITIVE, 20)
    stop = want[len(want) // 2]
    cut = want.index(stop) + 1
    eng = make_engine(model_params, spec=4)
    try:
        got = eng.generate_sync(REPETITIVE, max_new_tokens=20,
                                stop_token_ids=[stop])
        assert got == want[:cut], (got, want[:cut])
    finally:
        eng.shutdown()


def test_spec_near_max_seq_len(model_params):
    """Slots too close to max_seq_len veto the verify step (which
    writes K+1 positions); output still completes correctly."""
    want = _baseline(model_params, REPETITIVE, 20, max_seq_len=40)
    eng = make_engine(model_params, spec=4, max_seq_len=40)
    try:
        got = eng.generate_sync(REPETITIVE, max_new_tokens=20)
        assert got == want, (got, want)
    finally:
        eng.shutdown()


def test_spec_with_guided_coexists(model_params):
    """Guided requests (ineligible for speculation) work in a
    spec-enabled engine, and a concurrent spec request stays exact."""
    from ray_tpu.serve.llm import TokenFSM
    want = _baseline(model_params, REPETITIVE, 12)
    eng = make_engine(model_params, spec=4)
    try:
        fsm = TokenFSM.from_choices([[11, 12, 13]], vocab_size=128,
                                    eos_id=EOS)
        rid_g = eng.submit(PLAIN, max_new_tokens=6, guided_fsm=fsm)
        rid_s = eng.submit(REPETITIVE, max_new_tokens=12)
        got_g = [t for t in eng.stream(rid_g) if t != EOS]
        got_s = list(eng.stream(rid_s))
        assert got_g == [11, 12, 13]
        assert got_s == want, (got_s, want)
    finally:
        eng.shutdown()
