"""Sampling penalties + logit_bias (OpenAI presence/frequency semantics,
vLLM parity): device-resident per-slot token counts update in-jit from
last_tokens, so penalties cost no host round-trip and keep pipelining."""
import numpy as np
import pytest

import jax

from ray_tpu.models import Llama, LlamaConfig
from ray_tpu.serve.llm import LLMEngine, LLMEngineConfig

EOS = 0


@pytest.fixture(scope="module")
def model_params():
    cfg = LlamaConfig(vocab_size=128, d_model=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_ff=128, max_seq_len=128)
    model = Llama(cfg)
    return model, model.init_params(jax.random.PRNGKey(0))


def make_engine(model_params, **kw):
    model, params = model_params
    base = dict(max_slots=4, max_seq_len=128, prefill_buckets=(16, 32),
                eos_token_id=EOS)
    base.update(kw)
    return LLMEngine(model, params, LLMEngineConfig(**base))


PROMPT = np.arange(1, 9)


def test_logit_bias_forces_and_blocks(model_params):
    eng = make_engine(model_params)
    try:
        plain = eng.generate_sync(PROMPT, max_new_tokens=6)
        # +1e4 on one token makes greedy pick it every step
        forced = eng.generate_sync(PROMPT, max_new_tokens=6,
                                   logit_bias={77: 1e4})
        assert forced == [77] * 6
        # -1e4 on the plain path's first token changes the output
        blocked = eng.generate_sync(PROMPT, max_new_tokens=6,
                                    logit_bias={plain[0]: -1e4})
        assert blocked[0] != plain[0]
    finally:
        eng.shutdown()


def _margins_of_77(model_params, n):
    """From the model's plain forward (no engine, no cache): by how much
    the best other token leads token 77 at each of n greedy steps, (a)
    on the path where 77 is emitted every step and (b) on the path where
    77 is emitted once and never again."""
    model, params = model_params

    def logits_after(history):
        toks = np.asarray(history, np.int32)[None, :]
        out, _ = model.apply({"params": params}, toks)
        return np.array(out[0, -1], np.float32)

    def lead(logits):
        others = np.delete(logits, 77)
        return float(others.max() - logits[77])

    forced = [lead(logits_after(list(PROMPT) + [77] * k))
              for k in range(n)]
    once, hist = [], list(PROMPT) + [77]
    for _ in range(n - 1):
        lg = logits_after(hist)
        once.append(lead(lg))
        lg[77] = -np.inf
        hist.append(int(lg.argmax()))
    return forced, once


def test_presence_penalty_breaks_repetition(model_params):
    """The bias on token 77 is calibrated on this fixture's own logits,
    whatever build made the weights: large enough that greedy emits 77
    at every step of a 5-token budget without a penalty, and less than
    presence_penalty 2.0 (the OpenAI cap) above what 77 needs at the
    steps after its first emission, so that the penalty must then allow
    77 exactly once and suppress it for the rest of the budget."""
    n = 5
    forced, once = _margins_of_77(model_params, n)
    lo, hi = max(forced), min(once) + 2.0
    # room for the bf16 rounding between the plain forward and the
    # engine's padded, paged programs
    assert hi - lo > 0.25, (forced, once)
    bias = {77: (lo + hi) / 2}
    eng = make_engine(model_params)
    try:
        rep = eng.generate_sync(PROMPT, max_new_tokens=n, logit_bias=bias)
        assert rep == [77] * n  # calibration precondition
        pen = eng.generate_sync(PROMPT, max_new_tokens=n, logit_bias=bias,
                                presence_penalty=2.0)
        assert pen[0] == 77          # first emission unaffected
        assert pen.count(77) == 1    # counted once -> suppressed after
    finally:
        eng.shutdown()


def test_frequency_penalty_reduces_repeats(model_params):
    eng = make_engine(model_params)
    try:
        plain = eng.generate_sync(PROMPT, max_new_tokens=16)
        pen = eng.generate_sync(PROMPT, max_new_tokens=16,
                                frequency_penalty=2.0)
        def max_run(xs):
            best = run = 1
            for a, b in zip(xs, xs[1:]):
                run = run + 1 if a == b else 1
                best = max(best, run)
            return best
        # frequency penalty can only reduce the longest repeat run
        assert max_run(pen) <= max(max_run(plain), 2)
    finally:
        eng.shutdown()


def test_penalties_paged_and_concurrent(model_params):
    """Penalties work over the paged KV cache with concurrent requests
    (per-slot counts stay independent)."""
    eng = make_engine(model_params, kv_page_size=16, kv_pool_tokens=512)
    try:
        rid_a = eng.submit(PROMPT, max_new_tokens=6,
                           logit_bias={77: 1e4})
        rid_b = eng.submit(PROMPT + 1, max_new_tokens=6,
                           logit_bias={88: 1e4})
        a = list(eng.stream(rid_a))
        b = list(eng.stream(rid_b))
        assert a == [77] * 6 and b == [88] * 6
    finally:
        eng.shutdown()


def test_penalties_do_not_leak_across_slot_reuse(model_params):
    """A later request reusing the slot of a penalized one starts with
    fresh counts/bias (seeding is per assignment)."""
    eng = make_engine(model_params, max_slots=1)
    try:
        eng.generate_sync(PROMPT, max_new_tokens=4, logit_bias={77: 1e4})
        plain = eng.generate_sync(PROMPT, max_new_tokens=4)
        assert plain != [77] * 4
    finally:
        eng.shutdown()


def test_penalty_validation(model_params):
    eng = make_engine(model_params)
    try:
        with pytest.raises(ValueError, match="penalties"):
            eng.submit(PROMPT, presence_penalty=3.0)
    finally:
        eng.shutdown()


def test_penalties_with_guided_mask(model_params):
    """Guided mask + logit_bias compose: output stays in the language
    regardless of bias."""
    from ray_tpu.serve.llm import TokenFSM
    eng = make_engine(model_params)
    try:
        fsm = TokenFSM.from_choices([[11, 12], [21, 22]], vocab_size=128,
                                    eos_id=EOS)
        out = eng.generate_sync(PROMPT, max_new_tokens=6,
                                guided_fsm=fsm, logit_bias={21: 1e4})
        got = [t for t in out if t != EOS]
        assert got == [21, 22]  # bias steers WITHIN the language
    finally:
        eng.shutdown()


def test_release_completes_before_stream_end_under_churn(model_params):
    """Soak regression (mixed guided/spec/abort traffic): _release must
    finish ALL slot bookkeeping before publishing the end marker. The
    old order put _END first, and the jax dispatch inside
    _free_slot_pages dropped the GIL mid-cleanup — so a consumer woken
    by _END could observe a finished "pen" request still sitting in
    _active (its slot simultaneously in _free_slots), and state built
    from that view (penalty coefficient rows, masks) went stale. Pin:
    the moment generate_sync returns, the request is fully released."""
    import threading

    eng = make_engine(model_params, max_slots=3, kv_page_size=16,
                      kv_pool_tokens=512, ngram_speculation=4)
    try:
        stop = threading.Event()

        def churn():
            # repetitive prompts keep the speculation path hot while
            # short budgets force constant slot turnover
            rep = np.tile(np.array([5, 6, 7, 8]), 4)
            while not stop.is_set():
                rid = eng.submit(rep, max_new_tokens=3)
                for _ in eng.stream(rid):
                    pass

        t = threading.Thread(target=churn, daemon=True)
        t.start()
        try:
            for _ in range(15):
                rid = eng.submit(PROMPT, max_new_tokens=4,
                                 logit_bias={77: 2.5},
                                 presence_penalty=2.0)
                out = list(eng.stream(rid))
                assert out.count(77) <= 2, out
                # release-before-end-marker: no finished request may
                # still occupy a slot once its stream has ended
                stuck = [r.request_id for r in
                         list(eng._active.values())
                         if r.request_id == rid]
                assert not stuck, stuck
        finally:
            stop.set()
            t.join(timeout=60)
    finally:
        eng.shutdown()
