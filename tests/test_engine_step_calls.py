"""The engine loop's calls into the JAX runtime.

One loop iteration enqueues one step program and starts one fetch. What
the host decides between two programs (page rows, length resets, the
active mask, temperatures, top-p) rides into the next program as one
NumPy vector; the lengths, the last tokens and the sampling key are
carried from program to program and change nowhere else. These tests pin
(a) the count of runtime calls, by the engine's own counter and by a
proxy on its `_jnp` / `_jax` handles, (b) the chain of keys and the
sampled tokens against a straight-line loop that splits eagerly, as the
host did before the programs carried the key, and (c) slot and page
reuse under a deep pipeline.
"""
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax._src import core as jax_core

from ray_tpu.models import Llama, LlamaConfig, get_model
from ray_tpu.serve.llm import LLMEngine, LLMEngineConfig


@pytest.fixture(scope="module")
def tiny_llm():
    cfg = LlamaConfig(vocab_size=128, d_model=32, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_ff=64, max_seq_len=256, remat=False,
                      dtype=jnp.float32)
    model = Llama(cfg)
    return model, model.init_params(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def tiny_latent():
    model = get_model("latent-moe-debug")
    return model, model.init_params(jax.random.PRNGKey(0))


def _engine(pair, **overrides):
    model, params = pair
    base = dict(max_slots=4, max_seq_len=128, prefill_buckets=(16, 32),
                kv_page_size=16)
    base.update(overrides)
    return LLMEngine(model, params, LLMEngineConfig(**base))


def _settle(eng, quiet_s=0.05):
    """Wait until the loop has drained everything it dispatched."""
    deadline = time.time() + 30
    last = None
    while time.time() < deadline:
        st = eng.get_stats()
        now = (st["runtime_calls"], st["decode_steps"], st["active"],
               st["waiting"], st["prefilling"])
        if now == last and not any(now[2:]):
            return st
        last = now
        time.sleep(quiet_s)
    raise AssertionError("engine did not settle")


class _Counting:
    """Stands in for a module handle of the engine (`_jnp`, `_jax`):
    counts every call made through it on the engine's thread outside a
    trace, which is an eager call into the runtime."""

    def __init__(self, obj, calls, path):
        self.__dict__.update(_obj=obj, _calls=calls, _path=path)

    def __getattr__(self, name):
        value = getattr(self._obj, name)
        if isinstance(value, type) or not (
                callable(value) or hasattr(value, "__dict__")):
            return value
        return _Counting(value, self._calls, f"{self._path}.{name}")

    def __call__(self, *args, **kw):
        if (threading.current_thread().name == "llm-engine"
                and jax_core.trace_state_clean()):
            self._calls.append(self._path)
        return self._obj(*args, **kw)


def _mixed_traffic(eng, n=12, temperature=0.0):
    """Requests of mixed lengths, more than there are slots, so that
    slots are released and taken again while others decode."""
    rng = np.random.default_rng(7)
    rids = [eng.submit(rng.integers(1, 128, int(rng.integers(3, 30))),
                       max_new_tokens=int(rng.integers(2, 14)),
                       temperature=temperature, top_p=0.9)
            for _ in range(n)]
    return [list(eng.stream(r)) for r in rids]


# (a) two runtime calls a dispatch ----------------------------------------
def test_a_dispatch_is_one_program_and_one_fetch(tiny_llm):
    eng = _engine(tiny_llm)
    try:
        _mixed_traffic(eng)                 # every shape compiles here
        before = _settle(eng)
        eager, programs = [], []
        eng._jnp = _Counting(eng._jnp, eager, "jnp")
        eng._jax = _Counting(eng._jax, eager, "jax")
        for name in ("_prefill_paged_jit", "_decode_paged_jit",
                     "_chunk_paged_jit", "_verify_paged_jit",
                     "_copy_page_jit", "_pen_seed_jit"):
            def counted(*a, _fn=getattr(eng, name), _name=name, **kw):
                programs.append(_name)
                return _fn(*a, **kw)
            setattr(eng, name, counted)
        out = _mixed_traffic(eng)
        after = _settle(eng)
    finally:
        eng.shutdown()
    assert all(out)
    calls = after["runtime_calls"] - before["runtime_calls"]
    steps = after["decode_steps"] - before["decode_steps"]
    prefills = after["prefill_calls"] - before["prefill_calls"]
    releases = after["spans"]["slot.refill"][0] \
        - before["spans"]["slot.refill"][0]
    assert steps > 20 and prefills >= 3 and releases >= 8
    # the counter: a program and a fetch for each dispatch, nothing for
    # an admission or a release
    assert calls == 2 * (steps + prefills)
    # the proxies: the programs are the dispatches, and no call went
    # through the engine's jnp / jax handles between them
    assert len(programs) == steps + prefills
    assert set(programs) == {"_prefill_paged_jit", "_decode_paged_jit"}
    assert eager == []


def test_an_eager_call_put_back_on_the_path_is_counted(tiny_llm):
    """The guard guards: a loop that uploads its mask eagerly again is
    seen by the proxy."""
    eng = _engine(tiny_llm)
    try:
        _mixed_traffic(eng, n=4)
        _settle(eng)
        eager = []
        eng._jnp = _Counting(eng._jnp, eager, "jnp")
        ctl = eng._decode_ctl

        def eager_ctl(window):
            mask, *rest = ctl(window)
            return (np.asarray(eng._jnp.asarray(mask)), *rest)
        eng._decode_ctl = eager_ctl
        _mixed_traffic(eng, n=4)
    finally:
        eng.shutdown()
    assert eager and set(eager) == {"jnp.asarray"}


# (b) the carried key is the host's old chain of splits -------------------
def _chain(n):
    key = jax.random.PRNGKey(0)
    for _ in range(n):
        key, _sub = jax.random.split(key)
    return key


def _straight_line(eng, key, prompt, n_new, slot, temperature, top_p):
    """The request alone, as the host ran it before the programs carried
    the key: an eager split for every dispatch, the prefill and decode
    programs' own arithmetic (`*_impl`) on a pool of its own."""
    params = eng.params
    S, ps = eng._n_slots, eng.cfg.kv_page_size
    pools = [tuple(jnp.zeros_like(a) for a in layer)
             for layer in eng._pools]
    n_pages = -(-(len(prompt) + n_new) // ps)
    table = np.full((S, eng._pages.pages_per_slot), eng._pages.trash_page,
                    np.int32)
    table[slot, :n_pages] = np.arange(n_pages)
    table = jnp.asarray(table)
    pad = eng._bucket(len(prompt))
    tokens = np.zeros((1, pad), np.int32)
    tokens[0, :len(prompt)] = prompt
    key, sub = jax.random.split(key)
    toks, _lp, pools, lengths, *_ = eng._prefill_paged_impl(
        params, pools, table, jnp.zeros((S,), jnp.int32),
        jnp.asarray(tokens), jnp.asarray([slot]),
        jnp.asarray([len(prompt)]), jnp.asarray([temperature], jnp.float32),
        jnp.asarray([top_p], jnp.float32), sub, pad_len=pad,
        n_real=1 if eng._counted else None)
    last = jnp.zeros((S,), jnp.int32).at[slot].set(toks[0])
    mask = jnp.zeros((S,), bool).at[slot].set(True)
    temps = jnp.zeros((S,), jnp.float32).at[slot].set(temperature)
    top_ps = jnp.ones((S,), jnp.float32).at[slot].set(top_p)
    out = [int(toks[0])]
    for _ in range(n_new - 1):
        key, sub = jax.random.split(key)
        last, _lp, pools, lengths, *_ = eng._decode_paged_impl(
            params, pools, table, lengths, last, mask, temps, top_ps, sub)
        out.append(int(last[slot]))
    return out


def test_carried_key_and_sampled_tokens_follow_the_eager_chain(tiny_llm):
    eng = _engine(tiny_llm, max_prefill_batch=1)
    prompts = [np.arange(3, 3 + n) % 128 for n in (5, 19, 11)]
    sampled = []
    try:
        for i, prompt in enumerate(prompts):
            st = _settle(eng)
            dispatched = st["decode_steps"] + st["prefill_calls"]
            assert dispatched == st["runtime_calls"] // 2
            key = _chain(dispatched)
            # k decode and m prefill dispatches: the k + m-fold chain
            np.testing.assert_array_equal(np.asarray(eng._state.key),
                                          np.asarray(key))
            slot = eng._free_slots[-1]
            got = eng.generate_sync(prompt, max_new_tokens=9,
                                    temperature=0.8, top_p=0.9)
            want = _straight_line(eng, key, prompt, 9, slot, 0.8, 0.9)
            assert got == want, (i, got, want)
            sampled.append(got)
        st = _settle(eng)
        assert st["decode_steps"] > 3 * 8    # the pipeline's lagged steps
        np.testing.assert_array_equal(
            np.asarray(eng._state.key),
            np.asarray(_chain(st["decode_steps"] + st["prefill_calls"])))
        greedy = [eng.generate_sync(p, max_new_tokens=9) for p in prompts]
    finally:
        eng.shutdown()
    # the draws were draws: at 0.8 an answer leaves the greedy one
    assert sampled != greedy


@pytest.mark.parametrize("decode_block", [1, 3], ids=["step", "block"])
def test_decode_steps_drawn_counts_the_steps_with_a_sampled_row(
        tiny_llm, decode_block):
    """`decode_steps_drawn` is the engagement of the sampler's draw
    branch as the host knows it: 0 over greedy traffic (a top_p below 1
    on a greedy row asks for nothing), and the steps of the decode
    dispatches whose slots held a request with a temperature above 0,
    no more: a greedy request goes on alone after the sampled one."""
    eng = _engine(tiny_llm, decode_block=decode_block)
    with_sampled = []
    dispatch = eng._dispatch_decode

    def counted(inflight, snapshot, *rest):
        with_sampled.append(any(r.temperature > 0 for _s, r in snapshot))
        return dispatch(inflight, snapshot, *rest)
    try:
        _mixed_traffic(eng)
        greedy = _settle(eng)
        assert greedy["decode_steps"] > 20
        assert greedy["decode_steps_drawn"] == 0
        eng._dispatch_decode = counted
        long = eng.submit(np.arange(1, 9), max_new_tokens=40)
        short = eng.submit(np.arange(2, 12), max_new_tokens=6,
                           temperature=0.7)
        assert len(list(eng.stream(short))) == 6
        assert len(list(eng.stream(long))) == 40
        st = _settle(eng)
    finally:
        eng.shutdown()
    assert 0 < sum(with_sampled) < len(with_sampled)
    assert st["decode_steps_drawn"] == decode_block * sum(with_sampled)
    assert (st["decode_steps"] - greedy["decode_steps"]
            == decode_block * len(with_sampled))


# (c) a slot and its pages under a new owner, ten results in flight --------
@pytest.mark.parametrize("with_prefix", [False, True],
                         ids=["plain", "adopted_prefix"])
@pytest.mark.parametrize("family", ["tiny_llm", "tiny_latent"],
                         ids=["PagedKV", "PagedLatent"])
def test_slot_reuse_under_a_deep_pipeline(request, family, with_prefix):
    """Two slots, a pool that holds two requests and no third: every
    later request takes a slot in the iteration after its release, with
    the releaser's lagged decode steps still queued on the device, and
    is given the pages those steps write to. Each must decode what it
    decodes alone."""
    pair = request.getfixturevalue(family)
    prefix = (np.arange(40, 40 + 21) % 120 + 1) if with_prefix else None
    rng = np.random.default_rng(11)
    asks = [(rng.integers(1, 120, int(n)), int(k))
            for n, k in zip(rng.integers(3, 14, 7), rng.integers(4, 17, 7))]
    cfg = dict(max_slots=2, max_seq_len=64, kv_page_size=8,
               pipeline_depth=10, max_prefixes=1 if with_prefix else 0,
               # two requests' worst case (prefix 21 + prompt 13 +
               # answer 16 = 50 tokens = 7 pages) and the pinned prefix
               kv_pool_tokens=8 * (14 + (3 if with_prefix else 0)))

    eng = _engine(pair, **cfg)
    try:
        assert eng.cfg.pipeline_depth == 10
        pid = eng.register_prefix(prefix) if with_prefix else None

        def run(which):
            rids = [eng.submit(asks[i][0], max_new_tokens=asks[i][1],
                               prefix_id=pid) for i in which]
            return [list(eng.stream(r)) for r in rids]

        alone = []
        for i in range(len(asks)):
            alone += run([i])
            _settle(eng)            # nothing queued when the next starts
        before = eng.get_stats()
        together = run(range(len(asks)))
        st = _settle(eng)
    finally:
        eng.shutdown()
    assert together == alone
    assert [len(t) for t in together] == [k for _p, k in asks]
    # slots were taken again while lagged rows were still coming back
    assert (st["spans"]["slot.refill"][0]
            - before["spans"]["slot.refill"][0]) >= len(asks) - 2
    assert (st["decode_tokens_discarded"]
            > before["decode_tokens_discarded"])
    pinned = st["kv_pages"]["pinned_prefix"]
    assert st["kv_pages"]["free"] == st["kv_pages"]["total"] - pinned
