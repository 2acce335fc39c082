"""How many dispatched programs the engine loop keeps in flight.

The loop drains the oldest result once more than `target` programs are
queued. The target is `_InflightDepth`'s: 1 + ceil(H / P) from the
host's own time an iteration (a cautious reading: the recent largest,
one outlier cut) and the device's time a program (the interval between
two fetches that had to wait), floor 2, ceiling `cfg.pipeline_depth`.
These tests hold (a) the rule as a function of what it is fed, (b) an
engine whose loop is fed scripted readings, so that the target moves
floor -> ceiling -> floor while requests finish and slots are refilled,
to the tokens each request decodes alone, and (c) the counters the
benchmark's `engine_inflight_depth_mean` reads.
"""
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import Llama, LlamaConfig
from ray_tpu.serve.llm import LLMEngine, LLMEngineConfig
from ray_tpu.serve.llm.engine import _InflightDepth

MS = 1_000_000


def _feed(depth, n, host_ms, period_ms, waits=True, now=0):
    """`n` loop iterations: each drains one program whose fetch
    returned `period_ms` after the last one (having waited, or at
    once), then reports `host_ms` of host time. Returns the clock."""
    for _ in range(n):
        now += int(period_ms * MS)
        depth.fetched(int(period_ms * MS / 2) if waits else 20_000, now)
        depth.iterated(int(host_ms * MS))
    return now


def _unmeasured(d):
    pass


def _burst(d):              # mistral7b_short_burst's readings
    _feed(d, 100, 7.3, 12.4)


def _busy_host(d):
    _feed(d, 100, 20.0, 12.4)


def _prefill_iterations(d):     # every eighth iteration dispatches a group
    now = 0
    for _ in range(16):
        now = _feed(d, 7, 11.5, 13.45, now=now)
        now = _feed(d, 1, 25.0, 13.45, now=now)


def _host_far_slower_than_a_step(d):
    _feed(d, 100, 200.0, 12.4)


def _host_slower_side(d):       # no fetch ever waits
    _feed(d, 100, 30.0, 30.0, waits=False)


def _device_then_host_slower(d):
    now = _feed(d, 100, 40.0, 10.0)
    assert d.target(10) == 5
    _feed(d, 2 * d.block, 40.0, 40.0, waits=False, now=now)


def _one_stall(d):
    now = _feed(d, 100, 8.0, 12.0)
    # the whole machine stood for 3 s; the device had long finished
    now = _feed(d, 1, 3000.0, 3000.0, waits=False, now=now)
    # cut to CLIP x the usual 8 ms: 1 + ceil(32 / 12), not the ceiling
    assert d.target(10) == 4
    now = _feed(d, 1, 8.0, 12.0, now=now)
    assert d.host_ns < 12 * MS and d.period_ns < 20 * MS    # not learnt
    _feed(d, 2 * d.block, 8.0, 12.0, now=now)


def _stall_still_in_the_window(d):
    now = _feed(d, 100, 8.0, 12.0)
    now = _feed(d, 1, 3000.0, 3000.0, waits=False, now=now)
    _feed(d, d.block - 1, 8.0, 12.0, now=now)


def _idle_between_bursts(d):
    now = _feed(d, 50, 7.0, 12.0)
    d.idle()                    # ten seconds with nothing in flight
    _feed(d, 50, 7.0, 12.0, now=now + 10_000 * MS)
    assert d.period_ns < 13 * MS


@pytest.mark.parametrize("feed,ceiling,active,need_sync,want", [
    (_unmeasured, 10, True, False, 2),
    (_burst, 10, True, False, 2),
    (_busy_host, 10, True, False, 3),
    (_prefill_iterations, 10, True, False, 3),
    (_host_far_slower_than_a_step, 10, True, False, 10),
    (_host_far_slower_than_a_step, 3, True, False, 3),
    (_burst, 1, True, False, 1),
    (_burst, 0, True, False, 0),
    (_host_slower_side, 10, True, False, 2),
    (_device_then_host_slower, 10, True, False, 2),
    (_one_stall, 10, True, False, 2),
    (_stall_still_in_the_window, 10, True, False, 4),
    (_idle_between_bursts, 10, True, False, 2),
    (_busy_host, 10, True, True, 0),
    (_busy_host, 10, False, False, 0),
], ids=lambda v: getattr(v, "__name__", str(v)).lstrip("_"))
def test_the_target_is_a_function_of_what_the_loop_measured(
        feed, ceiling, active, need_sync, want):
    d = _InflightDepth()
    feed(d)
    assert d.target(ceiling, active, need_sync) == want


@pytest.fixture(scope="module")
def tiny_llm():
    cfg = LlamaConfig(vocab_size=128, d_model=32, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_ff=64, max_seq_len=256, remat=False,
                      dtype=jnp.float32)
    model = Llama(cfg)
    return model, model.init_params(jax.random.PRNGKey(0))


def _engine(pair, **overrides):
    model, params = pair
    base = dict(max_slots=2, max_seq_len=64, prefill_buckets=(16,),
                kv_page_size=8)
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


class _Scripted(_InflightDepth):
    """The loop's own calls, its clock readings replaced by a script's:
    every fetch waited one device period of 10 ms, and an iteration's
    host time is what `host_ms(i)` says of the i-th."""
    PERIOD = 10 * MS

    def __init__(self, host_ms, block):
        super().__init__(block)
        self.host_ms, self.now, self.i, self.targets = host_ms, 0, 0, []

    def fetched(self, wait_ns, now_ns):
        self.now += self.PERIOD
        super().fetched(self.PERIOD // 2, self.now)

    def iterated(self, host_ns):
        super().iterated(int(self.host_ms(self.i) * MS))
        self.i += 1

    def target(self, ceiling, active=True, need_sync=False):
        got = super().target(ceiling, active, need_sync)
        if active:
            self.targets.append(got)
        return got


def test_the_target_moves_while_slots_are_refilled(tiny_llm):
    """Two slots, ten requests of 8 to 24 tokens, ceiling 5. The
    script's host needs 1 ms an iteration, then 100 (ten device
    periods), then 1 again: the target goes 2 -> 5 -> 2 with requests
    finishing at every depth. Each request decodes what it decodes
    alone, and no request loses more rows than the ceiling."""
    ceiling = 5
    rng = np.random.default_rng(35)
    asks = [(rng.integers(1, 120, int(n)), int(k))
            for n, k in zip(rng.integers(3, 14, 10),
                            rng.integers(8, 25, 10))]
    eng = _engine(tiny_llm, pipeline_depth=ceiling)
    try:
        def run(which):
            rids = [eng.submit(asks[i][0], max_new_tokens=asks[i][1])
                    for i in which]
            return [list(eng.stream(r)) for r in rids]

        alone = []
        for i in range(len(asks)):
            alone += run([i])
            _settle(eng)
        before = eng.get_stats()
        script = _Scripted(lambda i: 100.0 if 20 <= i < 45 else 1.0,
                           block=4)
        eng._run_on_loop(lambda: setattr(eng, "_depth", script))
        together = run(range(len(asks)))
        st = _settle(eng)
    finally:
        eng.shutdown()
    assert together == alone
    assert [len(t) for t in together] == [k for _p, k in asks]
    seen = script.targets
    assert set(seen) <= set(range(2, ceiling + 1))
    up = seen.index(ceiling)
    assert seen[0] == 2 and 2 in seen[up:]
    down = up + seen[up:].index(2)
    # requests were still being admitted after the target came down
    assert (st["spans"]["slot.refill"][0]
            - before["spans"]["slot.refill"][0]) >= len(asks) - 2
    assert down < len(seen) - 10
    lost = (st["decode_tokens_discarded"]
            - before["decode_tokens_discarded"])
    assert 0 < lost <= ceiling * len(asks)
    n = st["decode_inflight_target_n"] - before["decode_inflight_target_n"]
    total = (st["decode_inflight_target_sum"]
             - before["decode_inflight_target_sum"])
    assert 2 < total / n < ceiling


@pytest.mark.parametrize("ceiling", [2, 10])
def test_the_counted_target_lies_between_floor_and_ceiling(tiny_llm,
                                                           ceiling):
    eng = _engine(tiny_llm, pipeline_depth=ceiling)
    try:
        rids = [eng.submit(np.arange(1, 6 + i), max_new_tokens=12)
                for i in range(5)]
        got = [list(eng.stream(r)) for r in rids]
        st = _settle(eng)
        dispatches = eng._decode_dispatches
    finally:
        eng.shutdown()
    assert [len(g) for g in got] == [12] * 5
    assert st["decode_inflight_target_n"] == dispatches > 0
    mean = (st["decode_inflight_target_sum"]
            / st["decode_inflight_target_n"])
    assert 2 <= mean <= ceiling
    assert set(st["inflight_depth"]) == {
        "target", "host_peak_ms", "host_usual_ms", "device_period_ms"}
    assert 2 <= st["inflight_depth"]["target"] <= ceiling
