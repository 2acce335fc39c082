"""The token hand-off inside the replica (engine -> actor loop ->
stream_next): an awaitable consumer gets what a blocking one gets, one
wake of its loop per engine iteration, no thread per stream. No timing
assertions: counts, orders and thread numbers only."""
import asyncio
import threading
import time

import numpy as np
import pytest

from ray_tpu.serve.llm import engine as engine_mod

PROMPTS = [np.arange(1, n + 1) for n in (5, 9, 12, 16)]


def _tiny_llm():
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import Llama, LlamaConfig
    cfg = LlamaConfig(vocab_size=128, d_model=32, n_layers=1, n_heads=4,
                      n_kv_heads=2, d_ff=64, max_seq_len=256, remat=False,
                      dtype=jnp.float32)
    model = Llama(cfg)
    return model, model.init_params(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def eng():
    from ray_tpu.serve.llm import LLMEngine, LLMEngineConfig
    model, params = _tiny_llm()
    e = LLMEngine(model, params, LLMEngineConfig(
        max_slots=4, max_seq_len=128, prefill_buckets=(16, 32),
        max_prefill_batch=4, kv_page_size=16, pipeline_depth=3,
        logprobs=True))
    yield e
    e.shutdown()
    e._loop_thread.join(timeout=30)
    assert not e._loop_thread.is_alive()


def _submit_together(eng, requests):
    """One admission pass sees the whole group, so that two runs of the
    same requests run the same programs on the same batches."""
    rids = []
    eng._run_on_loop(lambda: rids.extend(
        eng.submit(prompt, **kw) for prompt, kw in requests))
    return rids


def _blocking(eng, requests, abort_after=None):
    """[(tokens, logprobs, error type)] through stream_detailed."""
    out = []
    for rid in _submit_together(eng, requests):
        toks, lps, err = [], [], None
        try:
            for tok, lp in eng.stream_detailed(rid):
                toks.append(tok)
                lps.append(lp)
                if len(toks) == abort_after:
                    eng.abort(rid)
        except Exception as e:  # noqa: BLE001  the case compares its type
            err = type(e)
        out.append((toks, lps, err))
    return out


def _awaitable(eng, requests, abort_after=None):
    """The same through astream_detailed, all streams on one loop."""
    async def one(stream, rid):
        toks, lps, err = [], [], None
        try:
            async for tok, lp in stream:
                toks.append(tok)
                lps.append(lp)
                if len(toks) == abort_after:
                    eng.abort(rid)
        except Exception as e:  # noqa: BLE001
            err = type(e)
        return toks, lps, err

    async def main():
        # the engine thread is held between the submits and the attach,
        # so that no token goes to a queue before its consumer is there
        loop = asyncio.get_running_loop()
        submitted, attached, rids = threading.Event(), threading.Event(), []

        def submit_and_hold():
            rids.extend(eng.submit(prompt, **kw) for prompt, kw in requests)
            submitted.set()
            attached.wait(60)
        held = loop.run_in_executor(None, eng._run_on_loop, submit_and_hold)
        assert await loop.run_in_executor(None, submitted.wait, 60)
        streams = [eng.astream_detailed(rid) for rid in rids]
        attached.set()
        await held
        return await asyncio.gather(
            *(one(stream, rid) for stream, rid in zip(streams, rids)))
    return asyncio.run(main())


class _DispatchFailed(RuntimeError):
    pass


def _greedy(eng):
    return [(p, dict(max_new_tokens=10)) for p in PROMPTS], None


def _stop_ids(eng):
    # stop at the third token each prompt greedily produces
    thirds = [list(eng.stream(eng.submit(p, max_new_tokens=3)))[2]
              for p in PROMPTS]
    return [(p, dict(max_new_tokens=10, stop_token_ids=[t]))
            for p, t in zip(PROMPTS, thirds)], None


def _abort(eng):
    return [(p, dict(max_new_tokens=100)) for p in PROMPTS], 4


def _dispatch_error(eng):
    return [(p, dict(max_new_tokens=10)) for p in PROMPTS], None


@pytest.mark.parametrize("case", [_greedy, _stop_ids, _abort,
                                  _dispatch_error],
                         ids=lambda f: f.__name__.strip("_"))
def test_awaitable_consumer_yields_what_the_blocking_one_yields(
        eng, case, monkeypatch):
    requests, abort_after = case(eng)
    if case is _dispatch_error:
        def boom(*a, **kw):
            raise _DispatchFailed("prefill dispatch failed")
        monkeypatch.setattr(eng, "_prefill_paged_jit", boom)
    before = eng.get_stats()
    want = _blocking(eng, requests, abort_after)
    got = _awaitable(eng, requests, abort_after)
    st = eng.get_stats()
    assert len(got) == len(want) == len(PROMPTS)
    for (toks, lps, err), (wtoks, wlps, werr), (_p, kw) in zip(
            got, want, requests):
        assert err is werr
        if case is _abort:
            # how many lagged tokens follow an abort() depends on where
            # the pipeline stood; the stream still ends, short of budget
            assert err is None
            assert abort_after <= len(toks) < 100
            assert toks[:abort_after] == wtoks[:abort_after]
            continue
        assert toks == wtoks
        np.testing.assert_allclose(lps, wlps, rtol=0, atol=1e-5)
        if case is _dispatch_error:
            assert err is _DispatchFailed and toks == []
        elif case is _stop_ids:
            assert err is None and 1 <= len(toks) <= 3
            assert toks[-1] == kw["stop_token_ids"][0]
        else:
            assert len(toks) == 10 and all(lp is not None for lp in lps)
    # every request is gone from the engine on both paths
    assert eng._requests == {} and st["active"] == 0
    # the blocking half went through queues, the awaitable half did not
    blocking = (st["deliver_blocking_tokens"]
                - before["deliver_blocking_tokens"])
    assert blocking >= sum(len(t) for t, _l, _e in want)
    assert (st["deliver_items"] - before["deliver_items"]
            >= sum(len(t) for t, _l, _e in got))


def test_one_hand_over_per_iteration_carries_every_item(eng):
    calls = []          # (items in the outbox, batches added, items added)
    hand_over = eng._hand_over

    def counted():
        n, st = len(eng._outbox), eng.stats
        b0, i0 = st["deliver_batches"], st["deliver_items"]
        hand_over()
        calls.append((n, st["deliver_batches"] - b0,
                      st["deliver_items"] - i0))
    before = eng.get_stats()
    eng._hand_over = counted
    try:
        got = _awaitable(eng, [(p, dict(max_new_tokens=12))
                               for p in PROMPTS])
    finally:
        del eng._hand_over
    st = eng.get_stats()
    assert all(len(toks) == 12 and err is None for toks, _l, err in got)
    tokens = st["tokens_generated"] - before["tokens_generated"]
    assert tokens == 12 * len(PROMPTS)
    carried = [c for c in calls if c[0]]
    # one wake of the consumers' loop per iteration that put something,
    # carrying all of it: several streams' tokens ride together
    assert all(b == 1 and i == n for n, b, i in carried)
    assert sum(n for n, _b, _i in carried) == tokens + len(PROMPTS)
    assert len(carried) < tokens
    assert max(n for n, _b, _i in carried) >= len(PROMPTS)
    assert all(b == 0 and i == 0 for n, b, i in calls if not n)
    assert st["deliver_batches"] - before["deliver_batches"] == len(carried)
    assert (st["spans"]["engine.deliver"][0]
            - before["spans"]["engine.deliver"][0]) == len(carried)
    assert st["deliver_blocking_tokens"] == before["deliver_blocking_tokens"]
    # the put time rode with every token and the consumer closed the span
    assert (st["spans"]["stream.deliver"][0]
            - before["spans"]["stream.deliver"][0]) == tokens


def test_a_consumer_that_stops_taking_is_aborted_and_the_rest_go_on(
        eng, monkeypatch):
    monkeypatch.setattr(engine_mod, "_SINK_BOUND", 8)
    monkeypatch.setattr(eng, "_CONSUMER_STALL_TTL_S", 0.02)
    events = []
    event = eng._event
    monkeypatch.setattr(eng, "_event", lambda etype, *a, req=None, **kw: (
        events.append((etype, req.request_id if req is not None else None,
                       kw.get("kind"))), event(etype, *a, req=req, **kw)))

    async def main():
        rids = _submit_together(eng, [(p, dict(max_new_tokens=100))
                                      for p in PROMPTS])
        streams = [eng.astream_detailed(rid) for rid in rids]
        others_done = asyncio.Event()

        async def stalls(stream):
            toks = [(await anext(stream))[0], (await anext(stream))[0]]
            await others_done.wait()        # takes nothing meanwhile
            toks.extend([tok async for tok, _lp in stream])
            return toks

        async def takes(stream):
            return [tok async for tok, _lp in stream]
        stalled = asyncio.ensure_future(stalls(streams[0]))
        rest = await asyncio.gather(*(takes(s) for s in streams[1:]))
        req = eng._requests[rids[0]]
        others_done.set()
        return rids[0], req, await stalled, rest
    rid, req, stalled, rest = asyncio.run(main())
    # the others ran to their budget while one reader stood still
    assert [len(t) for t in rest] == [100] * (len(PROMPTS) - 1)
    # the stalled request was cut short by the TTL, and its stream still
    # ended: what was handed over before the abort is all there
    assert req.aborted and 2 + 8 <= len(stalled) < 100
    mine = [(etype, kind) for etype, r, kind in events if r == rid]
    assert ("sched.hang.suspected", "consumer_stalled") in mine
    assert ("sched.hang.resolved", "consumer_stalled") in mine
    assert mine.index(("sched.hang.suspected", "consumer_stalled")) \
        < mine.index(("sched.hang.resolved", "consumer_stalled"))
    # a reader that falls behind for a moment is suspected at most
    assert not any(etype == "sched.hang.resolved" and r != rid
                   for etype, r, _k in events)
    assert eng._requests == {} and not eng.wedged


class _RecordingLoop:
    """Stands where a consumer's event loop would: keeps what the
    engine hands over."""

    def __init__(self):
        self.items = []

    def call_soon_threadsafe(self, _deliver, batch):
        self.items.extend(item for _sink, item in batch)


def test_a_consumer_behind_its_bound_that_still_takes_keeps_its_request(
        eng, monkeypatch):
    """Slow is not gone: the stall clock restarts with every item the
    consumer takes, and runs out only once it takes nothing."""
    monkeypatch.setattr(engine_mod, "_SINK_BOUND", 4)
    monkeypatch.setattr(eng, "_CONSUMER_STALL_TTL_S", 0.05)
    events = []
    monkeypatch.setattr(eng, "_event", lambda etype, *a, **kw: (
        events.append(etype)))
    loop = _RecordingLoop()
    req = engine_mod._Request("slow", PROMPTS[0], 1000, 0.0)
    sink = req.sink = engine_mod._LoopSink(loop, "slow", eng._outbox)

    def put(i):
        eng._put_token(req, ("token", (i, None, time.time())))
    for i in range(6):
        put(i)              # the reader falls behind its bound of 4 ...
    for i in range(6, 40):
        sink.taken += 1     # ... and from there takes at the engine's
        put(i)              # pace, for four times the TTL
        time.sleep(0.005)
    assert not req.aborted
    assert events == ["sched.hang.suspected"]
    for i in range(40, 43):
        put(i)              # now it takes nothing
        time.sleep(0.04)
    assert req.aborted and req.max_new_tokens == req.generated
    assert events == ["sched.hang.suspected", "llm_engine.request_abort",
                      "sched.hang.resolved"]
    # the engine never waited, and every token it put before the abort
    # reached the consumer's loop, past the bound and in order
    for _ in range(500):
        if len(loop.items) >= 42:
            break
        time.sleep(0.01)
    assert [item[1][0] for item in loop.items] == list(range(42))


def test_a_consumer_that_attaches_while_the_engine_waits_loses_no_token(
        eng, monkeypatch):
    """The engine waits for a full blocking sink OUTSIDE the request's
    lock; a token it lands in the old queue after an awaitable consumer
    emptied it is carried over, in order."""
    monkeypatch.setattr(engine_mod, "_SINK_BOUND", 2)
    req = engine_mod._Request("swap", PROMPTS[0], 1000, 0.0)
    eng._requests["swap"] = req
    for i in range(2):
        eng._put_token(req, ("token", (i, None, time.time())))
    waits = threading.Thread(target=eng._put_token, args=(
        req, ("token", (2, None, time.time()))))
    waits.start()           # the queue is full: parks in its put
    time.sleep(0.1)

    async def main():
        assert waits.is_alive()
        stream = eng.astream_detailed("swap")
        await asyncio.get_running_loop().run_in_executor(None, waits.join)
        eng._put_token(req, ("token", (3, None, time.time())))
        eng._put(req, engine_mod._END)
        return [tok async for tok, _lp in stream]
    assert asyncio.run(main()) == [0, 1, 2, 3]
    assert "swap" not in eng._requests


def test_closing_an_awaitable_stream_early_aborts_its_request(eng):
    async def main():
        rid = eng.submit(PROMPTS[0], max_new_tokens=100)
        stream = eng.astream_detailed(rid)
        with pytest.raises(RuntimeError, match="awaitable consumer"):
            next(eng.stream_detailed(rid))
        first = [await anext(stream) for _ in range(3)]
        await stream.aclose()           # the client went away
        for _ in range(500):
            if not eng.get_stats()["active"]:
                break
            await asyncio.sleep(0.01)
        return first
    before = eng.get_stats()["tokens_generated"]
    assert len(asyncio.run(main())) == 3
    st = eng.get_stats()
    assert st["active"] == 0 and eng._requests == {}
    assert st["tokens_generated"] - before < 100


# ---- through a Replica object ---------------------------------------------
@pytest.fixture(scope="module")
def llm_replica():
    from ray_tpu.core import serialization
    from ray_tpu.serve.llm.openai_api import OpenAIServer
    from ray_tpu.serve.replica import Replica
    rep = Replica(
        "llm", "llm#0", serialization.dumps_call(OpenAIServer),
        (_tiny_llm,), dict(engine_config=dict(
            max_slots=2, max_seq_len=128, prefill_buckets=(16, 32),
            kv_page_size=16, pipeline_depth=3)),
        max_ongoing_requests=64)
    yield rep
    rep._callable.engine.shutdown()


async def _pull(rep, sid, on_pull=None):
    out = []
    while True:
        chunks, done = await rep.stream_next(sid, timeout_s=120)
        out.extend(chunks)
        if on_pull is not None:
            on_pull()
        if done:
            return out


def test_forty_streams_through_a_two_slot_replica_park_no_thread(
        llm_replica):
    rep, eng = llm_replica, llm_replica._callable.engine
    before = eng.get_stats()
    threads = [threading.active_count()]

    async def one(i):
        body = {"prompt": list(range(1, 6 + i % 7)), "max_tokens": 12,
                "temperature": 0.0, "stream": True}
        sid = await rep.stream_start("__call__", (body,), {})
        return await _pull(rep, sid, lambda: threads.append(
            threading.active_count()))

    async def main():
        return await asyncio.gather(*(one(i) for i in range(40)))
    outs = asyncio.run(main())
    # 38 of them waited inside the engine for a slot, and all finish
    assert len(outs) == 40
    for out in outs:
        assert out[-1] == "[DONE]"
        assert out[-2]["choices"][0]["finish_reason"] == "length"
        assert len(out) == 12 + 2
    # no thread per stream, waiting or decoding
    assert max(threads) <= threads[0] + 1
    st = eng.get_stats()
    # every token and end marker was handed over (but for a first token
    # the engine put between a submit and its attach, on a slow day)
    early = st["deliver_blocking_tokens"] - before["deliver_blocking_tokens"]
    assert early <= 2
    assert (st["deliver_items"] - before["deliver_items"] + early
            == 40 * (12 + 1))
    assert rep.get_metrics()["ongoing"] == 0
    assert rep._streams == {} and rep._drains == {}


def test_the_actor_loops_rows_count_replies_chunks_and_hand_overs(
        llm_replica):
    """Forty streams again, read through the rows of the process's
    table that `get_stats()["spans"]` reports beside the engine's own:
    one `replica.stream_next` a call made, one `replica.stream_put` a
    chunk buffered, one `consumer.deliver` a hand-over that carried
    something."""
    from ray_tpu.observability.profiler import PROCESS_SPANS
    rep, eng = llm_replica, llm_replica._callable.engine
    before = eng.get_stats()
    assert set(PROCESS_SPANS) <= set(before["spans"])
    calls = []

    async def one(i):
        body = {"prompt": list(range(1, 6 + i % 7)), "max_tokens": 12,
                "temperature": 0.0, "stream": True}
        sid = await rep.stream_start("__call__", (body,), {})
        out = await _pull(rep, sid, lambda: calls.append(sid))
        # a finished stream's id: answered at once, and counted
        assert await rep.stream_next(sid) == ([], True)
        calls.append(sid)
        return out

    async def main():
        return await asyncio.gather(*(one(i) for i in range(40)))
    started = time.perf_counter_ns()
    outs = asyncio.run(main())
    lived = time.perf_counter_ns() - started
    assert all(len(out) == 12 + 2 for out in outs)
    st = eng.get_stats()

    def grew(name, col=0):
        return st["spans"][name][col] - before["spans"][name][col]
    assert grew("replica.stream_next") == len(calls) >= 40 * 2
    assert grew("replica.stream_put") == 40 * (12 + 2)
    assert grew("consumer.deliver") \
        == st["deliver_batches"] - before["deliver_batches"] > 0
    # wall time of one loop's thread, the parked polls left out: the
    # rows together are less than the time the loop lived
    own = sum(grew(name, 1) for name in PROCESS_SPANS)
    assert 0 < own < lived
    # nothing here went through an actor's call
    assert grew("actor.call.reply") == 0


def test_stream_cancel_in_mid_stream_frees_the_engine_slot(llm_replica):
    rep, eng = llm_replica, llm_replica._callable.engine

    async def main():
        body = {"prompt": [1, 2, 3], "max_tokens": 100,
                "temperature": 0.0, "stream": True}
        sid = await rep.stream_start("__call__", (body,), {})
        chunks, done = await rep.stream_next(sid, timeout_s=120)
        assert chunks and not done and eng.get_stats()["active"] == 1
        assert await rep.stream_cancel(sid)
        assert not await rep.stream_cancel(sid)      # idempotent
        assert await rep.stream_next(sid) == ([], True)
        for _ in range(500):
            if not eng.get_stats()["active"]:
                break
            await asyncio.sleep(0.01)
    before = eng.get_stats()["tokens_generated"]
    asyncio.run(main())
    st = eng.get_stats()
    assert st["active"] == 0 and eng._requests == {}
    assert st["tokens_generated"] - before < 100
    assert rep.get_metrics()["ongoing"] == 0
    assert rep._streams == {} and rep._drains == {}


def _count_to(n):
    async def gen():
        for i in range(n):
            yield i
    return gen()


def _count_to_sync(n):
    yield from range(n)


@pytest.mark.parametrize("handler", [_count_to, _count_to_sync],
                         ids=["async-generator", "plain-generator"])
def test_stream_next_keeps_its_protocol_for_other_deployments(handler):
    from ray_tpu.core import serialization
    from ray_tpu.serve.replica import Replica
    rep = Replica("count", "count#0", serialization.dumps_call(handler),
                  (), {})

    async def main():
        # batches of at most `batch`, in order, then done
        sid = await rep.stream_start("__call__", (150,), {})
        first, done = await rep.stream_next(sid, batch=64, timeout_s=60)
        assert 1 <= len(first) <= 64 and not done
        rest = await _pull(rep, sid)
        assert first + rest == list(range(150))
        assert await rep.stream_next(sid) == ([], True)
        # a producer ahead of its consumer parks at the buffer's bound,
        # and a cancel reaches it there
        sid = await rep.stream_start("__call__", (5000,), {})
        buf = rep._streams[sid]
        for _ in range(2000):
            if len(buf.items) == 1024:
                break
            await asyncio.sleep(0.005)
        assert len(buf.items) == 1024 and rep.get_metrics()["ongoing"] == 1
        assert await rep.stream_cancel(sid)
        for _ in range(500):
            if not rep.get_metrics()["ongoing"]:
                break
            await asyncio.sleep(0.01)
        assert rep.get_metrics()["ongoing"] == 0 and rep._drains == {}
        # an empty buffer answers ([], False) once timeout_s is over
        hold = asyncio.Event()

        async def waits_first():
            await hold.wait()
            yield "late"
        rep._callable = waits_first
        sid = await rep.stream_start("__call__", (), {})
        assert await rep.stream_next(sid, timeout_s=0.05) == ([], False)
        hold.set()
        assert await _pull(rep, sid) == ["late"]
    asyncio.run(main())


def test_a_second_poll_of_one_stream_takes_over_from_the_first():
    from ray_tpu.serve.replica import _StreamBuffer

    async def main():
        buf = _StreamBuffer()
        first = asyncio.ensure_future(buf.wait(60))
        await asyncio.sleep(0)
        second = asyncio.ensure_future(buf.wait(60))
        assert await first is False     # "nothing yet", and at once
        await buf.put("x")
        assert await second is True and buf.pop() == "x"
        assert buf._ready is None
        assert await buf.wait(0.01) is False and buf._ready is None
    asyncio.run(main())


# ---- the caller's side of the stream pulls --------------------------------
def test_direct_call_futures_register_from_many_threads_at_once():
    """A serve proxy pulls every stream with a direct actor call from a
    thread of its own; registering a call's future evicts the oldest
    resolved ones, and an insert between another thread's iter() and
    next() raised 'OrderedDict mutated during iteration' into the
    request (one HTTP 500 in ~24 000 requests on the chip)."""
    import collections
    import sys
    from ray_tpu.core.worker import WorkerRuntime, _DirectFuture
    rt = object.__new__(WorkerRuntime)
    rt._direct_lock = threading.Lock()
    rt._direct_results = collections.OrderedDict()
    rt._direct_evicted = set()
    rt._DIRECT_RESULT_RETAIN = 8
    errors = []

    def register(k):
        try:
            for i in range(4000):
                fut = _DirectFuture()
                fut.ev.set()
                rt._register_direct_future(f"{k}-{i}", fut)
        except Exception as e:  # noqa: BLE001  asserted empty below
            errors.append(repr(e))
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=register, args=(k,))
                   for k in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    assert errors == []
    assert len(rt._direct_results) <= 8
