"""The program's own spans and counters (observability/profiler.py:
SpanTable) and what the serving and training hot paths record with it:
the engine loop's phases, the counters at the same boundaries, each
request's stamps, and the proxy-to-engine receipt time. The counts are
exact on the CPU; no time read here is a device metric.
"""
import asyncio
import gc
import glob
import json
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from ray_tpu.observability import profiler
from ray_tpu.observability.profiler import (GC_SPANS, LOCK_SPANS,
                                            PROCESS_SPANS, SpanTable,
                                            thread_clocks)

PROMPT_LENS = (10, 12, 9, 11)       # one bucket of 16, one group of 4
NEW_TOKENS = 8
DEPTH = 3


# ---- the primitive ------------------------------------------------------
def test_span_counts_total_and_max():
    t = SpanTable(["seeded"])
    for ms in (1, 3):
        with t.span("work", bucket=16):
            time.sleep(ms / 1000)
    rows = t.snapshot()
    assert rows["seeded"] == [0, 0, 0, 0]
    n, total, longest, cpu = rows["work"]
    assert n == 2 and total >= 4_000_000
    assert 3_000_000 <= longest < total
    # a sleeping span stands still: next to no CPU beside 4 ms of wall
    assert 0 <= cpu < 1_000_000


def _burn(ms):
    """Hold this thread's CPU for `ms` of its own clock."""
    end = time.thread_time_ns() + ms * 1_000_000
    while time.thread_time_ns() < end:
        pass


def test_cpu_self_time_is_work_and_never_more_than_wall():
    t = SpanTable()
    for _ in range(200):
        with t.span("empty"):
            pass
    with t.span("works"):
        _burn(20)
    with t.span("both"):
        _burn(10)
        time.sleep(0.02)
    rows = t.snapshot()
    for name, (n, total, _longest, cpu) in rows.items():
        assert 0 <= cpu <= total, name      # the CPU reads lie inside
    assert rows["works"][3] >= 20_000_000
    assert 10_000_000 <= rows["both"][3] < rows["both"][1] - 15_000_000


def test_a_childs_cpu_is_taken_off_its_parent():
    t = SpanTable()
    with t.span("outer"):
        _burn(10)
        with t.span("inner"):
            _burn(30)
            with t.span("asleep"):
                time.sleep(0.02)
    rows = t.snapshot()
    assert 30_000_000 <= rows["inner"][3] < 39_000_000
    assert 10_000_000 <= rows["outer"][3] < 19_000_000
    assert rows["asleep"][3] < 1_000_000 < 20_000_000 <= rows["asleep"][1]
    # an interval stamped elsewhere brings its own CPU, or none
    t.add("stamped", 5_000, 2_000)
    t.add("stamped", 7_000)
    assert t.snapshot()["stamped"] == [2, 12_000, 7_000, 2_000]


def test_call_times_one_call_without_nesting():
    t = SpanTable()
    with t.span("phase"):
        assert t.call("timed", _burn, 10) is None
        with pytest.raises(ZeroDivisionError):
            t.call("timed", lambda: 1 / 0)
    rows = t.snapshot()
    n, total, _longest, cpu = rows["timed"]
    assert n == 2 and 10_000_000 <= cpu <= total
    # nothing was taken off the span around the calls
    assert rows["phase"][3] >= cpu and rows["phase"][1] >= total


def test_call_opens_an_annotation_of_its_rows_name(monkeypatch):
    seen = []

    class Spy:
        def __init__(self, name, **attrs):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))
    t = SpanTable()
    t.call("warm", int)         # the real annotation is resolved
    monkeypatch.setattr(profiler, "_annotation", Spy)
    assert t.call("runtime.step", seen.append, "ran") is None
    with pytest.raises(ZeroDivisionError):
        t.call("step.release", lambda: 1 / 0)
    assert seen == [("enter", "runtime.step"), "ran",
                    ("exit", "runtime.step"), ("enter", "step.release"),
                    ("exit", "step.release")]
    t.add("stamped", 5)         # an `add` opens none
    assert len(seen) == 5


def _probe_mean_ns(rivals, probes):
    """Mean wait of `probes` probes with some Python between them,
    beside `rivals` threads that spin on the interpreter lock."""
    t = SpanTable(LOCK_SPANS)
    stop = threading.Event()

    def spin():
        while not stop.is_set():
            pass
    threads = [threading.Thread(target=spin) for _ in range(rivals)]
    for th in threads:
        th.start()
    try:
        for _ in range(probes):
            for _ in range(2000):
                pass
            t.lock_probe("lock.reacquire")
    finally:
        stop.set()
        for th in threads:
            th.join(timeout=30)
            assert not th.is_alive()
    n, total, longest, cpu = t.snapshot()["lock.reacquire"]
    assert n == probes and cpu == 0 and longest <= total
    return total / n


def test_the_lock_probe_waits_beside_a_rival_and_hardly_without():
    if profiler._resolve_released_clock() is None:
        pytest.skip("no clock can be read with the lock released here")
    quiet = _probe_mean_ns(0, 2000)
    # measured 0.6 us alone, 90-150 us beside two spinning threads (a
    # wait is now and then a whole switch interval of 5 ms)
    assert quiet < 50_000
    contended = _probe_mean_ns(2, 300)
    assert contended > 4 * quiet and contended > 5_000


@pytest.mark.parametrize("fault", [OSError("no libc"), "no symbol",
                                   "another clock"])
def test_a_probe_that_cannot_read_the_clock_adds_nothing(monkeypatch,
                                                         fault):
    import ctypes

    class NoSymbol:
        def __getattr__(self, name):
            raise AttributeError(name)

    def cdll(*_a, **_kw):
        if isinstance(fault, Exception):
            raise fault
        return NoSymbol()
    if fault == "another clock":
        monkeypatch.setattr(time, "get_clock_info", lambda _name: type(
            "info", (), {"implementation": "QueryPerformanceCounter()"}))
    else:
        monkeypatch.setattr(ctypes, "CDLL", cdll)
    monkeypatch.setattr(profiler, "_released_clock", profiler._UNRESOLVED)
    t = SpanTable(LOCK_SPANS)
    for _ in range(3):
        t.lock_probe("lock.reacquire")
    assert profiler._released_clock is None     # resolved once, to nothing
    assert t.snapshot() == {name: [0, 0, 0, 0] for name in LOCK_SPANS}


def test_long_waits_are_counted_beside_every_probe(monkeypatch):
    waits = iter([8_000, 900_000, 1_500_000, 5_000_000, 400])
    # the released reading lies this long before the one that follows
    monkeypatch.setattr(
        profiler, "_released_clock",
        lambda: time.perf_counter_ns() - next(waits))
    t = SpanTable(LOCK_SPANS)
    for _ in range(5):
        t.lock_probe("lock.reacquire")
    rows = t.snapshot()
    assert rows["lock.reacquire"][0] == 5
    # over 10 us: another thread ran in between
    assert rows["lock.reacquire.lost"][0] == 3
    assert rows["lock.reacquire.lost"][1] >= 900_000 + 6_500_000
    assert rows["lock.reacquire.long"][0] == 2      # over 1 ms alone
    assert 6_500_000 <= rows["lock.reacquire.long"][1] \
        <= rows["lock.reacquire"][1] < 8_000_000
    assert rows["lock.reacquire"][2] >= 5_000_000


def test_a_tally_is_one_threads_plain_share_of_its_row():
    t = SpanTable()
    t.add("row", 4, 1)
    mine, other = t.tally("row"), t.tally("row")
    mine.add(10)
    began = time.perf_counter_ns()
    closed = other.since(began)
    assert closed >= began
    n, total, longest, cpu = t.snapshot()["row"]
    assert (n, cpu) == (3, 1)
    assert total == 14 + (closed - began)
    assert longest == max(10, closed - began)
    assert t.tally("unseeded").n == 0 and t.snapshot()["unseeded"][0] == 0


def test_the_process_table_is_made_and_added_to_without_jax():
    import subprocess
    code = (
        "import sys\n"
        "from ray_tpu.observability import profiler\n"
        "t = profiler.process_table()\n"
        "assert t is profiler.process_table()\n"
        "assert set(t.snapshot()) == set(profiler.PROCESS_SPANS)\n"
        "t.add('actor.call.reply', 7)\n"
        "t.tally('replica.stream_next').add(9)\n"
        "t.lock_probe('lock.reacquire')\n"
        "rows = t.snapshot()\n"
        "assert rows['actor.call.reply'] == [1, 7, 7, 0], rows\n"
        "assert rows['replica.stream_next'] == [1, 9, 9, 0], rows\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n"
        "t.call('timed', int)    # the first annotation brings jax\n"
        "assert 'jax' in sys.modules\n"
        "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], timeout=120,
                         capture_output=True, text=True)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def test_a_collection_shows_in_the_gc_rows_until_unwatched():
    t = SpanTable()
    t.watch_gc()
    try:
        assert all(t.snapshot()[name] == [0, 0, 0, 0] for name in GC_SPANS)
        t.watch_gc()                            # one hook, however often
        assert gc.callbacks.count(t._on_gc) == 1
        gc.collect(0)
        young = t.snapshot()
        assert young["gc.pause"][0] >= 1
        full0 = young["gc.pause.full"][0]
        gc.collect()
        rows = t.snapshot()
        assert rows["gc.pause.full"][0] == full0 + 1
        assert rows["gc.pause"][0] >= young["gc.pause"][0] + 1
        assert 0 < rows["gc.pause.full"][2] <= rows["gc.pause"][1]

        # a collection may start on a thread that holds the very row
        # (a reader inside snapshot()): its pause is added all the same
        def collect_holding_the_row():
            with t._rows["gc.pause"].lock:
                gc.collect()
        th = threading.Thread(target=collect_holding_the_row, daemon=True)
        th.start()
        th.join(timeout=10)
        assert not th.is_alive()
        assert t.snapshot()["gc.pause.full"][0] == full0 + 2
    finally:
        t.unwatch_gc()
    assert t._on_gc not in gc.callbacks
    before = t.snapshot()
    gc.collect()
    assert t.snapshot() == before


def test_thread_clocks_leave_out_what_they_cannot_read():
    done = threading.Event()
    worker = threading.Thread(target=lambda: (_burn(20), done.wait(10)))
    worker.start()
    try:
        first = thread_clocks(me=[threading.current_thread()], nobody=[])
        _burn(10)
        while thread_clocks(w=[worker])["w"] < 20_000_000:
            time.sleep(0.001)       # its clock, read from this thread
        second = thread_clocks(me=[threading.current_thread()],
                               both=[threading.current_thread(), worker])
    finally:
        done.set()
        worker.join(timeout=10)
    assert not worker.is_alive()
    assert set(first) == {"me", "wall_ns"}      # no thread: no key
    assert second["me"] - first["me"] >= 10_000_000
    assert second["both"] >= second["me"] + 20_000_000
    assert second["wall_ns"] - first["wall_ns"] >= 10_000_000
    # a thread that has ended has taken its clock with it
    assert set(thread_clocks(gone=[worker])) == {"wall_ns"}


def test_nested_spans_store_self_time():
    t = SpanTable()
    t0 = time.perf_counter_ns()
    with t.span("outer"):
        time.sleep(0.01)
        with t.span("inner"):
            time.sleep(0.03)
            with t.span("innermost"):
                time.sleep(0.02)
    wall = time.perf_counter_ns() - t0
    rows = t.snapshot()
    assert rows["innermost"][1] >= 20_000_000
    assert rows["outer"][1] >= 10_000_000
    # a parent's row leaves out what its children covered, however long
    # the sleeps really took
    assert 30_000_000 <= rows["inner"][1] <= (
        wall - rows["innermost"][1] - 10_000_000)
    total = sum(r[1] for r in rows.values())
    assert wall * 0.95 <= total <= wall                  # they sum to it


def test_exception_inside_a_span_is_recorded_and_raised():
    t = SpanTable()
    with pytest.raises(KeyError):
        with t.span("outer"):
            with t.span("fails"):
                raise KeyError("boom")
    assert t.snapshot()["fails"][0] == 1
    assert t.snapshot()["outer"][0] == 1
    with t.span("after"):        # the thread's stack was unwound
        pass
    with t.span("outer"):
        pass
    rows = t.snapshot()
    assert rows["outer"][0] == 2 and rows["after"][0] == 1


def test_a_span_on_another_thread_is_no_child_of_this_one():
    t = SpanTable()

    def other():
        with t.span("other"):
            time.sleep(0.02)
    with t.span("mine"):
        th = threading.Thread(target=other)
        th.start()
        th.join(timeout=10)
        assert not th.is_alive()
    rows = t.snapshot()
    assert rows["other"][1] >= 20_000_000
    assert rows["mine"][1] >= rows["other"][1]     # nothing was taken off


def test_adds_from_more_threads_than_cores_lose_nothing():
    t = SpanTable()
    per_thread, n_threads = 2000, 32
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def hammer():
            for i in range(per_thread):
                t.add("stamp", 1 + i % 7)
        threads = [threading.Thread(target=hammer)
                   for _ in range(n_threads)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
            assert not th.is_alive()
    finally:
        sys.setswitchinterval(old)
    n, total, longest, cpu = t.snapshot()["stamp"]
    assert n == per_thread * n_threads and cpu == 0
    assert total == n_threads * sum(1 + i % 7 for i in range(per_thread))
    assert longest == 7


def test_a_compile_is_charged_to_the_span_open_on_its_thread():
    import jax
    import jax.numpy as jnp
    t = SpanTable()
    with t.span("compiling"):
        jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    with t.span("cached"):
        pass
    compiles = t.compiles()
    assert compiles["compiling"][0] >= 1 and compiles["compiling"][1] > 0
    assert "cached" not in compiles


# ---- a tiny paged engine with known lengths ------------------------------
@pytest.fixture(scope="module")
def tiny_llm():
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import Llama, LlamaConfig
    cfg = LlamaConfig(vocab_size=128, d_model=32, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_ff=64, max_seq_len=256, remat=False,
                      dtype=jnp.float32)
    model = Llama(cfg)
    return model, model.init_params(jax.random.PRNGKey(0))


def _engine(tiny_llm, **overrides):
    from ray_tpu.serve.llm import LLMEngine, LLMEngineConfig
    model, params = tiny_llm
    base = dict(max_slots=4, max_seq_len=128, prefill_buckets=(16, 32),
                max_prefill_batch=4, kv_page_size=16, pipeline_depth=DEPTH)
    base.update(overrides)
    return LLMEngine(model, params, LLMEngineConfig(**base))


def _host_events(trace_dir, prefix):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb"))[-1]
    return [(ev.name, dict(ev.stats))
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(prefix)]


@pytest.fixture(scope="module")
def drained(tiny_llm, tmp_path_factory):
    """Four prompts admitted in one pass into all four slots, eight
    tokens each, under a CPU capture; read after the engine drained."""
    import jax
    trace_dir = str(tmp_path_factory.mktemp("engine-trace"))
    eng = _engine(tiny_llm)
    started = time.perf_counter_ns()    # the loop thread has just started
    rids = []

    def submit_all():       # on the loop thread: one admission pass
        for n in PROMPT_LENS:
            rids.append(eng.submit(np.arange(1, n + 1),
                                   max_new_tokens=NEW_TOKENS))
    jax.profiler.start_trace(trace_dir)
    try:
        eng._run_on_loop(submit_all)
        outs = [list(eng.stream_detailed(rid)) for rid in rids]
        while eng.get_stats()["decode_steps"] < eng._decode_dispatches:
            time.sleep(0.01)        # the lagged steps drain
    finally:
        jax.profiler.stop_trace()
    stats = eng.get_stats()
    eng.shutdown()
    eng._loop_thread.join(timeout=30)
    assert not eng._loop_thread.is_alive()
    lived_ns = time.perf_counter_ns() - started
    return {"stats": stats, "spans": eng._spans.snapshot(), "outs": outs,
            "lived_ns": lived_ns, "trace_dir": trace_dir,
            "carried_leaves": len(jax.tree_util.tree_leaves(
                (eng._pools, eng._state))),
            "dispatches": eng._decode_dispatches,
            "metric": eng._m["tokens"].get(tags=eng._mtags)}


def test_prefill_counters_are_bucket_times_padded_group(drained):
    st = drained["stats"]
    assert st["prefills"] == st["prefill_rows_real"] == len(PROMPT_LENS)
    assert st["prefill_calls"] == 1 and st["prefill_rows_padded"] == 4
    assert st["prefill_tokens_real"] == sum(PROMPT_LENS)
    assert st["prefill_tokens_padded"] == 16 * 4
    assert st["prefill_shapes"] == {"16x4": 1}
    # no delta-rule layer, no chunk of a chunkwise form
    assert st["prefill_chunks_window"] == st["prefill_chunks_live"] == 0


def test_a_group_of_three_pads_to_four_rows(tiny_llm):
    eng = _engine(tiny_llm)
    rids = []

    def submit_all():
        for n in (20, 17, 30):
            rids.append(eng.submit(np.arange(1, n + 1), max_new_tokens=2))
    eng._run_on_loop(submit_all)
    for rid in rids:
        assert len(list(eng.stream(rid))) == 2
    st = eng.get_stats()
    eng.shutdown()
    assert st["prefill_rows_real"] == 3 and st["prefill_rows_padded"] == 4
    assert st["prefill_tokens_real"] == 67
    assert st["prefill_tokens_padded"] == 32 * 4
    assert st["prefill_shapes"] == {"32x4": 1}


def test_discarded_tokens_are_the_slot_steps_not_emitted(drained):
    st = drained["stats"]
    n = len(PROMPT_LENS)
    assert all(len(o) == NEW_TOKENS for o in drained["outs"])
    assert st["tokens_generated"] == n * NEW_TOKENS
    # the prefill emits each request's first token, decode rows the rest
    assert st["decode_tokens_emitted"] == n * (NEW_TOKENS - 1)
    assert st["decode_slot_steps"] == st["decode_steps"] * 4
    assert st["decode_steps"] == drained["dispatches"]
    # every slot was in every dispatched row: emitted or discarded
    assert st["decode_tokens_discarded"] == (
        st["decode_slot_steps"] - st["decode_tokens_emitted"])
    assert 0 < st["decode_tokens_discarded"] <= DEPTH * n


def test_request_stamps_count_requests_steps_and_tokens(drained):
    spans, st = drained["spans"], drained["stats"]
    assert spans["request.inflight_prefill"][0] == len(PROMPT_LENS)
    # every dispatched decode program was drained, each counted once
    assert st["decode_steps"] == drained["dispatches"] > 0
    assert spans["stream.deliver"][0] == sum(
        len(o) for o in drained["outs"])
    assert spans["slot.refill"][0] == 0         # no slot was used twice
    assert spans["request.ingress"][0] == 0     # direct submits
    assert "ingress_ms" not in st["ttft_breakdown_p50_ms"]
    assert set(st["ttft_breakdown_p50_ms"]) == {
        "queue_ms", "prefill_dispatch_ms", "emit_ms", "total_ms"}


def test_engine_phases_sum_to_the_loops_wall_time(drained):
    spans = drained["spans"]
    from ray_tpu.serve.llm.engine import _LOOP_SPANS
    assert set(_LOOP_SPANS) <= set(spans)
    phases = sum(spans[name][1] for name in _LOOP_SPANS)
    # the loop thread lives from the end of the engine's construction to
    # shutdown and spends all of it inside `engine.loop`
    assert abs(phases - drained["lived_ns"]) <= 0.02 * drained["lived_ns"]
    assert spans["engine.decode_dispatch"][0] == drained["dispatches"]
    assert spans["engine.prefill_dispatch"][0] == 1
    assert spans["engine.chunk_dispatch"][0] == 0
    assert spans["engine.idle_sleep"][0] > 0


def test_runtime_rows_count_the_loops_runtime_calls(drained):
    from ray_tpu.serve.llm.engine import _RUNTIME_SPANS
    spans, st = drained["spans"], drained["stats"]
    assert sum(spans[name][0] for name in _RUNTIME_SPANS) \
        == st["runtime_calls"] > 0
    # a program and a fetch a dispatch, and nothing eager between them
    assert spans["runtime.step"][0] == spans["runtime.fetch_start"][0] \
        == drained["dispatches"] + 1
    assert spans["runtime.other"][0] == 0
    # the donated leaves are released once a step call, outside its row
    assert spans["step.release"][0] == spans["runtime.step"][0]
    assert 0 < spans["step.release"][3] <= spans["step.release"][1]
    for name in _RUNTIME_SPANS:
        assert 0 <= spans[name][3] <= spans[name][1], name
    # the calls lie inside the dispatch phases and took nothing off them
    inside = sum(spans[name][1]
                 for name in _RUNTIME_SPANS + ("step.release",))
    assert 0 < inside <= (spans["engine.prefill_dispatch"][1]
                          + spans["engine.decode_dispatch"][1])
    # every phase has its CPU beside its wall time. A span's CPU reads
    # lie inside its wall reads, so a phase with no child holds
    # cpu <= wall whatever it does; what a child's reads cost falls to
    # its parent's CPU, so `engine.loop` (a dozen children and next to
    # no work of its own) is held only through the sum
    from ray_tpu.serve.llm.engine import _LOOP_SPANS
    for name in set(_LOOP_SPANS) - {"engine.loop", "engine.emit"}:
        assert 0 <= spans[name][3] <= spans[name][1], name
    assert 0 < sum(spans[name][3] for name in _LOOP_SPANS) \
        <= sum(spans[name][1] for name in _LOOP_SPANS)
    assert spans["engine.idle_sleep"][3] < 0.5 * spans["engine.idle_sleep"][1]
    assert spans["engine.decode_dispatch"][3] > 0


def test_the_engine_probes_every_eighth_step_call_and_counts_leaves(
        drained, tiny_llm):
    from ray_tpu.serve.llm.engine import _LOCK_PROBE_EVERY
    spans, st = drained["spans"], drained["stats"]
    calls = spans["runtime.step"][0]
    assert _LOCK_PROBE_EVERY == 8 and calls >= 8
    assert spans["lock.reacquire"][0] == calls // 8
    assert spans["lock.reacquire.long"][0] \
        <= spans["lock.reacquire.lost"][0] <= spans["lock.reacquire"][0]
    # two layers' K and V pools, lengths, last tokens, the key
    assert drained["carried_leaves"] == 2 * 2 + 3
    assert st["step_leaves_released"] \
        == spans["step.release"][0] * drained["carried_leaves"]
    # 24 more step calls: three more probes, wherever the count stood
    eng = _engine(tiny_llm)
    try:
        for _ in eng.stream(eng.submit(np.arange(1, 9),
                                       max_new_tokens=24)):
            pass
        while eng.get_stats()["decode_steps"] < eng._decode_dispatches:
            time.sleep(0.01)
        rows = eng.get_stats()["spans"]
        assert rows["lock.reacquire"][0] == rows["runtime.step"][0] // 8 \
            >= 3
    finally:
        eng.shutdown()


def test_a_fresh_engine_seeds_every_row_and_its_threads(tiny_llm):
    from ray_tpu.serve.llm.engine import (_LOOP_SPANS, _REQUEST_SPANS,
                                          _RUNTIME_SPANS, _STEP_SPANS)
    eng = _engine(tiny_llm)
    try:
        st = eng.get_stats()
        assert set(st["spans"]) >= set(
            _LOOP_SPANS + _REQUEST_SPANS + _RUNTIME_SPANS + _STEP_SPANS
            + GC_SPANS + LOCK_SPANS + PROCESS_SPANS)
        assert "request.inflight_decode" not in st["spans"]
        assert {"runtime.step", "runtime.fetch_start", "runtime.other",
                "step.release", "gc.pause", "gc.pause.full",
                "slot.refill.starved"} <= set(st["spans"])
        assert all(len(row) == 4 for row in st["spans"].values())
        # nobody streams from an event loop yet: no key, no guess
        assert set(st["threads"]) == {"engine", "wall_ns"}

        async def consume():
            rid = eng.submit(np.arange(1, 9), max_new_tokens=3)
            return [tok async for tok, _lp in eng.astream_detailed(rid)]
        loop_thread_cpu = {}

        def on_a_loop():
            assert len(asyncio.run(consume())) == 3
            loop_thread_cpu.update(eng.get_stats()["threads"])
        th = threading.Thread(target=on_a_loop)
        th.start()
        th.join(timeout=60)
        assert not th.is_alive()
        assert set(loop_thread_cpu) == {"engine", "consumers", "wall_ns"}
        assert loop_thread_cpu["consumers"] > 0
        assert loop_thread_cpu["engine"] >= st["threads"]["engine"]
        assert loop_thread_cpu["wall_ns"] > st["threads"]["wall_ns"]
        # that loop's thread has ended and taken its clock with it
        assert set(eng.get_stats()["threads"]) == {"engine", "wall_ns"}
    finally:
        eng.shutdown()


def test_the_engine_counts_collections_until_shutdown(tiny_llm):
    eng = _engine(tiny_llm)
    try:
        before = eng.get_stats()["spans"]
        gc.collect()
        after = eng.get_stats()["spans"]
        for name in GC_SPANS:
            assert after[name][0] >= before[name][0] + 1, name
            assert after[name][1] > before[name][1], name
        assert after["gc.pause.full"][2] > 0
    finally:
        eng.shutdown()
    assert eng._spans._on_gc not in gc.callbacks
    after = eng._spans.snapshot()
    gc.collect()
    assert eng._spans.snapshot()["gc.pause"] == after["gc.pause"]


def test_compiles_are_named_by_the_phase_that_compiled(drained):
    st = drained["stats"]
    assert st["compiles"]["engine.prefill_dispatch"] >= 1
    assert st["compiles"]["engine.decode_dispatch"] >= 1
    assert set(st["compile_ns"]) == set(st["compiles"])
    assert not any(k.startswith(("request.", "stream."))
                   for k in st["compiles"])


def test_token_metric_total_is_unchanged_by_batched_updates(drained):
    assert drained["metric"] == drained["stats"]["tokens_generated"]


def test_capture_holds_decode_dispatch_events_with_their_step(drained):
    events = _host_events(drained["trace_dir"], "engine.")
    names = {name for name, _ in events}
    assert {"engine.loop", "engine.admit", "engine.prefill_dispatch",
            "engine.decode_dispatch", "engine.drain_wait",
            "engine.emit"} <= names
    steps = sorted(stats["step"] for name, stats in events
                   if name == "engine.decode_dispatch")
    assert steps == list(range(1, drained["dispatches"] + 1))
    prefill = [s for name, s in events if name == "engine.prefill_dispatch"]
    assert prefill == [{"bucket": 16, "group": 4, "group_padded": 4}]
    # only the engine thread annotates: no per-request event can take
    # a device idle gap from the loop's phases, nor one of the
    # consumers' loop
    for prefix in ("request.", "stream.", "lock.", "actor.", "replica.",
                   "consumer."):
        assert not _host_events(drained["trace_dir"], prefix), prefix


def test_capture_holds_the_runtime_calls_inside_their_dispatch(drained):
    """`SpanTable.call` annotates: each step call, release and fetch
    start is an event of its row's name on the engine thread's line,
    inside the dispatch phase that made it."""
    from jax.profiler import ProfileData
    path = sorted(glob.glob(
        f"{drained['trace_dir']}/plugins/profile/*/*.xplane.pb"))[-1]
    named = ("runtime.step", "step.release", "runtime.fetch_start")
    counted = dict.fromkeys(named, 0)
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                      for ev in line.events]
            phases = [ev for ev in events if ev[0] in (
                "engine.prefill_dispatch", "engine.decode_dispatch")]
            for name, start, end in events:
                if name in named:
                    counted[name] += 1
                    assert any(p[1] <= start and end <= p[2]
                               for p in phases), (name, start)
    spans = drained["spans"]
    assert counted == {name: spans[name][0] for name in named}
    assert counted["step.release"] == drained["dispatches"] + 1


def test_slot_refill_counts_each_reuse_of_a_slot(tiny_llm):
    eng = _engine(tiny_llm, max_slots=2)
    rids = [eng.submit(np.arange(1, 9), max_new_tokens=3) for _ in range(5)]
    for rid in rids:
        assert len(list(eng.stream(rid))) == 3
    spans = eng.get_stats()["spans"]
    eng.shutdown()
    assert spans["slot.refill"][0] == 3          # five requests, two slots
    assert spans["slot.refill"][1] > 0


def test_slot_refill_is_split_at_the_requests_arrival(tiny_llm):
    eng = _engine(tiny_llm, max_slots=1)
    try:
        # the second request waits in the engine while the first decodes:
        # the freed slot stood empty for the engine's own reasons
        rids = [eng.submit(np.arange(1, 9), max_new_tokens=3)
                for _ in range(2)]
        for rid in rids:
            assert len(list(eng.stream(rid))) == 3
        spans = eng.get_stats()["spans"]
        assert spans["slot.refill"][0] == 1 and spans["slot.refill"][1] > 0
        assert spans["slot.refill.starved"][:3] == [1, 0, 0]
        # the third arrives 50 ms after the slot fell free: the slot
        # starved for that long, and was refilled soon after
        while eng.get_stats()["free_slots"] < 1:
            time.sleep(0.005)
        time.sleep(0.05)
        assert len(list(eng.stream(
            eng.submit(np.arange(1, 9), max_new_tokens=3)))) == 3
        spans = eng.get_stats()["spans"]
    finally:
        eng.shutdown()
    refill, starved = spans["slot.refill"], spans["slot.refill.starved"]
    assert refill[0] == starved[0] == 2
    assert 50_000_000 <= starved[1] == starved[2] <= refill[2]
    assert starved[1] <= refill[1]


def test_ingress_is_recorded_only_for_a_stamped_submit(tiny_llm):
    eng = _engine(tiny_llm)
    list(eng.stream(eng.submit(np.arange(1, 9), max_new_tokens=2)))
    assert eng.get_stats()["spans"]["request.ingress"][0] == 0
    list(eng.stream(eng.submit(np.arange(1, 9), max_new_tokens=2,
                               recv_ts=time.time() - 0.05)))
    st = eng.get_stats()
    eng.shutdown()
    n, total, _, _ = st["spans"]["request.ingress"]
    assert n == 1 and 50_000_000 <= total < 5_000_000_000
    assert st["ttft_breakdown_p50_ms"]["ingress_ms"] >= 50.0


# ---- through serve.run and the HTTP proxy --------------------------------
def _factory():
    import jax
    from ray_tpu.models import Llama, LlamaConfig
    cfg = LlamaConfig(vocab_size=128, d_model=32, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_ff=64, max_seq_len=128, remat=False)
    model = Llama(cfg)
    return model, model.init_params(jax.random.PRNGKey(0))


def test_ingress_is_stamped_by_the_proxy_and_read_by_the_engine(rt):
    from ray_tpu import serve
    from ray_tpu.serve.http_proxy import start_proxy
    from ray_tpu.serve.llm import build_openai_deployment
    handle = serve.run(build_openai_deployment(
        _factory, engine_config={"max_slots": 2, "max_seq_len": 64,
                                 "prefill_buckets": (16,),
                                 "kv_page_size": 16},
        model_name="tiny"), name="spans-app", route_prefix="/v1")
    try:
        _proxy, port = start_proxy(port=0)
        for stream in (False, True):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/v1/completions",
                data=json.dumps({"prompt": [1, 2, 3, 4], "max_tokens": 3,
                                 "stream": stream}).encode(),
                headers={"Content-Type": "application/json"})
            deadline = time.time() + 60
            while True:
                try:
                    with urllib.request.urlopen(req, timeout=120) as r:
                        assert r.status == 200 and r.read()
                    break
                except urllib.error.HTTPError as e:
                    # a new proxy answers 404 until it has the routes; a
                    # request it turns away never reaches the engine
                    if e.code != 404 or time.time() > deadline:
                        raise
                    time.sleep(0.25)
        st = handle.stats.remote(None).result(timeout_s=60)
        n, total, longest, _ = st["spans"]["request.ingress"]
        assert n == 2 and 0 < longest <= total < 60_000_000_000
        assert st["ttft_breakdown_p50_ms"]["ingress_ms"] > 0
        assert st["spans"]["stream.deliver"][0] == 6
        # a call on the handle did not come through a proxy: no stamp
        out = handle.remote({"prompt": [1, 2, 3], "max_tokens": 2}).result(
            timeout_s=60)
        assert out["usage"]["completion_tokens"] == 2
        st = handle.stats.remote(None).result(timeout_s=60)
        assert st["spans"]["request.ingress"][0] == 2
        assert st["spans"]["request.inflight_prefill"][0] == 3
    finally:
        serve.shutdown()


# ---- the trainer's loop ----------------------------------------------------
def test_trainer_phases_are_spans_of_fit(tmp_path):
    from ray_tpu.parallel import MeshSpec
    from ray_tpu.train import RunConfig, SpmdTrainer, SpmdTrainerConfig

    rng = np.random.RandomState(0)

    def data():
        while True:
            yield {"tokens": rng.randint(0, 255, (8, 16))}

    tr = SpmdTrainer(
        SpmdTrainerConfig(model="llama-debug", mesh=MeshSpec(dp=8),
                          total_steps=5, log_every=2, warmup_steps=1),
        data, run_config=RunConfig(name="spans", storage_path=str(tmp_path)))
    assert tr.spans is None
    res = tr.fit()
    assert res.metrics["step"] == 5
    rows = tr.spans.snapshot()
    assert rows["train.step"][0] == 5
    assert rows["train.report"][0] == 3         # steps 2, 4 and the last
    assert rows["train.next_batch"][0] == 4     # none drawn past the end
    assert tr.spans.compiles()["train.step"][0] >= 1
