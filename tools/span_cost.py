"""What the always-on instrumentation costs on this machine's host.

`SpanTable` (observability/profiler.py) has no switch, so its price is
paid by every decode step and every `stream_next` reply. This prints,
in ns, loops of 10^5-10^6 of each piece around no-ops:

  clock, cpu_clock   `time.perf_counter_ns()`, `time.thread_time_ns()`
  add, tally         a locked row update; one thread's plain integers
  span, call, probe  an empty span; `call` of a no-op (annotated);
                     `lock_probe` with no rival for the lock
  reply              one `stream_next` reply's stamps and tallies, in
                     the order the actor loop makes them
                     (core/worker.py:_run_actor_task_async around
                     serve/replica.py:stream_next, one chunk's
                     `put_chunk`, 1/61 of a `consumer.deliver`: 61
                     items a hand-over at saturation), against the same
                     loop without them
  step               one step call's share on the engine thread
                     (serve/llm/engine.py:_step and _start_fetch: three
                     annotated calls, the leaf counter, a probe every
                     8th) against the same calls timed without
                     annotation, probe or counter

A host number of whichever machine runs it, never a device metric:

    python -m tools.span_cost [--out chiprun_out/x.json]
"""
from __future__ import annotations

import argparse
import json
import time

from ray_tpu.observability.profiler import SpanTable

_now = time.perf_counter_ns


def _noop():
    return None


def _loop(n, body):
    """ns an iteration of `body()` over n, the empty loop taken off."""
    t0 = _now()
    for _ in range(n):
        pass
    empty = _now() - t0
    t0 = _now()
    for _ in range(n):
        body()
    return (_now() - t0 - empty) / n


def _reply_bare():
    _noop()     # resolve
    _noop()     # stream_next before the wait
    _noop()     # ... and after it
    _noop()     # reply
    _noop()     # telemetry
    _noop()     # a chunk's put


def _reply_circuit(table: SpanTable):
    resolve, reply, telemetry, s_next, s_put = (
        table.tally(name) for name in (
            "actor.call.resolve", "actor.call.reply",
            "actor.call.telemetry", "replica.stream_next",
            "replica.stream_put"))

    def body():         # the clock spelt as the instrumented code does
        began = time.perf_counter_ns()      # _run_actor_task_async
        _noop()
        resolve.since(began)
        t0 = time.perf_counter_ns()         # stream_next
        _noop()
        t1 = time.perf_counter_ns()
        waited = time.perf_counter_ns() - t1            # the await
        _noop()
        s_next.add(time.perf_counter_ns() - t0 - waited)
        began = time.perf_counter_ns()      # back in the coroutine
        _noop()
        began = reply.since(began)
        _noop()
        telemetry.since(began)
        t0 = time.perf_counter_ns()         # _drain's put_chunk
        _noop()
        s_put.since(t0)

    def deliver():      # _LoopSink.deliver: once in ~61 replies
        t0 = time.perf_counter_ns()
        table.add("consumer.deliver", time.perf_counter_ns() - t0)
    return body, deliver


def _step_circuits(table: SpanTable):
    def call_bare(name, fn):        # `call` as it was before PR 54
        t0 = _now()
        c0 = time.thread_time_ns()
        try:
            return fn()
        finally:
            cpu = time.thread_time_ns() - c0
            table.add(name, _now() - t0, cpu)

    def bare():
        call_bare("runtime.step", _noop)
        call_bare("step.release", _noop)
        call_bare("runtime.fetch_start", _noop)
    state = {"calls": 0, "leaves": 0}

    def instrumented():
        table.call("runtime.step", _noop)
        state["calls"] += 1
        if state["calls"] % 8 == 0:
            table.lock_probe("lock.reacquire")
        state["leaves"] += 35
        table.call("step.release", _noop)
        table.call("runtime.fetch_start", _noop)
    return bare, instrumented


def measure(n: int = 200_000) -> dict:
    table = SpanTable(["row"])
    tally = table.tally("row")

    def span():
        with table.span("row"):
            pass
    span()      # the first span imports jax
    out = {
        "clock_ns": _loop(n, _now),
        "cpu_clock_ns": _loop(n, time.thread_time_ns),
        "add_ns": _loop(n, lambda: table.add("row", 5)),
        "tally_ns": _loop(n, lambda: tally.add(5)),
        "span_ns": _loop(n, span),
        "call_ns": _loop(n, lambda: table.call("row", _noop)),
        "probe_ns": _loop(n, lambda: table.lock_probe("lock.reacquire")),
    }
    probes = table.snapshot()["lock.reacquire"]
    out["probe_wait_mean_ns"] = probes[1] / max(1, probes[0])
    reply, deliver = _reply_circuit(table)
    out["reply_ns"] = (_loop(n, reply) - _loop(n, _reply_bare)
                       + _loop(n, deliver) / 61)
    step_bare, step = _step_circuits(table)
    out["step_ns"] = _loop(n, step) - _loop(n, step_bare)
    return {k: round(v, 1) for k, v in out.items()}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=200_000)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    result = measure(args.n)
    print(json.dumps(result))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f)


if __name__ == "__main__":
    main()
