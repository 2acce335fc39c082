"""Size a change to the engine's host loop on a CPU before any chip time.

A toy Llama (d_model 32, 1 layer, vocabulary 512) behind the serving
cells' engine shape (64 slots, 64-token pages, pipeline_depth 10 as the
ceiling of what the loop keeps in flight), 64
streams kept open, and five ways of consuming them. The engine does the
same work in every row; what differs is how many threads a token wakes,
and with it how long the engine thread waits for the interpreter lock
at each of its JAX calls:

  threads    one thread per stream, blocked in engine.stream(rid)
  executor   one asyncio loop, run_in_executor(None, next, it) per token
             (serve/replica.py before PR 25)
  handover   one asyncio loop, engine.astream_detailed: one
             call_soon_threadsafe per engine drain (serve/replica.py now)
  poll       one thread polling every queue without blocking (the floor)
  replica    `handover` through a serve Replica object: OpenAIServer's
             SSE chunks, stream_start / stream_next on one loop (all of
             the chip's path but the actor call's reply)

Prints, per consumer, decode steps per second, the engine thread's calls
into the JAX runtime per decode step (get_stats()["runtime_calls"]: two a
dispatch when nothing eager stands between two programs), the mean
number of programs the loop left in flight (`depth`) and the self
time of each `engine.*` phase in ms per decode step (deltas of
get_stats()["spans"]).
These are CPU numbers of a toy: they rank host-loop designs and are
never a device metric (PERF.md section 5).

    JAX_PLATFORMS=cpu python -m tools.handoff_rehearsal [--seconds 10]
"""
from __future__ import annotations

import argparse
import asyncio
import queue
import random
import threading
import time

import numpy as np

STREAMS = 64
PHASES = ("decode_dispatch", "decode_prep", "emit", "deliver", "admit",
          "prefill_dispatch")


def _toy_model():
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import Llama, LlamaConfig
    cfg = LlamaConfig(vocab_size=512, d_model=32, n_layers=1, n_heads=4,
                      n_kv_heads=2, d_ff=64, max_seq_len=1024,
                      remat=False, dtype=jnp.float32)
    model = Llama(cfg)
    return model, model.init_params(jax.random.PRNGKey(0))


def build_replica():
    """A serve Replica object hosting OpenAIServer over the toy engine,
    in this process; the other consumers reach its engine directly."""
    from ray_tpu.core import serialization
    from ray_tpu.serve.llm.openai_api import OpenAIServer
    from ray_tpu.serve.replica import Replica
    return Replica(
        "rehearsal", "rehearsal#0", serialization.dumps_call(OpenAIServer),
        (_toy_model,), dict(engine_config=dict(
            max_slots=STREAMS, max_seq_len=1024, kv_page_size=64,
            kv_pool_tokens=49152, pipeline_depth=10,
            prefill_buckets=(64, 128, 256), max_prefill_batch=4,
            logprobs=False)),
        max_ongoing_requests=STREAMS)


def lengths(rng):
    return rng.randint(64, 200), rng.randint(128, 384)


def submit(eng, rng) -> str:
    prompt_len, new_tokens = lengths(rng)
    return eng.submit(np.ones((prompt_len,), np.int32),
                      max_new_tokens=new_tokens)


# ---- the consumers: each keeps STREAMS streams open until `stop` ----
def consume_threads(eng, stop):
    def one(seed):
        rng = random.Random(seed)
        while not stop.is_set():
            for _ in eng.stream(submit(eng, rng)):
                pass
    threads = [threading.Thread(target=one, args=(i,), daemon=True)
               for i in range(STREAMS)]
    for t in threads:
        t.start()
    return threads


def _on_loop(eng, stop, one):
    async def main():
        await asyncio.gather(*(one(random.Random(i))
                               for i in range(STREAMS)))
    t = threading.Thread(target=lambda: asyncio.run(main()), daemon=True)
    t.start()
    return [t]


def consume_executor(eng, stop):
    end = object()

    async def one(rng):
        loop = asyncio.get_running_loop()
        while not stop.is_set():
            it = eng.stream(submit(eng, rng))
            while await loop.run_in_executor(None, next, it, end) \
                    is not end:
                pass
    return _on_loop(eng, stop, one)


def consume_handover(eng, stop):
    async def one(rng):
        while not stop.is_set():
            async for _ in eng.astream_detailed(submit(eng, rng)):
                pass
    return _on_loop(eng, stop, one)


def consume_poll(eng, stop):
    def poll():
        rng = random.Random(0)
        rids = [submit(eng, rng) for _ in range(STREAMS)]
        while not stop.is_set():
            for i, rid in enumerate(rids):
                q = eng._requests[rid].sink.q
                try:
                    while True:
                        if q.get_nowait()[0] != "token":
                            eng._requests.pop(rid, None)
                            rids[i] = submit(eng, rng)
                            break
                except queue.Empty:
                    pass
            time.sleep(0.001)
    t = threading.Thread(target=poll, daemon=True)
    t.start()
    return [t]


def consume_replica(eng, stop, rep):
    async def one(rng):
        while not stop.is_set():
            prompt_len, new_tokens = lengths(rng)
            sid = await rep.stream_start("__call__", ({
                "prompt": [1] * prompt_len, "max_tokens": new_tokens,
                "temperature": 0.0, "stream": True},), {})
            done = False
            while not done and not stop.is_set():
                _chunks, done = await rep.stream_next(sid)
            if not done:
                await rep.stream_cancel(sid)
    return _on_loop(eng, stop, one)


CONSUMERS = {"threads": consume_threads, "executor": consume_executor,
             "handover": consume_handover, "poll": consume_poll,
             "replica": consume_replica}


def run(rep, name: str, seconds: float, ramp_s: float) -> dict:
    """One consumer against the shared engine: ramp, then the deltas of
    get_stats() over `seconds`, then abort what is open and go idle."""
    eng = rep._callable.engine
    stop = threading.Event()
    extra = (rep,) if name == "replica" else ()
    threads = CONSUMERS[name](eng, stop, *extra)
    time.sleep(ramp_s)
    s0, t0 = eng.get_stats(), time.time()
    time.sleep(seconds)
    s1, t1 = eng.get_stats(), time.time()
    stop.set()
    while any(t.is_alive() for t in threads):
        for rid in list(eng._requests):
            eng.abort(rid)
        time.sleep(0.05)
    steps = s1["decode_steps"] - s0["decode_steps"]
    row = {"steps_per_s": steps / (t1 - t0),
           "occupancy": (s1["decode_tokens_emitted"]
                         - s0["decode_tokens_emitted"]) / max(
                             1, steps * STREAMS),
           "compiles": sum(s1["compiles"].values())
           - sum(s0["compiles"].values())}
    for ph in PHASES:
        a, b = s0["spans"][f"engine.{ph}"], s1["spans"][f"engine.{ph}"]
        row[ph] = (b[1] - a[1]) / 1e6 / max(1, steps)
    for k in ("deliver_batches", "deliver_items",
              "deliver_blocking_tokens"):
        row[k] = s1[k] - s0[k]
    row["calls_per_step"] = (s1["runtime_calls"]
                             - s0["runtime_calls"]) / max(1, steps)
    row["depth"] = ((s1["decode_inflight_target_sum"]
                     - s0["decode_inflight_target_sum"])
                    / max(1, s1["decode_inflight_target_n"]
                          - s0["decode_inflight_target_n"]))
    return row


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--ramp", type=float, default=4.0)
    ap.add_argument("--consumers", default=",".join(CONSUMERS))
    args = ap.parse_args()
    rep = build_replica()
    eng = rep._callable.engine
    # every program the traffic can reach compiles here, not in a row
    warm = run(rep, "poll", 12.0, 12.0)
    print(f"warm-up compiled {sum(eng.get_stats()['compiles'].values())} "
          f"programs ({warm['compiles']} in its second half)")
    print(f"{'consumer':<10}{'steps/s':>9}{'occ':>6}{'calls/step':>12}"
          f"{'depth':>7}" + "".join(f"{p:>17}" for p in PHASES)
          + f"{'sum':>8}"
          + f"{'items/batch':>13}{'blocking':>10}{'compiles':>10}")
    for name in args.consumers.split(","):
        r = run(rep, name, args.seconds, args.ramp)
        total = sum(r[p] for p in PHASES)
        per = r["deliver_items"] / max(1, r["deliver_batches"])
        print(f"{name:<10}{r['steps_per_s']:>9.1f}{r['occupancy']:>6.2f}"
              f"{r['calls_per_step']:>12.2f}{r['depth']:>7.2f}"
              + "".join(f"{r[p]:>17.2f}" for p in PHASES)
              + f"{total:>8.1f}{per:>13.1f}"
              + f"{r['deliver_blocking_tokens']:>10d}"
              + f"{r['compiles']:>10d}", flush=True)
    eng.shutdown()


if __name__ == "__main__":
    main()
