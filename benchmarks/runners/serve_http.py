"""Runner of the serve cells: the documented path, end to end.

`ray_tpu.init` -> `serve.run(build_llm_deployment(..., server_cls=
BenchServer, ray_actor_options={"num_tpus": 1}))` -> `start_proxy` ->
open-loop `POST /v1/completions` with `"stream": true` from this
process, which never touches a JAX backend (one owner per chip).

Set-up (all of it counted in `setup_s`): runtime and replica start,
weights made on the device from the seed, warm-up of the cell's own
shapes from inside the replica, then the traffic ramp. The window opens
`ramp_s` after traffic starts and lasts `--seconds`.
"""
from __future__ import annotations

import asyncio
import functools
import json
import os
import shutil
import statistics
import sys
import time
from typing import List

import numpy as np

from ..harness import client, modelcfg, schedule, window
from ..harness.client import CLOCK


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def warm_spec(reqs: List[schedule.Request], engine: dict) -> dict:
    """The programs this schedule can reach: prefill buckets by the
    prompts' lengths (groups of 1-4 each), decode windows by every total
    length a request passes through."""
    buckets = sorted(engine["prefill_buckets"])
    ps, max_len = engine["kv_page_size"], engine["max_seq_len"]
    pages_per_slot = -(-max_len // ps)

    def bucket(n):
        return next(b for b in buckets if n <= b)

    def decode_window(length):        # engine._decode_window_pages
        w = _next_pow2(-(-(length + 1) // ps))
        return 0 if w >= pages_per_slot else w

    reached = sorted({bucket(r.prompt_len) for r in reqs})
    groups = [[b, g] for b in reached
              for g in range(1, engine["max_prefill_batch"] + 1)]
    lo = min(r.prompt_len for r in reqs)
    hi = max(r.prompt_len + r.max_tokens for r in reqs)
    max_prompt = max(r.prompt_len for r in reqs)
    wins, decode = [], []
    for length in range(lo, hi + 1):
        w = decode_window(length)
        if w not in wins:
            # the first total length in this window: reach it from the
            # longest prompt of the cell that is not past it
            wins.append(w)
            p = min(length, max_prompt)
            decode.append([p, max(2, length - p + 2)])
    return {"prefill_groups": groups, "decode_windows": decode,
            "windows": wins}


def _worker_pids() -> list:
    me, out = os.getpid(), []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmd = f.read()
            with open(f"/proc/{pid}/stat") as f:
                fields = f.read().rsplit(")", 1)[1].split()
        except OSError:
            continue
        if (b"ray_tpu.core.worker" in cmd and int(fields[1]) == me
                and fields[0] != "Z"):
            out.append(int(pid))
    return out


async def _drive(host, port, reqs, payloads, traffic, seconds, arm,
                 trace_spec):
    """Ramp, window, drain. Returns (load, t0, t1)."""
    loop = asyncio.get_running_loop()
    origin = CLOCK() + 0.5
    t0 = origin + traffic["ramp_s"]
    t1 = t0 + seconds
    to_epoch = time.time() - CLOCK()
    spec = {"t0": t0 + to_epoch, "t1": t1 + to_epoch}
    if trace_spec:
        spec["trace"] = trace_spec
    await loop.run_in_executor(None, arm, spec)
    load = client.LoadRun(host, port, [r.due_s for r in reqs], payloads,
                          origin)
    await load.send_all(until=t1)
    # drain: until every request due in the window has its first token,
    # but no longer than the traffic file allows
    await asyncio.sleep(max(0.0, t1 - CLOCK()))
    due = [i for i, r in enumerate(reqs)
           if t0 <= origin + r.due_s < t1]
    deadline = t1 + traffic["drain_s"]
    while CLOCK() < deadline:
        due = [i for i in due if not load.first_token_seen(i)]
        if not due:
            break
        await asyncio.sleep(0.05)
    load.stop()
    await asyncio.sleep(0.05)
    return load, t0, t1


def run(ctx: dict):
    cfg, traffic = ctx["config"], ctx["traffic"]
    seconds, seed, rehearse = ctx["seconds"], ctx["seed"], ctx["rehearse"]
    root = ctx["root"]
    phases = {}
    mark = ctx["t_start"]

    def phase(name):
        nonlocal mark
        now = CLOCK()
        phases[name] = now - mark
        mark = now

    # workers import `benchmarks.harness.replica` by name
    os.environ["PYTHONPATH"] = os.pathsep.join(
        [root] + [p for p in os.environ.get("PYTHONPATH", "").split(
            os.pathsep) if p])
    # the compile cache: the program's own rule (util/jaxenv.py) puts a
    # TPU worker's at JAX_COMPILATION_CACHE_DIR if that is set and at
    # <checkout>/.jax_cache otherwise: a fixed path inside the checkout

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.http_proxy import start_proxy
    from ray_tpu.serve.llm import build_llm_deployment
    from ..harness.replica import BenchServer, model_factory

    ray_tpu.init()
    try:
        chips = int(ray_tpu.cluster_resources().get("TPU", 0))
        if not rehearse and chips < ctx["cell"]["chips"]:
            print(f"benchmark: the runtime counts {chips} TPU chip(s), the "
                  f"cell needs {ctx['cell']['chips']}", file=sys.stderr)
            return _fail(serve, ray_tpu)
        engine_cfg = dict(cfg["engine"])
        engine_cfg["prefill_buckets"] = tuple(engine_cfg["prefill_buckets"])
        app = build_llm_deployment(
            functools.partial(model_factory, cfg, seed),
            engine_config=engine_cfg, server_cls=BenchServer,
            server_kwargs={"model_name": cfg["name"]},
            max_ongoing_requests=cfg["deployment"]["max_ongoing_requests"],
            ray_actor_options={"num_tpus": 1} if chips else None,
            route_prefix="/v1", name="BenchServer")
        handle = serve.run(app, name="bench", route_prefix="/v1",
                           wait_for_ready_timeout_s=1100)
        _proxy, port = start_proxy(port=0)
        host = "127.0.0.1"
        phase("runtime_replica_weights_s")

        def call(method, arg=None, timeout_s=1150):
            return getattr(handle, method).remote(arg).result(
                timeout_s=timeout_s)

        info = call("bench_info")
        device = info["device"]
        if not rehearse:
            from ..harness.peaks import peaks_for
            if device["platform"] != "tpu":
                print(f"benchmark: the replica runs on {device}",
                      file=sys.stderr)
                return _fail(serve, ray_tpu)
            peaks = peaks_for(device["kind"])
        else:
            peaks = None

        rates = ctx["sweep"] or [None]
        vocab = cfg["vocab_size"]
        # warm for the fastest schedule of the call (the same lengths at
        # every rate; only the horizon differs)
        reqs = schedule.build(traffic, seed, seconds, rates[0])
        warm = warm_spec(reqs, cfg["engine"])
        warmed = call("bench_warm", warm)
        phase("warm_up_s")
        # the service path too: a few requests one after another through
        # proxy, router and handle, so that whatever the first calls on
        # that path cost (channels, a replica held suspect for its 10 s)
        # is paid here and not inside the ramp
        shortest = min(reqs, key=lambda r: r.prompt_len)
        primed = []
        for _ in range(3):
            t = CLOCK()
            ans = client.post_once(host, port, {
                "prompt": shortest.prompt(seed, vocab).tolist(),
                "max_tokens": 2, "temperature": 0.0, "stream": True})
            primed.append({"seconds": CLOCK() - t, "error": ans["error"]})
        phase("service_prime_s")

        trace_dir = os.path.join(root, ".bench_out",
                                 "trace-" + ctx["cell"]["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)

        def arm(spec):
            return call("bench_arm", spec, 60)

        curve = []
        for rate in rates:
            reqs = schedule.build(traffic, seed, seconds, rate)
            meta = [{"prompt_len": r.prompt_len, "max_tokens": r.max_tokens}
                    for r in reqs]
            payloads = [client.encode_request(host, port, {
                "prompt": r.prompt(seed, vocab).tolist(),
                "max_tokens": r.max_tokens, "temperature": 0.0,
                "stream": True}, traffic["request_timeout_s"])
                for r in reqs]
            phase("schedule_s")
            load, t0, t1 = asyncio.run(_drive(
                host, port, reqs, payloads, traffic, seconds, arm,
                dict(traffic["trace"], dir=trace_dir)
                if ctx["trace"] else None))
            setup_s = t0 - ctx["t_start"]
            phases["ramp_s"] = traffic["ramp_s"]
            mark = CLOCK()
            streams = load.streams(meta)
            quiet = call("bench_quiesce", None, 300)
            armed = call("bench_collect", None, 300)
            if armed["error"] or armed["alive"] or not armed["stats1"]:
                raise SystemExit(f"benchmark: the replica's sampler "
                                 f"failed: {armed}")
            stats0, stats1 = armed["stats0"], armed["stats1"]
            if ctx["sweep"]:
                curve.append(_sweep_point(rate, traffic, streams, t0, t1,
                                          seconds, stats0, stats1))
                print(json.dumps(curve[-1]), flush=True)
        if ctx["sweep"]:
            if ctx["out"]:
                os.makedirs(ctx["out"], exist_ok=True)
                with open(os.path.join(
                        ctx["out"], f"sweep.{ctx['cell']['name']}.json"),
                        "w") as f:
                    json.dump({"cell": ctx["cell"]["name"], "seed": seed,
                               "seconds": seconds, "device": device,
                               "curve": curve}, f, indent=1)
            _shutdown(serve, ray_tpu)
            return None

        checks = _checks(call, host, port, cfg, traffic, seed, streams)
        correct = all(v["ok"] if isinstance(v, dict) else v
                      for v in checks.values())
        attempted, failed = window.attempted_failed(streams, t0, t1)
        trace = None
        if ctx["trace"]:
            trace = call("bench_trace_reduce", {"dir": trace_dir}, 600)
            trace["traced_s"] = traffic["trace"]["seconds"]
            shutil.rmtree(trace_dir, ignore_errors=True)
        info = call("bench_info")
        phase("checks_s")
    except BaseException:
        _shutdown(serve, ray_tpu)
        raise
    _shutdown(serve, ray_tpu)
    phase("shutdown_s")

    device_out = dict(device, memory_peak_bytes=info["memory_peak_bytes"])
    late = [(s.sent - s.due) * 1000.0 for s in window.due_in(streams, t0, t1)
            if s.sent is not None]
    errors = sorted({s.error for s in streams if s.error})[:5]
    in_window = [t for t in stats1["compile_times"]
                 if stats0["at"] <= t <= stats1["at"]]
    return {
        "kind": "serve", "streams": streams, "t0": t0, "t1": t1,
        "seconds": seconds, "setup_s": setup_s, "stats0": stats0,
        "stats1": stats1, "trace": trace, "config": cfg,
        "traffic": traffic, "model": modelcfg.model_section(cfg),
        "late_ms": late, "peaks": peaks, "device": device_out,
        "compiles_in_window": len(in_window),
        "trace_contexts": armed["contexts"],
        "backlog_end": sum(1 for s in streams if s.due < t1
                           and not any(t <= t1 for t in s.token_times)),
        "correct": correct, "attempted": attempted, "failed": failed,
        "checks": checks, "phases": phases,
        "detail": {"warm": warmed, "warm_spec": warm, "info": info,
                   "primed": primed,
                   "client": _client_summary(streams, t0, t1,
                                             traffic["ramp_s"]),
                   "errors": errors, "quiesce": quiet,
                   "stats0": _plain(stats0), "stats1": _plain(stats1),
                   "trace": trace, "trace_times": armed["trace"],
                   "offered": schedule.offered(reqs)},
    }


def _checks(call, host, port, cfg, traffic, seed, streams) -> dict:
    """Outside the window, on an idle engine: one seeded greedy prompt
    sent twice over HTTP (the same answer both times, of exactly the
    length asked for), the reference comparison on that prompt and
    answer, and every answer the server itself ended in the run being
    as long as its request asked."""
    rng = np.random.default_rng([int(seed), 99])
    prompt = rng.integers(1, cfg["vocab_size"],
                          traffic["check"]["prompt_len"]).tolist()
    body = {"prompt": prompt, "temperature": 0.0, "stream": True,
            "max_tokens": traffic["check"]["new_tokens"]}
    first = client.post_once(host, port, body)
    again = client.post_once(host, port, body)
    return {
        "reference": call("bench_check", {
            "model": modelcfg.model_section(cfg), "check": cfg["check"],
            "prompt": prompt, "generated": first["tokens"]}, 600),
        "greedy_repeatable": bool(
            first["tokens"] and first["tokens"] == again["tokens"]
            and first["error"] is None),
        "check_length_exact": len(first["tokens"])
        == traffic["check"]["new_tokens"],
        "output_lengths_exact": not any(
            s.done and len(s.token_times) != s.max_tokens
            for s in streams),
    }


def _client_summary(streams, t0, t1, ramp_s) -> dict:
    """For the run's detail file: the tails beside the judged numbers and
    tokens received in each second from the start of traffic, where a
    stall of the service shows at a glance."""
    ttft = window.ttft_ms(streams, t0, t1)
    gaps = window.gaps_ms(streams, t0, t1)
    start = t0 - ramp_s
    per_s = [0] * (int(t1 - start) + 1)
    for s in streams:
        for t in s.token_times:
            if start <= t < t1:
                per_s[int(t - start)] += 1
    return {"ttft_ms": {q: window.percentile(ttft, q) for q in (50, 95, 99)}
            if ttft else None,
            "gap_ms": {q: window.percentile(gaps, q) for q in (50, 95, 99)}
            if gaps else None,
            "gap_mean_ms": statistics.fmean(gaps) if gaps else None,
            "gap_count": len(gaps), "tokens_per_second": per_s}


def _plain(stats: dict) -> dict:
    return {k: v for k, v in stats.items() if k != "compile_times"}


def _sweep_point(rate, traffic, streams, t0, t1, seconds, stats0, stats1):
    attempted, failed = window.attempted_failed(streams, t0, t1)
    ttft = window.ttft_ms(streams, t0, t1)
    gaps = window.gaps_ms(streams, t0, t1)
    offered_tok = sum(s.max_tokens for s in window.due_in(streams, t0, t1))
    return {"rate_rps": rate if rate is not None else traffic["rate_rps"],
            "seconds": seconds, "attempted": attempted, "failed": failed,
            "offered_tok_s": offered_tok / seconds,
            "out_tok_s": window.tokens_in_window(streams, t0, t1) / seconds,
            "ttft_p50_ms": window.percentile(ttft, 50) if ttft else None,
            "ttft_p95_ms": window.percentile(ttft, 95) if ttft else None,
            "itl_p95_ms": window.percentile(gaps, 95) if gaps else None,
            "backlog_end": sum(1 for s in streams if s.due < t1
                               and not any(t <= t1 for t in s.token_times)),
            "engine_tok_s": (stats1["tokens_generated"]
                             - stats0["tokens_generated"])
            / max(stats1["at"] - stats0["at"], 1e-9),
            "engine_waiting_end": stats1.get("waiting"),
            "active_end": stats1.get("active")}


def _shutdown(serve, ray_tpu):
    try:
        serve.shutdown()
    finally:
        ray_tpu.shutdown()
    deadline = time.time() + 60
    while _worker_pids() and time.time() < deadline:
        time.sleep(0.2)
    for pid in _worker_pids():
        try:
            os.kill(pid, 9)
        except ProcessLookupError:
            pass


def _fail(serve, ray_tpu):
    _shutdown(serve, ray_tpu)
    sys.stdout.flush()
    os._exit(3)
