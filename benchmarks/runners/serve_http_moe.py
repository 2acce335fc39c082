"""Runner of the serve cells whose model is a sparse-expert decoder
(OLMoE through models/mixtral.py): `serve_http`'s path, end to end, with
the server class, the model factory and the reference of
`harness/replica_olmoe.py`. `serve_http.run` names its server class and
factory itself, so this runner carries its own `run`; everything else
(warm-up plan, load driver, checks, summaries, shutdown) is imported from
`serve_http`, not copied.
"""
from __future__ import annotations

import asyncio
import functools
import json
import os
import shutil
import sys

from ..harness import client, schedule, window
from ..harness.client import CLOCK
from .serve_http import (_checks, _client_summary, _drive, _fail, _plain,
                         _shutdown, _sweep_point, warm_spec)

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
    from ..harness.replica_olmoe import (OlmoeBenchServer, model_factory,
                                         model_section, olmoe_preset)
    # before anything is started: a program that lacks the preset fails
    # here, at once, with the reason
    olmoe_preset()

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
            engine_config=engine_cfg, server_cls=OlmoeBenchServer,
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
        "traffic": traffic, "model": model_section(cfg),
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
