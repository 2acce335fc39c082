"""The benchmark's side of the replica: a subclass of the program's
OpenAIServer, deployed through `build_llm_deployment(server_cls=...)`.
It is how the benchmark's own code reaches the one process that owns the
chip: it makes the weights there, warms the cell's shapes, reads
`get_stats()`, starts and stops `jax.profiler`, reduces the trace and
runs the reference comparison. It changes nothing of how a request is
served: `/v1/completions` goes through OpenAIServer untouched.
"""
from __future__ import annotations

import os
import threading
import time
from typing import Any, Dict, List

import numpy as np

from ray_tpu.serve.llm.openai_api import OpenAIServer

from . import modelcfg

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def model_factory(cfg: dict, seed: int):
    """Runs inside the replica: (model, params), bf16 weights made on the
    replica's device in one jitted call from the seed."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import Llama
    from ray_tpu.util.jaxenv import enable_compile_cache
    enable_compile_cache()
    # persist every program, also those that compile in under a second
    # (the engine's small eager ops): each run is a new process
    if jax.default_backend() == "tpu":
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    model = Llama(modelcfg.llama_config(cfg, param_dtype=jnp.bfloat16))
    key = jax.random.PRNGKey(np.uint32(int(seed) % (2 ** 32)))
    params = jax.jit(lambda k: model.init(
        k, jnp.zeros((1, 8), jnp.int32))["params"])(key)
    jax.block_until_ready(params)
    return model, params


class BenchServer(OpenAIServer):

    def __init__(self, model_factory, engine_config=None, tokenizer=None,
                 cached_prefixes=None, model_name="bench", bench=None):
        import jax
        self._compiles: List[tuple] = []     # (time, seconds)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        t0 = time.time()
        super().__init__(model_factory, engine_config, tokenizer,
                         cached_prefixes=cached_prefixes,
                         model_name=model_name)
        self._bench = bench or {}
        self._built_s = time.time() - t0

    def _on_duration(self, event: str, seconds: float, **_kw):
        if event == _COMPILE_EVENT:
            self._compiles.append((time.time(), seconds))

    # ---- what the harness calls (one positional argument each) ----------
    def bench_info(self, _body=None) -> Dict[str, Any]:
        import jax
        dev = jax.devices()[0]
        mem = dev.memory_stats() or {}
        return {"device": dict(self.engine.device),
                "memory_peak_bytes": mem.get("peak_bytes_in_use"),
                "memory_in_use_bytes": mem.get("bytes_in_use"),
                "memory_limit_bytes": mem.get("bytes_limit"),
                "built_s": self._built_s, "pid": os.getpid(),
                "cache_dir": jax.config.jax_compilation_cache_dir,
                "compiles": len(self._compiles),
                "compile_s": sum(s for _t, s in self._compiles)}

    def bench_stats(self, _body=None) -> Dict[str, Any]:
        out = self.engine.get_stats()
        out["at"] = time.time()
        out["compile_times"] = [t for t, _s in self._compiles]
        return out

    def bench_warm(self, spec: Dict[str, Any]) -> Dict[str, Any]:
        """Run every program the cell's traffic can reach, and no other:
        for each prefill bucket a synchronised group of 1, 2, 3 and 4
        prompts (the engine pads a group to a power of two, and its
        eager bookkeeping ops are shaped by the real group size), and
        one short generation in every decode window. Groups are
        submitted from the engine's own loop thread, between two steps,
        so that one admission pass sees the whole group."""
        eng = self.engine
        t0, n0 = time.time(), len(self._compiles)
        took: List[list] = []           # [what, seconds, compiles]
        for length, group in spec["prefill_groups"]:
            t1, n1 = time.time(), len(self._compiles)
            rids: List[str] = []

            def submit_all(length=length, group=group, rids=rids):
                for _ in range(group):
                    rids.append(eng.submit(
                        np.ones((length,), np.int32), max_new_tokens=2))
            eng._run_on_loop(submit_all)
            for rid in rids:
                for _ in eng.stream(rid):
                    pass
            took.append([f"prefill {length}x{group}", time.time() - t1,
                         len(self._compiles) - n1])
        for length, new_tokens in spec["decode_windows"]:
            t1, n1 = time.time(), len(self._compiles)
            rid = eng.submit(np.ones((length,), np.int32),
                             max_new_tokens=new_tokens)
            for _ in eng.stream(rid):
                pass
            took.append([f"decode {length}+{new_tokens}", time.time() - t1,
                         len(self._compiles) - n1])
        return {"seconds": time.time() - t0,
                "compiles": len(self._compiles) - n0,
                "compile_s": sum(s for _t, s in self._compiles[n0:]),
                "steps": took,
                "prefill_compile_ms": dict(eng._prefill_compile_ms)}

    def bench_quiesce(self, _body=None) -> Dict[str, Any]:
        """After the window: the clients are gone, so abort what they
        left behind and wait until the engine is idle."""
        eng = self.engine
        t0 = time.time()
        left = list(eng._requests)
        for rid in left:
            eng.abort(rid)
        idle_since = None
        while time.time() - t0 < 120:
            s = eng.get_stats()
            if s["active"] or s["waiting"] or s["prefilling"]:
                idle_since = None
                for rid in list(eng._requests):
                    eng.abort(rid)
            elif idle_since is None:
                idle_since = time.time()
            elif time.time() - idle_since > 0.5:
                break       # the proxy's stragglers have stopped coming
            time.sleep(0.05)
        return {"aborted": len(left), "seconds": time.time() - t0}

    def bench_arm(self, spec: Dict[str, Any]) -> float:
        """Before traffic starts: the times (this machine's epoch clock)
        at which to read `get_stats()` and to start and stop the
        profiler. A thread of this process does it, so that nothing of
        the harness competes with the traffic for a request slot inside
        the window."""
        self._armed = {"stats0": None, "stats1": None, "trace": None,
                       "contexts": [], "error": None}
        self._arm_thread = threading.Thread(
            target=self._armed_run, args=(spec,), daemon=True,
            name="bench-sampler")
        self._arm_thread.start()
        return time.time()

    def _contexts(self) -> List[int]:
        """Context length (tokens in the cache) of every decoding slot,
        from the engine's own host mirror; empty where a later engine
        keeps it elsewhere."""
        eng = self.engine
        lens = dict(getattr(eng, "_disp_len", {}) or {})
        return [int(lens[s]) for s in list(getattr(eng, "_active", {}))
                if s in lens]

    def _armed_run(self, spec: Dict[str, Any]) -> None:
        import jax
        out = self._armed

        def until(t):
            d = t - time.time()
            if d > 0:
                time.sleep(d)

        try:
            # the trace covers the last seconds of the window and is
            # stopped only after the closing reading of get_stats():
            # stop_trace works for tens of seconds in this process, and
            # that must fall after the window, not into it
            events = [(spec["t0"], 0, "stats0"), (spec["t1"], 1, "stats1")]
            tr = spec.get("trace")
            if tr:
                begin = spec["t1"] - tr["seconds"]
                events += [(begin, 0, "start"),
                           (begin + 0.5 * tr["seconds"], 0, "contexts"),
                           (spec["t1"], 2, "stop")]
            for t, _order, what in sorted(events):
                until(t)
                if what in ("stats0", "stats1"):
                    out[what] = self.bench_stats()
                elif what == "contexts":
                    out["contexts"] = self._contexts()
                elif what == "start":
                    os.makedirs(tr["dir"], exist_ok=True)
                    opts = jax.profiler.ProfileOptions()
                    # the Python tracer stamps every call of every thread
                    # of this process and slows the engine loop severalfold
                    opts.python_tracer_level = 0
                    jax.profiler.start_trace(tr["dir"],
                                             profiler_options=opts)
                    out["trace"] = {"dir": tr["dir"], "started": time.time()}
                else:
                    out["trace"]["stop_called"] = time.time()
                    jax.profiler.stop_trace()
                    out["trace"]["stopped"] = time.time()
        except BaseException as e:  # noqa: BLE001  reported by collect
            out["error"] = repr(e)

    def bench_collect(self, _body=None) -> Dict[str, Any]:
        self._arm_thread.join(timeout=120)
        return dict(self._armed, alive=self._arm_thread.is_alive())

    def bench_trace_reduce(self, spec: Dict[str, Any]) -> Dict[str, Any]:
        """After the window: the benchmark's own reduction of the trace,
        run here because this process has the file and the library."""
        from . import trace_reduce
        return trace_reduce.reduce_dir(spec["dir"], **spec.get("args", {}))

    def bench_check(self, spec: Dict[str, Any]) -> Dict[str, Any]:
        from . import checks
        return checks.serve_check(self.engine, spec)
