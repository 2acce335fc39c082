"""ray_tpu.serve.llm — LLM serving on the continuous-batching engine.

Reference parity: the fork's `serve.llm` vLLM integration
(build_llm_deployment / LLMServer): one replica owns the TPU chip and an
LLMEngine; requests stream tokens via the serve streaming path.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, Optional

import numpy as np

from ..deployment import Application, deployment_decorator
from .engine import LLMEngine, LLMEngineConfig
from .guided import (GuidedSpec, TokenFSM, compile_guided,
                     json_schema_to_regex)


class LLMServer:
    """Deployment class wrapping an LLMEngine.

    `model_factory` is a zero-arg callable returning (model, params) —
    kept as a factory so weights load inside the replica process (on the
    TPU host), not in the driver.

    `cached_prefixes`: shared prompt prefixes (strings or token lists,
    e.g. the system prompt) registered on the engine at startup; any
    request whose prompt starts with one adopts its KV instead of
    re-prefilling it (engine prefix caching).

    Matching is TOKEN-level (correctness is never at risk — a miss
    just pays the normal full prefill). For STRING prefixes under a
    BPE tokenizer, prefer passing token ids that align with how full
    prompts tokenize: a merge across the prefix/suffix boundary (or a
    chat template) makes encode(prefix) not a token-prefix of
    encode(prefix + suffix) and the cache silently never matches —
    watch the engine's `prefix_tokens_saved` stat to confirm hits.
    """

    def __init__(self, model_factory, engine_config: Optional[dict] = None,
                 tokenizer: Optional[Any] = None,
                 cached_prefixes: Optional[list] = None):
        model, params = model_factory()
        engine_config = dict(engine_config or {})
        if cached_prefixes:
            engine_config.setdefault("max_prefixes",
                                     len(cached_prefixes))
        cfg = LLMEngineConfig(**engine_config)
        self.engine = LLMEngine(model, params, cfg)
        self.tokenizer = tokenizer
        import threading
        self._prefix_lock = threading.Lock()
        self._prefix_keys = {}          # affinity key -> engine pid
        self._prefix_inflight = set()   # keys mid-registration
        self._cached_prefixes = []      # (tokens, pid), longest first
        for p in cached_prefixes or []:
            ids = np.asarray(self._encode(p), np.int32).reshape(-1)
            pid = self.engine.register_prefix(ids)
            self._cached_prefixes.append((ids, pid))
        self._cached_prefixes.sort(key=lambda t: -t[0].size)

    def _match_prefix(self, prompt):
        """(submit_prompt, prefix_id): strip the longest registered
        prefix the prompt starts with; the engine re-attaches its
        tokens but adopts its KV by copy."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        with self._prefix_lock:
            prefixes = list(self._cached_prefixes)
        for ids, pid in prefixes:
            if prompt.size > ids.size and np.array_equal(
                    prompt[:ids.size], ids):
                return prompt[ids.size:], pid
        return prompt, None

    def register_prefix(self, body: Dict[str, Any]) -> Dict[str, Any]:
        """Dynamic shared-prefix registration (scale-out router path):
        the serve controller pushes `serve.register_prefix(...)`
        payloads here — to the affinity ring owner at registration time
        and to every replica started afterwards. body: {"prefix":
        str | [token ids], "key": affinity key}. Idempotent per key;
        requires engine_config max_prefixes > 0 (the engine's KV slots
        for warm prefixes)."""
        key = body.get("key") or ""
        prefix = body["prefix"]
        ids = np.asarray(self._encode(prefix), np.int32).reshape(-1)
        with self._prefix_lock:
            pid = self._prefix_keys.get(key) if key else None
            if pid is not None:
                return {"key": key, "prefix_id": int(pid),
                        "prefix_tokens": int(ids.size)}
            if key in self._prefix_inflight:
                # a concurrent push (controller re-warm racing the
                # _check_started push) is already prefilling this key —
                # don't burn a second engine prefix slot on it
                return {"key": key, "prefix_id": -1, "pending": True}
            self._prefix_inflight.add(key)
        try:
            # the prefill can take seconds cold — never under the lock
            # (the request path's _match_prefix reads under it)
            pid = self.engine.register_prefix(ids)
        finally:
            with self._prefix_lock:
                self._prefix_inflight.discard(key)
        with self._prefix_lock:
            if key:
                self._prefix_keys[key] = pid
            self._cached_prefixes.append((ids, pid))
            self._cached_prefixes.sort(key=lambda t: -t[0].size)
        return {"key": key, "prefix_id": int(pid),
                "prefix_tokens": int(ids.size)}

    def _encode(self, prompt):
        if isinstance(prompt, str):
            if self.tokenizer is None:
                raise ValueError(
                    "text prompt but no tokenizer configured; pass token "
                    "ids or set tokenizer=")
            return self.tokenizer.encode(prompt)
        return prompt

    def _decode_tok(self, tok: int):
        if self.tokenizer is not None:
            return self.tokenizer.decode([tok])
        return tok

    def __call__(self, body: Dict[str, Any]):
        """Unary or streaming generate. body: {"prompt": [ids] | str,
        "max_tokens": int, "temperature": float, "top_p": float,
        "stop_token_ids": [ids], "stream": bool}."""
        from ..context import get_request_deadline, get_request_recv_ts
        prompt, prefix_id = self._match_prefix(
            self._encode(body["prompt"]))
        max_tokens = body.get("max_tokens")
        temperature = float(body.get("temperature", 0.0))
        rid = self.engine.submit(
            prompt, max_tokens, temperature,
            top_p=float(body.get("top_p", 1.0)),
            stop_token_ids=body.get("stop_token_ids"),
            prefix_id=prefix_id,
            deadline_ts=get_request_deadline(),
            recv_ts=get_request_recv_ts())
        if body.get("stream"):
            # an async generator, for the loop this call runs on (the
            # replica's stream_start) to consume: tokens arrive by the
            # engine's hand-over, no thread parks for the stream
            tokens = self.engine.astream_detailed(rid)

            async def agen():
                async with contextlib.aclosing(tokens):
                    async for tok, _lp in tokens:
                        yield self._decode_tok(tok)
            return agen()
        toks = list(self.engine.stream(rid))
        if self.tokenizer is not None:
            return {"text": self.tokenizer.decode(toks), "tokens": toks}
        return {"tokens": toks}

    def generate(self, body: Dict[str, Any]):
        return self(body)

    def stats(self, _body=None) -> Dict[str, Any]:
        return self.engine.get_stats()

    def autoscale_metrics(self) -> Dict[str, Any]:
        """Replica.get_autoscale_metrics hook: the live engine signals
        the serve autoscaler's SLO terms key on (queue depth, TTFT/TPOT,
        KV-page utilization) plus prefix-cache savings for the router's
        affinity accounting."""
        s = self.engine.get_stats()
        out: Dict[str, Any] = {
            "queue_depth": float(s.get("waiting", 0) or 0),
            "active_slots": float(s.get("active", 0) or 0),
            "prefix_tokens_saved": float(
                s.get("prefix_tokens_saved", 0) or 0),
        }
        kv = s.get("kv_pages") or {}
        if kv.get("total"):
            out["kv_util"] = kv["in_use"] / max(kv["total"], 1)
        ttft = s.get("ttft_breakdown_p50_ms") or {}
        if ttft.get("total_ms") is not None:
            out["ttft_p50_ms"] = float(ttft["total_ms"])
        if s.get("tpot_p50_ms") is not None:
            out["tpot_ms"] = float(s["tpot_p50_ms"])
        return out

    def check_health(self):
        if not self.engine._loop_thread.is_alive():
            raise RuntimeError("engine loop died")
        if self.engine.wedged:
            from ...exceptions import EngineWedgedError
            raise EngineWedgedError(
                "wedged: engine loop made no forward progress past "
                "its watchdog window; replica must be replaced")


def build_llm_deployment(model_factory, *, engine_config=None,
                         tokenizer=None, name: str = "LLMServer",
                         num_replicas: int = 1,
                         max_ongoing_requests: int = 32,
                         cached_prefixes=None,
                         server_cls=None, server_kwargs=None,
                         ray_actor_options: Optional[dict] = None,
                         route_prefix: str = "/") -> Application:
    """Build a ready-to-run LLM serving app:
    `serve.run(build_llm_deployment(factory,
    ray_actor_options={"num_tpus": 1}))`. `server_cls` swaps the
    deployment class (e.g. openai_api.OpenAIServer); `cached_prefixes`
    registers shared prompt prefixes for engine prefix caching.

    `ray_actor_options` is how a replica gets its chip: a replica whose
    options request no TPU runs in a worker pinned to the CPU (one owner
    per chip, util/jaxenv.py)."""
    dep = deployment_decorator(
        server_cls or LLMServer, name=name, num_replicas=num_replicas,
        max_ongoing_requests=max_ongoing_requests,
        ray_actor_options=ray_actor_options,
        route_prefix=route_prefix)
    return dep.bind(model_factory, engine_config=engine_config,
                    tokenizer=tokenizer,
                    cached_prefixes=cached_prefixes,
                    **(server_kwargs or {}))


def __getattr__(name):
    if name in ("OpenAIServer", "build_openai_deployment"):
        from . import openai_api
        return getattr(openai_api, name)
    raise AttributeError(name)


__all__ = ["LLMEngine", "LLMEngineConfig", "GuidedSpec",
           "json_schema_to_regex",
           "TokenFSM", "compile_guided", "LLMServer",
           "build_llm_deployment", "OpenAIServer",
           "build_openai_deployment"]
