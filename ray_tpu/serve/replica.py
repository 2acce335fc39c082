"""Replica actor: hosts one copy of a deployment's callable.

Reference parity: python/ray/serve/_private/replica.py (request handling,
ongoing-request accounting, health checks, reconfigure, streaming) —
re-shaped for the ray_tpu runtime: one actor per replica, async
`handle_request` running on the worker's persistent asyncio loop, and a
poll-based streaming protocol (`stream_next`) instead of gRPC streams.
The streaming protocol lives on that loop too: a stream's drain task
fills a bounded buffer and `stream_next` awaits it, so an open stream
costs a task and a future, not a parked thread.
"""
from __future__ import annotations

import asyncio
import collections
import inspect
import itertools
import threading
import time
from typing import Any, Dict, Optional

from ..exceptions import DeadlineExceededError, ReplicaDrainingError
from ..observability.profiler import process_table

_STREAM_END = "__ray_tpu_stream_end__"


class _StreamBuffer:
    """Bounded buffer between one stream's drain task (`put`) and its
    consumer's `stream_next` calls (`wait`, then `items`), both on the
    actor's event loop — so no lock, and one waiter a side."""

    def __init__(self, maxsize: int = 1024):
        self.items: collections.deque = collections.deque()
        self._maxsize = maxsize
        self._room: Optional[asyncio.Future] = None    # a parked put
        self._ready: Optional[asyncio.Future] = None   # a parked wait

    @staticmethod
    def _wake(fut: Optional[asyncio.Future]) -> None:
        if fut is not None and not fut.done():
            fut.set_result(None)

    async def put(self, item) -> None:
        """Never blocks the event loop: a slow consumer parks the drain
        task here (and a cancelled stream's task is cancelled here)."""
        while len(self.items) >= self._maxsize:
            self._room = asyncio.get_running_loop().create_future()
            try:
                await self._room
            finally:
                self._room = None
        self.items.append(item)
        self._wake(self._ready)

    async def wait(self, timeout_s: float) -> bool:
        """Until there is an item; False after timeout_s without one. A
        second poll of one stream (a caller that gave up on its first)
        takes over: the first then answers "nothing yet" at once."""
        if self.items:
            return True
        self._wake(self._ready)
        fut = self._ready = asyncio.get_running_loop().create_future()
        try:
            await asyncio.wait_for(fut, timeout_s)
        except asyncio.TimeoutError:
            pass
        finally:
            if self._ready is fut:
                self._ready = None
        return bool(self.items)

    def pop(self):
        item = self.items.popleft()
        self._wake(self._room)
        return item


class Replica:
    """The actor class the controller instantiates per replica.

    Wraps either a user class (instantiated with init args) or a plain
    function. All requests land on `handle_request`; generators/async
    generators are exposed through `stream_start`/`stream_next` so HTTP
    proxies and handles can pull token-by-token.
    """

    def __init__(self, deployment_name: str, replica_id: str,
                 callable_bytes: bytes, init_args, init_kwargs,
                 user_config: Optional[Dict[str, Any]] = None,
                 max_ongoing_requests: int = 5):
        from .. import core  # noqa: F401  (ensures runtime symbols loaded)
        from ..core import serialization
        self._deployment_name = deployment_name
        self._replica_id = replica_id
        self._max_ongoing = max_ongoing_requests
        self._ongoing = 0
        self._total_served = 0
        self._lock = threading.Lock()
        self._draining = False
        # chaos-injection state (serve/chaos.py): deterministic fault
        # modes for the fault-tolerance tests; all default off
        self._chaos_delay_s = 0.0
        self._chaos_health_mode = ""   # "" | "fail" | "hang" | "wedged"
        self._streams: Dict[str, _StreamBuffer] = {}
        self._stream_counter = itertools.count()
        # drain tasks still pumping, by stream id: stream_cancel cancels
        # the task of a consumer that hung up, wherever it is parked (on
        # the handler's next chunk or on a full buffer), instead of
        # leaking the buffer and a permanently-elevated _ongoing count
        self._drains: Dict[str, asyncio.Task] = {}
        # the streaming calls' own time on the actor's event loop
        # (wall, this loop's thread alone adds): both synchronous parts
        # of a `stream_next` call, once a call; a chunk's `buf.put`
        self._next_row = process_table().tally("replica.stream_next")
        self._put_row = process_table().tally("replica.stream_put")

        target = serialization.loads_call(callable_bytes)
        if inspect.isclass(target):
            self._callable = target(*init_args, **init_kwargs)
            self._is_function = False
        else:
            self._callable = target
            self._is_function = True
        if user_config is not None:
            self.reconfigure(user_config)

    # ---- lifecycle --------------------------------------------------------
    def ready(self) -> str:
        """Readiness probe: returns once __init__ (and any model load in
        the user ctor) has completed."""
        return self._replica_id

    def health_check(self) -> bool:
        if self._chaos_health_mode == "hang":
            time.sleep(3600)           # probe times out controller-side
        if self._chaos_health_mode == "fail":
            raise RuntimeError("chaos: health check failing")
        if self._chaos_health_mode == "wedged":
            from ..exceptions import EngineWedgedError
            raise EngineWedgedError("chaos: wedged")
        user_check = getattr(self._callable, "check_health", None)
        if user_check is not None:
            user_check()
        return True

    def reconfigure(self, user_config: Dict[str, Any]) -> None:
        fn = getattr(self._callable, "reconfigure", None)
        if fn is not None:
            fn(user_config)

    def prepare_for_shutdown(self) -> int:
        """Graceful drain: stop admitting new requests (they raise the
        retriable ReplicaDrainingError and fail over) and report the
        in-flight count so the controller can wait for it to hit zero.
        Counts BOTH running handlers (_ongoing) and streams whose
        consumer is still pulling buffered chunks (_streams keeps the
        id until the consumer reads the end marker or cancels) —
        _ongoing alone drops when the PRODUCER finishes, which would
        let the controller kill us mid-consumer-read. Idempotent; the
        controller re-calls it as its drain poll."""
        with self._lock:
            self._draining = True
            return self._ongoing + len(self._streams)

    def chaos(self, mode: str, seconds: float = 0.0) -> bool:
        """Deterministic fault injection (serve/chaos.py; tests only).
        Modes: "delay" (every request sleeps `seconds` first),
        "health_fail" / "health_hang" / "health_wedged" (health probe
        fails / blocks / raises EngineWedgedError), "wedge" (stall the
        hosted LLM engine's loop for `seconds` — real watchdog path),
        "die" (hard-exit the replica process), "reset" (clear all)."""
        if mode == "delay":
            self._chaos_delay_s = float(seconds)
        elif mode in ("health_fail", "health_hang", "health_wedged"):
            self._chaos_health_mode = mode.split("_", 1)[1]
        elif mode == "wedge":
            engine = getattr(self._callable, "engine", None)
            if engine is None:
                raise ValueError("replica hosts no LLM engine to wedge")
            engine._chaos_stall(float(seconds))
        elif mode == "die":
            import os
            os._exit(1)
        elif mode == "reset":
            self._chaos_delay_s = 0.0
            self._chaos_health_mode = ""
        else:
            raise ValueError(f"unknown chaos mode {mode!r}")
        return True

    def _admit(self, kwargs) -> tuple:
        """Shared admission gate for unary + stream paths: reject while
        draining (retriable — the handle fails over), shed requests
        whose propagated deadline already expired, and apply the chaos
        delay. Returns the request's stamps for serve/context.py: its
        absolute deadline and the proxy's receipt time (each or None)."""
        deadline_ts = kwargs.pop("__serve_deadline_ts", None)
        recv_ts = kwargs.pop("__serve_recv_ts", None)
        if self._draining:
            raise ReplicaDrainingError(
                f"replica {self._replica_id} is draining")
        if deadline_ts is not None and time.time() >= deadline_ts:
            self._shed("deadline_expired")
            raise DeadlineExceededError(
                f"deadline expired {time.time() - deadline_ts:.3f}s "
                f"before admission on {self._replica_id}")
        if self._chaos_delay_s > 0:
            time.sleep(self._chaos_delay_s)
        return deadline_ts, recv_ts

    def _shed(self, reason: str) -> None:
        from ..util import events as events_mod
        events_mod.emit_safe("serve.request.shed",
                             counter="ray_tpu_serve_requests_shed_total",
                             counter_tags={"reason": reason},
                             replica_id=self._replica_id,
                             deployment=self._deployment_name,
                             reason=reason)

    def shutdown_user_callable(self) -> None:
        fn = getattr(self._callable, "__del__", None)
        del fn  # user __del__ runs when the process exits; nothing to do

    # ---- metrics ----------------------------------------------------------
    def get_metrics(self) -> Dict[str, Any]:
        with self._lock:
            return {"replica_id": self._replica_id,
                    "ongoing": self._ongoing,
                    "total": self._total_served,
                    "max_ongoing": self._max_ongoing}

    def get_queue_len(self) -> int:
        with self._lock:
            return self._ongoing

    def get_autoscale_metrics(self) -> Dict[str, Any]:
        """Live load sample for the controller's autoscaler/scale-down
        victim selection: in-flight handlers + undrained streams, plus
        whatever the hosted callable exposes via an `autoscale_metrics`
        hook (LLMServer reports engine queue depth, TTFT/TPOT, and
        KV-page utilization through it)."""
        with self._lock:
            out: Dict[str, Any] = {"replica_id": self._replica_id,
                                   "ongoing": self._ongoing,
                                   "streams": len(self._streams),
                                   "total": self._total_served,
                                   "ts": time.time()}
        hook = getattr(self._callable, "autoscale_metrics", None)
        if callable(hook):
            try:
                engine = hook()
                if isinstance(engine, dict):
                    out["engine"] = engine
            except Exception:  # noqa: BLE001  telemetry must not fail
                pass
        return out

    # ---- request path -----------------------------------------------------
    def _resolve_method(self, method_name: str):
        if self._is_function:
            if method_name not in ("__call__", None):
                raise AttributeError(
                    f"function deployment has no method {method_name!r}")
            return self._callable
        return getattr(self._callable, method_name or "__call__")

    async def handle_request(self, method_name: str, args, kwargs) -> Any:
        """Unary request. Runs user coroutines on the worker loop; sync
        handlers run in the default executor so they don't block the loop
        (and so max_ongoing_requests > 1 gives real concurrency)."""
        stamps = self._admit(kwargs)
        with self._lock:
            self._ongoing += 1
        try:
            mux_id = kwargs.pop("__serve_multiplexed_model_id", "")
            from .context import _set_request_stamps
            from .multiplex import _set_multiplexed_model_id
            method = self._resolve_method(method_name)
            if inspect.iscoroutinefunction(method):
                if mux_id:
                    _set_multiplexed_model_id(mux_id)
                _set_request_stamps(*stamps)
                result = await method(*args, **kwargs)
            else:
                def _call_sync():
                    # contextvar set inside the executor thread: plain
                    # run_in_executor does not propagate context.
                    if mux_id:
                        _set_multiplexed_model_id(mux_id)
                    _set_request_stamps(*stamps)
                    return method(*args, **kwargs)
                loop = asyncio.get_running_loop()
                result = await loop.run_in_executor(None, _call_sync)
                if inspect.iscoroutine(result):
                    result = await result
            if inspect.isgenerator(result) or inspect.isasyncgen(result):
                raise TypeError(
                    "handler returned a generator; call it via the "
                    "streaming path (handle.options(stream=True))")
            return result
        finally:
            with self._lock:
                self._ongoing -= 1
                self._total_served += 1

    # ---- streaming path ---------------------------------------------------
    async def stream_start(self, method_name: str, args, kwargs) -> str:
        """Start a streaming call; returns a stream id to poll with
        stream_next(). The generator is drained on a background task and
        chunks buffered, so slow consumers don't stall the handler. An
        async generator (an LLM stream: serve/llm) is iterated on this
        loop; a plain generator is stepped on the default executor."""
        stamps = self._admit(kwargs)
        stream_id = f"{self._replica_id}-s{next(self._stream_counter)}"
        buf = self._streams[stream_id] = _StreamBuffer()
        with self._lock:
            self._ongoing += 1
        mux_id = kwargs.pop("__serve_multiplexed_model_id", "")
        from .context import _set_request_stamps
        from .multiplex import _set_multiplexed_model_id
        if mux_id:
            _set_multiplexed_model_id(mux_id)
        _set_request_stamps(*stamps)
        method = self._resolve_method(method_name)

        def _next_with_ctx(it):
            # executor threads don't inherit the loop's contextvars; a
            # sync generator reading get_multiplexed_model_id() needs the
            # var set in the thread actually running its frames.
            if mux_id:
                _set_multiplexed_model_id(mux_id)
            _set_request_stamps(*stamps)
            return next(it, _STREAM_END)

        async def put_chunk(chunk):
            # a put parks only on a full buffer (1 024 chunks behind)
            t0 = time.perf_counter_ns()
            await buf.put(("chunk", chunk))
            self._put_row.since(t0)

        async def _drain():
            try:
                result = method(*args, **kwargs)
                if inspect.iscoroutine(result):
                    result = await result
                if inspect.isasyncgen(result):
                    try:
                        async for chunk in result:
                            await put_chunk(chunk)
                    finally:
                        # a cancelled stream closes its generator NOW:
                        # an LLM stream aborts its engine request there
                        await result.aclose()
                elif inspect.isgenerator(result):
                    loop = asyncio.get_running_loop()
                    it = iter(result)
                    while True:
                        chunk = await loop.run_in_executor(
                            None, _next_with_ctx, it)
                        if chunk == _STREAM_END:
                            break
                        await put_chunk(chunk)
                else:  # unary result streamed as a single chunk
                    await put_chunk(result)
                await buf.put(("end", None))
            except asyncio.CancelledError:
                raise              # consumer gone: just stop pumping
            except BaseException as e:  # noqa: BLE001
                await buf.put(("error", e))
            finally:
                self._drains.pop(stream_id, None)
                with self._lock:
                    self._ongoing -= 1
                    self._total_served += 1

        self._drains[stream_id] = asyncio.ensure_future(_drain())
        return stream_id

    async def stream_cancel(self, stream_id: str) -> bool:
        """Consumer abandoned the stream (client hung up): stop the
        drain task and drop the buffer. Idempotent; unknown/finished
        ids are a no-op."""
        if self._streams.pop(stream_id, None) is None:
            return False
        task = self._drains.get(stream_id)
        if task is not None:
            task.cancel()
        return True

    async def stream_next(self, stream_id: str, batch: int = 64,
                          timeout_s: float = 30.0):
        """Pull up to `batch` buffered chunks, waiting up to timeout_s
        for the first. Returns (chunks, done). Raises the handler's
        exception if the stream errored."""
        t0 = time.perf_counter_ns()
        waited = 0      # inside `buf.wait`: not this call's own time
        try:
            buf = self._streams.get(stream_id)
            if buf is None:
                return [], True
            from ..util import waits as waits_mod  # noqa: PLC0415
            wtok = waits_mod.park("serve-stream", stream_id,
                                  pending=len(buf.items))
            t1 = time.perf_counter_ns()
            try:
                if not await buf.wait(timeout_s):
                    return [], False
            finally:
                waited = time.perf_counter_ns() - t1
                waits_mod.unpark(wtok)
            chunks = []
            while buf.items and len(chunks) < batch:
                kind, payload = buf.pop()
                if kind == "chunk":
                    chunks.append(payload)
                    continue
                self._streams.pop(stream_id, None)
                if kind == "error":
                    raise payload
                return chunks, True     # end
            return chunks, False
        finally:
            self._next_row.add(time.perf_counter_ns() - t0 - waited)
