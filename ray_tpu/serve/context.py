"""Per-request serve context (deadline and receipt-time propagation).

The proxy stamps every request with an ABSOLUTE deadline (epoch
seconds); the handle forwards it as the reserved
`__serve_deadline_ts` kwarg; the replica pops it and exposes it here
for the user callable — the LLM server reads it and threads it into
engine admission, so an expired request is shed instead of executed.
The time the proxy received the request travels the same way
(`__serve_recv_ts`): the LLM server hands it to `engine.submit`, which
records proxy-to-engine time (`request.ingress`). Both are readings of
`time.time()` on the proxy's host; against a replica on another host
they are as good as the two clocks agree.

Mirrors multiplex.py's contextvar pattern: sync handlers run in
executor threads that don't inherit the loop's context, so the replica
sets the var inside the thread actually running the handler frames.
"""
from __future__ import annotations

import contextvars
import time
from typing import Optional

_request_deadline: contextvars.ContextVar = contextvars.ContextVar(
    "ray_tpu_serve_request_deadline", default=None)
_request_recv_ts: contextvars.ContextVar = contextvars.ContextVar(
    "ray_tpu_serve_request_recv_ts", default=None)


def _set_request_stamps(deadline_ts: Optional[float],
                        recv_ts: Optional[float]) -> None:
    _request_deadline.set(deadline_ts)
    _request_recv_ts.set(recv_ts)


def get_request_recv_ts() -> Optional[float]:
    """When the proxy received the serve request being handled (epoch
    seconds, the proxy's clock), or None for a call that did not come
    through a proxy."""
    return _request_recv_ts.get()


def get_request_deadline() -> Optional[float]:
    """Absolute deadline (epoch seconds) of the serve request being
    handled, or None when the caller set no deadline."""
    return _request_deadline.get()


def remaining_budget() -> Optional[float]:
    """Seconds until the current request's deadline (clamped at 0), or
    None when no deadline was propagated."""
    d = _request_deadline.get()
    if d is None:
        return None
    return max(0.0, d - time.time())
