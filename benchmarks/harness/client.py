"""Open-loop load over HTTP from one thread: an asyncio loop with one
raw connection per request. Inside the window the client only stamps
each read with the clock; parsing waits until the run is over.

The server answers `POST /v1/completions` with `"stream": true` as
chunked server-sent events, one `data: {...}` line per token (with no
tokenizer the text of a token is its id), then one event with an empty
text and a `finish_reason`, then `data: [DONE]`.
"""
from __future__ import annotations

import asyncio
import json
import time
from typing import Callable, Dict, List, Optional, Sequence

from .window import Stream

CLOCK = time.perf_counter


class _Conn(asyncio.Protocol):
    """One request on one connection. `reads` is the whole answer as
    (time, bytes) in arrival order."""

    def __init__(self, payload: bytes, on_close: Callable[[], None]):
        self.payload = payload
        self.reads: List[tuple] = []
        self.sent: Optional[float] = None
        self.closed_by: Optional[str] = None
        self.transport = None
        self._on_close = on_close

    def connection_made(self, transport):
        self.transport = transport
        transport.write(self.payload)
        self.sent = CLOCK()

    def data_received(self, data: bytes):
        self.reads.append((CLOCK(), data))

    def connection_lost(self, exc):
        if self.closed_by is None:
            self.closed_by = "server" if exc is None else repr(exc)
        self._on_close()


def encode_request(host: str, port: int, body: dict, timeout_s: float) -> bytes:
    data = json.dumps(body, separators=(",", ":")).encode()
    head = (f"POST /v1/completions HTTP/1.1\r\nHost: {host}:{port}\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(data)}\r\n"
            f"X-Serve-Timeout-S: {timeout_s:g}\r\n"
            f"Connection: close\r\n\r\n").encode()
    return head + data


def parse_reads(reads: Sequence[tuple], keep_text: bool = False
                ) -> Dict[str, object]:
    """Status, token arrival times, and how the stream ended. An event
    arrives with the read that completed its line. `keep_text` also
    returns each token's text (the checks read the ids back)."""
    status, tokens, done, error, texts = None, [], False, None, []
    buf = b""
    for t, data in reads:
        buf += data
        *lines, buf = buf.split(b"\n")
        for line in lines:
            line = line.strip()
            if status is None and line.startswith(b"HTTP/"):
                status = int(line.split()[1])
                continue
            if not line.startswith(b"data: "):
                continue
            payload = line[6:]
            if payload in (b"[DONE]", b'"[DONE]"'):
                done = True
                continue
            try:
                event = json.loads(payload)
            except ValueError:
                error = error or f"unparsable event {payload[:80]!r}"
                continue
            if "error" in event:
                error = error or str(event["error"])[:200]
            elif event["choices"][0].get("text"):
                tokens.append(t)
                if keep_text:
                    texts.append(event["choices"][0]["text"])
    if status is None:
        error = error or "no response"
    elif status != 200:
        error = error or f"HTTP {status}: {buf[:120]!r}"
    return {"status": status, "token_times": tokens, "done": done,
            "error": error, "texts": texts}


class LoadRun:
    """Sends `payloads[i]` at `origin + dues[i]` and keeps what comes
    back until `stop()`."""

    def __init__(self, host: str, port: int, dues: Sequence[float],
                 payloads: Sequence[bytes], origin: float):
        self.host, self.port = host, port
        self.dues, self.payloads, self.origin = dues, payloads, origin
        self.conns: List[Optional[_Conn]] = [None] * len(dues)
        self.connect_errors: Dict[int, str] = {}
        self.open = 0

    def _closed(self):
        self.open -= 1

    async def send_all(self, until: float):
        """Issue every request due before `until` (absolute)."""
        loop = asyncio.get_running_loop()
        for i, due in enumerate(self.dues):
            at = self.origin + due
            if at >= until:
                break
            delay = at - CLOCK()
            if delay > 0:
                await asyncio.sleep(delay)
            conn = _Conn(self.payloads[i], self._closed)
            self.conns[i] = conn
            self.open += 1
            task = loop.create_task(self._connect(i, conn))
            task.add_done_callback(lambda _t: None)

    async def _connect(self, i: int, conn: _Conn):
        loop = asyncio.get_running_loop()
        try:
            await loop.create_connection(lambda: conn, self.host, self.port)
        except OSError as e:
            self.connect_errors[i] = repr(e)
            self.open -= 1

    def first_token_seen(self, i: int) -> bool:
        c = self.conns[i]
        return bool(c and (c.closed_by or any(
            b'"text":' in d and b'"text": ""' not in d
            for _t, d in c.reads)))

    def stop(self):
        """Close what is still open: the run is over for them."""
        for c in self.conns:
            if c is not None and c.closed_by is None:
                c.closed_by = "client"
                if c.transport is not None:
                    c.transport.abort()

    def streams(self, meta: Sequence[dict]) -> List[Stream]:
        out = []
        for i, c in enumerate(self.conns):
            if c is None and i not in self.connect_errors:
                continue            # never due before the run ended
            s = Stream(idx=i, due=self.origin + self.dues[i], **meta[i])
            if c is None or i in self.connect_errors:
                s.error = self.connect_errors.get(i, "not connected")
            else:
                p = parse_reads(c.reads)
                s.sent, s.token_times = c.sent, p["token_times"]
                s.done, s.error = p["done"], p["error"]
                if c.closed_by == "client" and p["status"] is None:
                    # still waiting for a replica when the run ended (the
                    # proxy answers only once it has one): open, not failed
                    s.error = None
                elif (s.error is None and not s.done
                        and c.closed_by != "client"):
                    s.error = f"stream cut short ({c.closed_by})"
            out.append(s)
        return out


def post_once(host: str, port: int, body: dict, timeout_s: float = 300.0
              ) -> dict:
    """One blocking streamed request outside any window (the checks).
    Returns the parse of its answer plus the token ids it carried."""
    import socket
    payload = encode_request(host, port, body, timeout_s)
    reads = []
    with socket.create_connection((host, port), timeout=timeout_s) as sock:
        sock.sendall(payload)
        while True:
            data = sock.recv(65536)
            if not data:
                break
            reads.append((CLOCK(), data))
    out = parse_reads(reads, keep_text=True)
    out["tokens"] = [int(x) for x in out["texts"]]
    return out
