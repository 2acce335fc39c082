"""Window accounting on the client's event log, and the statistics.

A stream is one request as the client saw it: when it was due and sent,
and the arrival time of every token event. All times are on one clock
(the client's `time.perf_counter`). Nothing here knows about HTTP.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import List, Optional, Sequence


@dataclasses.dataclass
class Stream:
    idx: int
    due: float                      # absolute, client clock
    sent: Optional[float] = None
    token_times: List[float] = dataclasses.field(default_factory=list)
    done: bool = False              # the server closed the stream itself
    error: Optional[str] = None     # refused, failed or an error event
    prompt_len: int = 0
    max_tokens: int = 0


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between
    order statistics; infinite values (failed requests) sort last."""
    if not values:
        raise ValueError("percentile of nothing")
    v = sorted(values)
    pos = (len(v) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    if math.isinf(v[hi]) or lo == hi:
        return v[hi]
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def iqr_spread(values: Sequence[float]) -> float:
    """Distance between the first and third quartile as a share of the
    median, the contract's measure of a set's spread."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def due_in(streams: Sequence[Stream], t0: float, t1: float) -> List[Stream]:
    return [s for s in streams if t0 <= s.due < t1]


def tokens_in_window(streams: Sequence[Stream], t0: float, t1: float) -> int:
    """Token events that arrived in [t0, t1], whichever request they
    belong to: one begun before t0 or ending after t1 counts alike."""
    return sum(1 for s in streams for t in s.token_times if t0 <= t <= t1)


def ttft_ms(streams: Sequence[Stream], t0: float, t1: float) -> List[float]:
    """For every request due in the window: first token received minus
    the time it was due. A failed or refused request, or one with no
    token when the run ended, is worse than any (inf)."""
    return [(s.token_times[0] - s.due) * 1000.0
            if s.token_times and s.error is None else math.inf
            for s in due_in(streams, t0, t1)]


def gaps_ms(streams: Sequence[Stream], t0: float, t1: float) -> List[float]:
    """Every gap between consecutive token events of one stream whose
    later event arrived in the window."""
    out = []
    for s in streams:
        tt = s.token_times
        out.extend((b - a) * 1000.0 for a, b in zip(tt, tt[1:])
                   if t0 <= b <= t1)
    return out


def attempted_failed(streams: Sequence[Stream], t0: float, t1: float):
    """(attempted, failed) over requests due in the window. A stream
    still open when the run ended is not a failure; one that ended
    with another count of tokens than it asked for is."""
    due = due_in(streams, t0, t1)
    failed = sum(1 for s in due if s.error is not None
                 or (s.done and len(s.token_times) != s.max_tokens))
    return len(due), failed
