"""From a profiler trace (`.xplane.pb`) to numbers: the device's busy
time as the union of its operations' intervals, seconds per operation
and per program, collective time not hidden behind compute, and the
longest idle gaps named by what the host was doing in them.

Read with `jax.profiler.ProfileData` alone. A TPU trace has one plane
per chip (`/device:TPU:n`) with the lines `XLA Ops` (one event per
operation run) and `XLA Modules` (one per program run), and host planes
with one line per thread. Which planes and lines to read are arguments,
so that the same arithmetic can be checked on a trace recorded anywhere.
"""
from __future__ import annotations

import glob
import heapq
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Interval = Tuple[float, float]          # seconds

SHORT_GAP_S = 20e-6
COLLECTIVE_RE = (r"^(all-gather|all-reduce|reduce-scatter|all-to-all|"
                 r"collective-permute|send|recv)")


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The part of the union `a` that no interval of the union `b`
    covers (both sorted and disjoint)."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: Sequence[Interval]) -> List[Interval]:
    return [(a[1], b[0]) for a, b in zip(busy, busy[1:]) if b[0] > a[1]]


def category(name: str) -> str:
    """`fusion.123` -> `fusion`; `%all-gather-start.4 = bf16[..] ...` (a
    TPU trace names an operation by its whole HLO line) ->
    `all-gather-start`."""
    name = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"[.\d]+$", "", name) or name


def name_gaps(idle: Sequence[Interval], host: Sequence[Tuple[float, float, str]],
              unattributed: str) -> Dict[str, float]:
    """Seconds of idle time by name. A gap under 20 us is the device's
    own turn-around between operations. A longer one takes the name of
    the innermost host event that covers its midpoint, or `unattributed`
    where the host recorded nothing there."""
    out: Dict[str, float] = {}
    long = sorted((0.5 * (a + b), b - a) for a, b in idle
                  if b - a >= SHORT_GAP_S)
    short = sum(b - a for a, b in idle if b - a < SHORT_GAP_S)
    if short:
        out["between-ops-under-20us"] = short
    events = sorted(host)
    live: list = []                     # heap by end
    i = 0
    for mid, length in long:
        while i < len(events) and events[i][0] <= mid:
            s, e, n = events[i]
            heapq.heappush(live, (e, s, n))
            i += 1
        while live and live[0][0] < mid:
            heapq.heappop(live)
        name = unattributed
        if live:
            _e, _s, name = min(live, key=lambda x: x[0] - x[1])
        out[name] = out.get(name, 0.0) + length
    return out


def program_mean_seconds(trace: dict, module_re: str):
    """Mean device seconds of one run of the programs whose name
    matches, from a reduced trace; None where none ran."""
    hits = [m for n, m in (trace.get("modules") or {}).items()
            if re.search(module_re, n)]
    count = sum(m["count"] for m in hits)
    return sum(m["seconds"] for m in hits) / count if count else None


def _events(line) -> List[Tuple[float, float, str]]:
    return [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9,
             ev.name) for ev in line.events]


def _line_events(lines: dict, name: str):
    """Events of the line called `name`, or whose name starts with it
    (a host thread's line carries the thread's id after its name)."""
    for key in sorted(lines):
        if key == name or key.startswith(name + "/"):
            return _events(lines[key])
    return []


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce_dir(trace_dir: str, **kw) -> dict:
    return reduce_file(find_xplane(trace_dir), **kw)


def reduce_file(path: str, device_plane_re: str = r"^/device:TPU:\d+$",
                ops_line: str = "XLA Ops", modules_line: str = "XLA Modules",
                host_plane_re: str = r"^/host:", top: int = 10,
                unattributed: str = "engine-loop-unattributed") -> dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host = [], []
    for plane in data.planes:
        if re.search(device_plane_re, plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            devices.append((plane.name, lines))
        elif re.search(host_plane_re, plane.name):
            for ln in plane.lines:
                host.extend(_events(ln))
    if not devices:
        return {"devices": 0, "planes": [p.name for p in data.planes]}
    n = len(devices)
    busy_s = window_s = coll_s = exposed_s = 0.0
    ops: Dict[str, float] = {}
    raw_ops: Dict[str, float] = {}
    modules: Dict[str, dict] = {}
    idle_named: Dict[str, float] = {}
    for _name, lines in devices:
        evs = _line_events(lines, ops_line)
        mods = _line_events(lines, modules_line)
        if not evs:
            continue
        busy = union((s, e) for s, e, _n in evs)
        busy_s += total(busy)
        window_s += busy[-1][1] - busy[0][0]
        coll = union((s, e) for s, e, nm in evs
                     if re.search(COLLECTIVE_RE, category(nm)))
        comp = union((s, e) for s, e, nm in evs
                     if not re.search(COLLECTIVE_RE, category(nm)))
        coll_s += total(coll)
        exposed_s += total(subtract(coll, comp))
        for s, e, nm in evs:
            ops[category(nm)] = ops.get(category(nm), 0.0) + (e - s)
            short = nm.split(" = ", 1)[0].lstrip("%")
            raw_ops[short] = raw_ops.get(short, 0.0) + (e - s)
        for s, e, nm in mods:
            m = modules.setdefault(re.sub(r"\(.*$", "", nm),
                                   {"count": 0, "seconds": 0.0})
            m["count"] += 1
            m["seconds"] += e - s
        for k, v in name_gaps(gaps(busy), host, unattributed).items():
            idle_named[k] = idle_named.get(k, 0.0) + v

    def ranked(d, k=top):
        return [[a, b / n] for a, b in
                sorted(d.items(), key=lambda x: -x[1])[:k]]

    return {"devices": n, "busy_s": busy_s / n, "window_s": window_s / n,
            "collective_s": coll_s / n,
            "collective_exposed_s": exposed_s / n,
            "ops": {k: v / n for k, v in ops.items()},
            "modules": {k: {"count": v["count"] / n,
                            "seconds": v["seconds"] / n}
                        for k, v in modules.items()},
            "device_ops": ranked(ops), "idle_gaps": ranked(idle_named),
            "raw_ops_top": ranked(raw_ops, 40),
            "lines": sorted({ln for _n, ls in devices for ln in ls})}
