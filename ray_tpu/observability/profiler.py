"""On-device profiling: jax profiler traces and the program's own spans.

Reference parity: ray.timeline covers host-side task spans
(observability/timeline.py); this module adds the DEVICE side — XLA/TPU
op-level traces via jax.profiler — so a perf investigation gets both
views. Traces open in TensorBoard's profile plugin or Perfetto.

`SpanTable` is the one span primitive of the hot paths (the engine loop,
`SpmdTrainer.fit`): a span adds its self time, on the wall clock and on
its thread's CPU clock, to an in-memory table and opens a
`jax.profiler.TraceAnnotation` over the same interval, so that during a
capture the program's phases lie in the same `.xplane.pb`, on the same
clock, as the device's operations. What holds a thread up from outside
its spans is read beside the table: the collector's pauses
(`SpanTable.watch_gc`) and the CPU clocks of the threads that share the
interpreter lock (`thread_clocks`).
"""
from __future__ import annotations

import contextlib
import gc
import os
import threading
import time
from typing import Dict, List, Optional

_active_dir: Optional[str] = None

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# resolved by the first SpanTable (spans are made where jax already is)
_annotation = _step_annotation = None
_open = threading.local()       # .span: the innermost open span, per thread
# the rows `SpanTable.watch_gc` keeps: every collection, the full ones
GC_SPANS = ("gc.pause", "gc.pause.full")


def _on_duration(event: str, seconds: float, **_kw) -> None:
    """The process's one jax.monitoring listener (listeners cannot be
    taken back, so tables do not register their own): a compile is
    charged to the span open on the compiling thread."""
    span = getattr(_open, "span", None)
    if span is not None and event == _COMPILE_EVENT:
        span.table.add_compile(span.name, int(seconds * 1e9))


class Span:
    """One timed interval of one thread; made by `SpanTable.span`."""
    __slots__ = ("table", "name", "_ann", "_t0", "_c0", "_children_ns",
                 "_children_cpu", "_parent")

    def __init__(self, table: "SpanTable", name: str, ann):
        self.table, self.name, self._ann = table, name, ann

    def __enter__(self) -> "Span":
        self._parent = getattr(_open, "span", None)
        _open.span = self
        self._children_ns = self._children_cpu = 0
        self._ann.__enter__()
        # the CPU reads lie inside the wall reads: cpu <= wall
        self._t0 = time.perf_counter_ns()
        self._c0 = time.thread_time_ns()
        return self

    def __exit__(self, *exc) -> bool:
        cpu = time.thread_time_ns() - self._c0
        dt = time.perf_counter_ns() - self._t0
        self._ann.__exit__(*exc)
        _open.span = self._parent
        if self._parent is not None:
            self._parent._children_ns += dt
            self._parent._children_cpu += cpu
        self.table.add(self.name, dt - self._children_ns,
                       cpu - self._children_cpu)
        return False


class _Row:
    """One name's `[n, total_ns, max_ns, cpu_ns]` under its own lock: a
    thread that loses the interpreter while it holds one row stalls
    only the writers of that row (a stream's consumers never hold up
    the engine thread's phases). Re-entrant: a collection may start on
    a thread that holds a row, and its pause is added from that thread
    (`SpanTable.watch_gc`)."""
    __slots__ = ("lock", "n", "total_ns", "max_ns", "cpu_ns")

    def __init__(self):
        self.lock = threading.RLock()
        self.n = self.total_ns = self.max_ns = self.cpu_ns = 0


class SpanTable:
    """`{name: [n, total_ns, max_ns, cpu_ns]}`, owned by whoever makes it.

    `span(name, **attrs)` times an interval and stores its SELF time
    (what its child spans on the same thread covered is taken off, so
    the names of one loop sum to the loop's wall time), on the wall
    clock (`total_ns`, `max_ns`) and on the thread's CPU clock
    (`cpu_ns`): total - cpu is how long the thread stood still inside
    that name — asleep, blocked in a call, or waiting for the
    interpreter lock. The attributes go to the annotation only.
    `add(name, ns, cpu_ns)` records an interval measured elsewhere (a
    request's stamps), with no annotation; `call(name, fn, ...)` is
    `add` around one call. Any thread may add. Outside a capture an
    annotation is inert; a span then costs its four clock reads and one
    locked row update. The CPU clock is a system call (6 us a read on
    the chip machine's host, where the wall clock costs 0.1): most of a
    span's ~15 us there (docs/OBSERVABILITY.md).
    """

    def __init__(self, names=()):
        global _annotation, _step_annotation
        if _annotation is None:
            import jax
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _step_annotation = jax.profiler.StepTraceAnnotation
            _annotation = jax.profiler.TraceAnnotation
        self._lock = threading.Lock()       # new rows, and the compiles
        self._rows: Dict[str, _Row] = {n: _Row() for n in names}
        self._compiles: Dict[str, List[int]] = {}    # name -> [n, ns]
        self._gc_began = None   # (wall, cpu) of the collection under way

    def span(self, name: str, **attrs) -> Span:
        return Span(self, name, _annotation(name, **attrs))

    def step(self, name: str, step_num: int) -> Span:
        """A span the profiler also reads as one training step."""
        return Span(self, name, _step_annotation(name, step_num=step_num))

    def add(self, name: str, ns: int, cpu_ns: int = 0) -> None:
        row = self._rows.get(name)
        if row is None:
            with self._lock:
                row = self._rows.setdefault(name, _Row())
        with row.lock:
            row.n += 1
            row.total_ns += ns
            row.cpu_ns += cpu_ns
            if ns > row.max_ns:
                row.max_ns = ns

    def call(self, name: str, fn, *args, **kw):
        """`fn(*args, **kw)`, its wall and CPU time added to `name`:
        no annotation, and no nesting (the span open around the call
        keeps the interval in its own self time)."""
        t0 = time.perf_counter_ns()
        c0 = time.thread_time_ns()
        try:
            return fn(*args, **kw)
        finally:
            cpu = time.thread_time_ns() - c0
            self.add(name, time.perf_counter_ns() - t0, cpu)

    def watch_gc(self) -> None:
        """From here to `unwatch_gc`, every collection of this process
        adds its pause to the row `gc.pause`, a full one (generation 2)
        to `gc.pause.full` as well: wall time, and the CPU of the
        thread it ran on. Collections do not nest, so one slot holds
        the start."""
        with self._lock:
            for name in GC_SPANS:
                self._rows.setdefault(name, _Row())
        if self._on_gc not in gc.callbacks:
            gc.callbacks.append(self._on_gc)

    def unwatch_gc(self) -> None:
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            self._gc_began = (time.perf_counter_ns(), time.thread_time_ns())
            return
        began, self._gc_began = self._gc_began, None
        if began is None:       # watched from the middle of a collection
            return
        cpu = time.thread_time_ns() - began[1]
        ns = time.perf_counter_ns() - began[0]
        self.add("gc.pause", ns, cpu)
        if info.get("generation") == 2:
            self.add("gc.pause.full", ns, cpu)

    def add_compile(self, name: str, ns: int) -> None:
        with self._lock:
            row = self._compiles.setdefault(name, [0, 0])
            row[0] += 1
            row[1] += ns

    def snapshot(self) -> Dict[str, List[int]]:
        out = {}
        for name, row in list(self._rows.items()):
            with row.lock:
                out[name] = [row.n, row.total_ns, row.max_ns, row.cpu_ns]
        return out

    def compiles(self) -> Dict[str, List[int]]:
        """`{span name: [compiles, ns]}` by the span that was open on
        the compiling thread."""
        with self._lock:
            return {k: list(v) for k, v in self._compiles.items()}


def thread_clocks(**groups) -> Dict[str, int]:
    """`{group: cpu_ns, ..., "wall_ns": perf_counter_ns}`: the CPU time
    each group of `threading.Thread`s has used so far, summed, beside
    the wall clock it is a share of. A group that cannot be read (no
    thread yet, one that has ended, a platform without per-thread
    clocks) is left out, never guessed."""
    out = {}
    for group, threads in groups.items():
        threads = list(threads)
        try:
            if threads and all(th.is_alive() for th in threads):
                out[group] = sum(
                    time.clock_gettime_ns(
                        time.pthread_getcpuclockid(th.ident))
                    for th in threads)
        except (AttributeError, OSError):
            pass
    out["wall_ns"] = time.perf_counter_ns()
    return out


def start_trace(log_dir: str) -> str:
    """Begin capturing a device trace into log_dir (one capture at a
    time; mirrors jax.profiler.start_trace)."""
    global _active_dir
    import jax
    os.makedirs(log_dir, exist_ok=True)
    jax.profiler.start_trace(log_dir)
    _active_dir = log_dir
    return log_dir


def stop_trace() -> Optional[str]:
    global _active_dir
    import jax
    jax.profiler.stop_trace()
    out, _active_dir = _active_dir, None
    return out


@contextlib.contextmanager
def trace(log_dir: str):
    """with profiler.trace("/tmp/prof"): step(...)"""
    start_trace(log_dir)
    try:
        yield log_dir
    finally:
        stop_trace()


def device_memory_profile(path: Optional[str] = None) -> bytes:
    """Snapshot device memory (pprof format; jax.profiler parity)."""
    import jax
    data = jax.profiler.device_memory_profile()
    if path:
        with open(path, "wb") as f:
            f.write(data)
    return data


def hbm_usage() -> Dict[str, int]:
    """bytes-in-use per local accelerator device (device.memory_stats,
    the cheap always-callable sibling of device_memory_profile). Only
    consults jax when user code already imported it — a worker that
    never touched jax must not pay the import — AND only when a
    backend is already live: jax.local_devices() on a cold process
    would initialize the backend, which breaks a later
    jax.distributed.initialize() (multihost SPMD workers would die on
    'must be called before any JAX computations'). Returns {} on
    backends that do not report memory stats (CPU)."""
    import sys
    if "jax" not in sys.modules:
        return {}
    import jax
    try:
        from jax._src import xla_bridge  # noqa: PLC0415
        if not getattr(xla_bridge, "_backends", None):
            return {}
    except Exception:
        return {}
    out: Dict[str, int] = {}
    try:
        for dev in jax.local_devices():
            stats_fn = getattr(dev, "memory_stats", None)
            stats = stats_fn() if callable(stats_fn) else None
            if not stats:
                continue
            used = stats.get("bytes_in_use")
            if used is not None:
                out[str(dev.id)] = int(used)
    except Exception:
        pass
    return out


def host_rss_bytes() -> int:
    """This process's resident set size (/proc/self/statm)."""
    with open("/proc/self/statm") as f:
        pages = int(f.read().split()[1])
    return pages * os.sysconf("SC_PAGE_SIZE")


__all__ = ["start_trace", "stop_trace", "trace", "Span", "SpanTable",
           "GC_SPANS", "thread_clocks",
           "device_memory_profile", "hbm_usage", "host_rss_bytes"]
