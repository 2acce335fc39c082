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
(`SpanTable.watch_gc`), the CPU clocks of the threads that share the
interpreter lock (`thread_clocks`), and how long a thread that lets the
lock go waits to run again (`SpanTable.lock_probe`). The layers under
the engine's consumers (the worker's actor loop, the replica's streaming
calls) add to one table of the process, `process_table()`, which never
imports jax.
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
# resolved by the first span or timed call (`_annotations`): a table
# that is only added to never imports jax
_annotation = _step_annotation = None
_open = threading.local()       # .span: the innermost open span, per thread
# the rows `SpanTable.watch_gc` keeps: every collection, the full ones
GC_SPANS = ("gc.pause", "gc.pause.full")
# the rows `SpanTable.lock_probe("lock.reacquire")` keeps: every probe;
# the probes in which another thread took the lock (a wait over
# _LOCK_LOST_NS: alone the thread is back in 0.4 us), whose mean is what
# a release costs ONCE IT IS LOST (a probe's release is over in 0.1 us,
# sooner than a parked rival wakes, so it is lost more rarely than a
# long release is: the count says little, the mean a lot); and the
# probes that waited longer than _LOCK_LONG_NS (a count: a row has no
# percentile, and its maximum is over the process's life)
LOCK_SPANS = ("lock.reacquire", "lock.reacquire.lost",
              "lock.reacquire.long")
_LOCK_LOST_NS = 10_000
_LOCK_LONG_NS = 1_000_000
# the rows of `process_table()`, seeded so that a reader finds each
# whether or not its layer ran: an async actor call's entry up to its
# first await, its reply, its telemetry (core/worker.py); the
# synchronous parts of one `stream_next` call and of one buffered chunk
# (serve/replica.py); one hand-over filed on the consumers' loop
# (serve/llm/engine.py:_LoopSink.deliver)
PROCESS_SPANS = ("actor.call.resolve", "actor.call.reply",
                 "actor.call.telemetry", "replica.stream_next",
                 "replica.stream_put", "consumer.deliver")
_process_table: Optional["SpanTable"] = None
_module_lock = threading.Lock()     # what this module makes on first use
# () -> CLOCK_MONOTONIC ns, read with the interpreter lock released;
# None where ctypes or the symbol is missing; resolved by the first probe
_released_clock = _UNRESOLVED = object()


def _on_duration(event: str, seconds: float, **_kw) -> None:
    """The process's one jax.monitoring listener (listeners cannot be
    taken back, so tables do not register their own): a compile is
    charged to the span open on the compiling thread."""
    span = getattr(_open, "span", None)
    if span is not None and event == _COMPILE_EVENT:
        span.table.add_compile(span.name, int(seconds * 1e9))


def _annotations():
    """`jax.profiler.TraceAnnotation`, resolved once, with the process's
    compile listener registered beside it."""
    global _annotation, _step_annotation
    with _module_lock:
        if _annotation is None:
            import jax
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _step_annotation = jax.profiler.StepTraceAnnotation
            _annotation = jax.profiler.TraceAnnotation
    return _annotation


def _resolve_released_clock():
    """libc's `clock_gettime(CLOCK_MONOTONIC)` through `ctypes.CDLL`,
    which drops the interpreter lock around the foreign call: the clock
    `time.perf_counter_ns()` reads, read while another thread may run.
    None where any piece is missing (no ctypes, no symbol, another
    clock behind `perf_counter`): the probe then records nothing."""
    try:
        import ctypes
        if "CLOCK_MONOTONIC" not in time.get_clock_info(
                "perf_counter").implementation:
            return None
        clock_id = time.CLOCK_MONOTONIC

        class timespec(ctypes.Structure):
            _fields_ = [("tv_sec", ctypes.c_long),
                        ("tv_nsec", ctypes.c_long)]
        gettime = ctypes.CDLL(None).clock_gettime
        gettime.argtypes = [ctypes.c_int, ctypes.POINTER(timespec)]
        gettime.restype = ctypes.c_int
    except (ImportError, OSError, AttributeError, ValueError):
        return None
    byref = ctypes.byref

    def read() -> int:
        ts = timespec()         # the caller's own: any thread may probe
        gettime(clock_id, byref(ts))
        return ts.tv_sec * 1_000_000_000 + ts.tv_nsec
    return read


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


class _Tally:
    """One thread's additions to one row, in plain integers: made by
    `SpanTable.tally` for a row that one event loop writes thousands of
    times a second (a locked `add` costs three times as much), read by
    `snapshot()`. Wall time only."""
    __slots__ = ("n", "total_ns", "max_ns")

    def __init__(self):
        self.n = self.total_ns = self.max_ns = 0

    def add(self, ns: int) -> None:
        self.n += 1
        self.total_ns += ns
        if ns > self.max_ns:
            self.max_ns = ns

    def since(self, t0: int) -> int:
        """Add the time from the clock reading `t0` to now; returns
        now, the reading that opens the neighbouring segment. (`add`
        written out: a nested call is a third of the whole cost.)"""
        now = time.perf_counter_ns()
        ns = now - t0
        self.n += 1
        self.total_ns += ns
        if ns > self.max_ns:
            self.max_ns = ns
        return now


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
    `add` around one call, under an annotation of its own. Any thread
    may add. Outside a capture an annotation is inert; a span then
    costs its four clock reads and one locked row update. The CPU clock
    is a system call (6 us a read on the chip machine's host, where the
    wall clock costs 0.1): most of a span's ~15 us there
    (docs/OBSERVABILITY.md). `add` and `tally` need no annotation, so a
    table that only they touch never imports jax.
    """

    def __init__(self, names=()):
        self._lock = threading.Lock()   # new rows, tallies, the compiles
        self._rows: Dict[str, _Row] = {n: _Row() for n in names}
        self._tallies: List[tuple] = []              # (name, _Tally)
        self._compiles: Dict[str, List[int]] = {}    # name -> [n, ns]
        self._gc_began = None   # (wall, cpu) of the collection under way

    def span(self, name: str, **attrs) -> Span:
        return Span(self, name, (_annotation or _annotations())(
            name, **attrs))

    def step(self, name: str, step_num: int) -> Span:
        """A span the profiler also reads as one training step."""
        if _step_annotation is None:
            _annotations()
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

    def tally(self, name: str) -> _Tally:
        """A `_Tally` of `name` for ONE thread to add to; it stays in
        the table for good (a row never counts backwards)."""
        tally = _Tally()
        with self._lock:
            self._rows.setdefault(name, _Row())
            self._tallies.append((name, tally))
        return tally

    def call(self, name: str, fn, *args, **kw):
        """`fn(*args, **kw)`, its wall and CPU time added to `name`,
        under an annotation of that name (in a capture the call lies
        inside the phase that made it, on the same thread's line) but
        with no nesting: the span open around the call keeps the
        interval in its own self time."""
        with (_annotation or _annotations())(name):
            t0 = time.perf_counter_ns()
            c0 = time.thread_time_ns()
            try:
                return fn(*args, **kw)
            finally:
                cpu = time.thread_time_ns() - c0
                self.add(name, time.perf_counter_ns() - t0, cpu)

    def lock_probe(self, name: str) -> None:
        """Let the interpreter lock go once and add to `name` how long
        this thread then waited to run again: a clock read while the
        lock is released (`_resolve_released_clock`), the same clock
        read after it is taken back. That wait is what every call that
        releases the lock pays when another thread wants it; a probe
        pays it too, so the caller spaces its probes. A wait over
        `_LOCK_LOST_NS` (another thread ran in between) is also added
        to `name + ".lost"`, one over `_LOCK_LONG_NS` to `name +
        ".long"` as well. Where the released read cannot be made,
        nothing is added."""
        global _released_clock
        read = _released_clock
        if read is _UNRESOLVED:
            read = _released_clock = _resolve_released_clock()
        if read is None:
            return
        released = read()
        waited = time.perf_counter_ns() - released
        self.add(name, waited)
        if waited > _LOCK_LOST_NS:
            self.add(name + ".lost", waited)
            if waited > _LOCK_LONG_NS:
                self.add(name + ".long", waited)

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
        # unlocked: a tally's last addition may be seen half-made
        for name, tally in list(self._tallies):
            row = out.setdefault(name, [0, 0, 0, 0])
            row[0] += tally.n
            row[1] += tally.total_ns
            row[2] = max(row[2], tally.max_ns)
        return out

    def compiles(self) -> Dict[str, List[int]]:
        """`{span name: [compiles, ns]}` by the span that was open on
        the compiling thread."""
        with self._lock:
            return {k: list(v) for k, v in self._compiles.items()}


def process_table() -> SpanTable:
    """The one table of this process for the layers under the engine's
    consumers (`PROCESS_SPANS`), made on first use; `LLMEngine.
    get_stats()["spans"]` reports its rows beside the engine's own."""
    global _process_table
    if _process_table is None:
        with _module_lock:
            if _process_table is None:
                _process_table = SpanTable(PROCESS_SPANS)
    return _process_table


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
           "GC_SPANS", "LOCK_SPANS", "PROCESS_SPANS", "process_table",
           "thread_clocks",
           "device_memory_profile", "hbm_usage", "host_rss_bytes"]
