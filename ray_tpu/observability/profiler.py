"""On-device profiling: jax profiler traces and the program's own spans.

Reference parity: ray.timeline covers host-side task spans
(observability/timeline.py); this module adds the DEVICE side — XLA/TPU
op-level traces via jax.profiler — so a perf investigation gets both
views. Traces open in TensorBoard's profile plugin or Perfetto.

`SpanTable` is the one span primitive of the hot paths (the engine loop,
`SpmdTrainer.fit`): a span adds its self time to an in-memory table and
opens a `jax.profiler.TraceAnnotation` over the same interval, so that
during a capture the program's phases lie in the same `.xplane.pb`, on
the same clock, as the device's operations.
"""
from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Dict, List, Optional

_active_dir: Optional[str] = None

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# resolved by the first SpanTable (spans are made where jax already is)
_annotation = _step_annotation = None
_open = threading.local()       # .span: the innermost open span, per thread


def _on_duration(event: str, seconds: float, **_kw) -> None:
    """The process's one jax.monitoring listener (listeners cannot be
    taken back, so tables do not register their own): a compile is
    charged to the span open on the compiling thread."""
    span = getattr(_open, "span", None)
    if span is not None and event == _COMPILE_EVENT:
        span.table.add_compile(span.name, int(seconds * 1e9))


class Span:
    """One timed interval of one thread; made by `SpanTable.span`."""
    __slots__ = ("table", "name", "_ann", "_t0", "_children_ns", "_parent")

    def __init__(self, table: "SpanTable", name: str, ann):
        self.table, self.name, self._ann = table, name, ann

    def __enter__(self) -> "Span":
        self._parent = getattr(_open, "span", None)
        _open.span = self
        self._children_ns = 0
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> bool:
        dt = time.perf_counter_ns() - self._t0
        self._ann.__exit__(*exc)
        _open.span = self._parent
        if self._parent is not None:
            self._parent._children_ns += dt
        self.table.add(self.name, dt - self._children_ns)
        return False


class _Row:
    """One name's `[n, total_ns, max_ns]` under its own lock: a thread
    that loses the interpreter while it holds one row stalls only the
    writers of that row (a stream's consumers never hold up the engine
    thread's phases)."""
    __slots__ = ("lock", "n", "total_ns", "max_ns")

    def __init__(self):
        self.lock = threading.Lock()
        self.n = self.total_ns = self.max_ns = 0


class SpanTable:
    """`{name: [n, total_ns, max_ns]}`, owned by whoever makes it.

    `span(name, **attrs)` times an interval and stores its SELF time
    (what its child spans on the same thread covered is taken off, so
    the names of one loop sum to the loop's wall time); the attributes
    go to the annotation only. `add(name, ns)` records an interval
    measured elsewhere (a request's stamps), with no annotation. Any
    thread may add. Outside a capture an annotation is inert; a span
    then costs its two clock reads and one locked row update.
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

    def span(self, name: str, **attrs) -> Span:
        return Span(self, name, _annotation(name, **attrs))

    def step(self, name: str, step_num: int) -> Span:
        """A span the profiler also reads as one training step."""
        return Span(self, name, _step_annotation(name, step_num=step_num))

    def add(self, name: str, ns: int) -> None:
        row = self._rows.get(name)
        if row is None:
            with self._lock:
                row = self._rows.setdefault(name, _Row())
        with row.lock:
            row.n += 1
            row.total_ns += ns
            if ns > row.max_ns:
                row.max_ns = ns

    def add_compile(self, name: str, ns: int) -> None:
        with self._lock:
            row = self._compiles.setdefault(name, [0, 0])
            row[0] += 1
            row[1] += ns

    def snapshot(self) -> Dict[str, List[int]]:
        out = {}
        for name, row in list(self._rows.items()):
            with row.lock:
                out[name] = [row.n, row.total_ns, row.max_ns]
        return out

    def compiles(self) -> Dict[str, List[int]]:
        """`{span name: [compiles, ns]}` by the span that was open on
        the compiling thread."""
        with self._lock:
            return {k: list(v) for k, v in self._compiles.items()}


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
           "device_memory_profile", "hbm_usage", "host_rss_bytes"]
