"""JAX continuous-batching LLM engine.

Reference parity: the fork's vLLM-style serving path (continuous batching,
paged KV, streaming) — re-designed TPU-first:

* Paged KV cache (serve/llm/pages.py owns it: pools' shapes, pages,
  page table, lengths, windows): a pool per layer as the model's cache
  spec has it, indexed by token through the page table or, for a
  layer that keeps a fixed-size state, by slot. A model with slot state
  refuses prefix caching and speculation by name (a prefix would be a
  state snapshot, a rejected proposal a rollback), and get_stats() adds
  state_bytes_per_slot, decode_state_rows_window and
  decode_state_rows_live beside kv_bytes_per_token (paged layers only,
  the bytes a token takes as the pools are laid out).
  A slot reserves the pages its prompt + budget need at admission.
  Static shapes, so the decode step compiles once per power-of-two
  page window.
* Continuous batching: ONE jitted decode step advances ALL active slots
  together (the MXU sees batch=max_slots matmuls, not per-request calls).
  Requests join/leave between steps with no recompile.
* Prefill: prompts are padded to power-of-two buckets -> a handful of
  compiles total; KV scatters straight into the request's pages.
* Sampling (greedy / temperature / global top-k / per-request nucleus
  top-p) happens on-device inside the jitted step; only the sampled
  token ids (max_slots int32) cross to host per step. Per-request stop
  token ids terminate a stream like EOS. The arg-max runs in every
  call; the draw (divide, noise, a second arg-max) only in a call with
  a temperature above 0, the nucleus sort only with a top_p below 1
  among its rows (stats["decode_steps_drawn"] counts the former).
* Pipelined host loop: the loop dispatches step programs AHEAD of the
  host-side token fetch (device->host copies start at dispatch time,
  `copy_to_host_async`) and drains the oldest result once more than a
  target number are in flight. The target is derived, not set
  (`_InflightDepth`): enough programs that the device's queue never
  runs empty while the host does its own work of an iteration,
  1 + ceil(host time / device time a program) from what the loop
  measures, at least 2, at most `pipeline_depth` (the ceiling); 0 when
  the next step needs the last one's tokens on the host. Prefills go
  into the same queue; a request's first token is sampled on-device
  inside the prefill and comes out when the programs queued before it
  have run, so every program of depth beyond what feeds the device is
  one device step of waiting for each first token, and one discarded
  row for each finished request (termination is decided at drain
  time). stats["decode_inflight_target_sum"] / ["..._n"] is the mean
  target over decode dispatches.
* Hand-off to the consumers: a request's tokens, errors and end marker
  go to its sink. A consumer thread parks on a bounded queue
  (stream_detailed); a consumer on an event loop (astream_detailed, the
  serve replica) is woken by ONE call_soon_threadsafe per loop iteration
  that carries every request's items — a thread woken per token would
  take the interpreter lock from this loop at its next JAX call.
* One enqueue and one fetch a dispatch: the state the step programs
  share (lengths, last tokens, the sampling key) changes only INSIDE
  them and is handed from one to the next, donated. What the host
  decided since the last dispatch (page rows, length resets, the active
  mask, temperatures, top-p, a prefill group's prompts) rides into the
  next program, whichever kind it is, as ONE int32 vector of fixed
  layout (`_pack`), uploaded by the call's own argument path. No eager
  JAX call stands between two programs: each would hand the interpreter
  lock to the consumers' threads and put a tiny program into the
  device's queue. stats["runtime_calls"] counts every call the loop
  makes into the runtime.
* Spans and counters (observability/profiler.py:SpanTable, always on):
  the loop's phases are `engine.*` spans on the engine thread — self
  times in get_stats()["spans"], annotations in a profiler capture —
  and each request's stamps (`request.*`, `slot.refill*`,
  `stream.deliver`) are table rows with no annotation, so that a
  per-stream event never takes a device idle gap from the loop's.
  Every row has its CPU time beside its wall time: the difference is
  how long the thread stood still in it (the device in
  `engine.drain_wait`, the sleep in `engine.idle_sleep`, elsewhere the
  interpreter lock or a block inside the runtime). The `runtime.*` rows
  time the loop's runtime calls one by one, `step.release` the
  destruction of the donated leaves after a step, `gc.pause*` the
  collector, and get_stats()["threads"] reads the CPU clocks of the two
  threads that share the lock: this one and the consumers' event loop.
"""
from __future__ import annotations

import asyncio
import collections
import itertools
import math
import queue as queue_mod
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, NamedTuple, Optional

import numpy as np

from ...observability.profiler import SpanTable, thread_clocks
from ...util import knobs

# every phase of _engine_loop; seeded in the table so that a reader of
# get_stats()["spans"] finds each key whether or not the phase ran
_LOOP_SPANS = ("engine.loop", "engine.control", "engine.admit",
               "engine.prefill_dispatch", "engine.chunk_dispatch",
               "engine.decode_prep", "engine.decode_dispatch",
               "engine.drain_wait", "engine.emit", "engine.deliver",
               "engine.bookkeep", "engine.idle_sleep")
_REQUEST_SPANS = ("request.ingress", "request.inflight_prefill",
                  "request.inflight_decode", "slot.refill",
                  "slot.refill.starved", "stream.deliver")
# the loop's calls into the JAX runtime, timed one by one where
# stats["runtime_calls"] counts them (_step, _start_fetch, _runtime):
# rows added around the call, inside whichever phase makes it
_RUNTIME_SPANS = ("runtime.step", "runtime.fetch_start", "runtime.other")
# what no call shows: after a step call the carried pools and state are
# rebound to its results, and the last references to the leaves that
# were donated into it go. The runtime destroys each leaf then: most of
# a dispatch phase's time on the chip (PERF.md section 6, PR 37)
_STEP_SPANS = ("step.release",)


@dataclass
class LLMEngineConfig:
    max_slots: int = 8              # max concurrently-decoding sequences
    max_seq_len: int = 1024         # prompt + generation budget per slot
    prefill_buckets: tuple = (32, 64, 128, 256, 512, 1024)
    eos_token_id: Optional[int] = None
    max_new_tokens_default: int = 64
    top_k: int = 0                  # 0 = full softmax sampling
    # CEILING of the programs the loop keeps in flight ahead of the
    # host-side token fetch; at most this many x decode_block discarded
    # tokens per finished request. The depth in force is what the loop
    # derives from its own measurements (_InflightDepth: 2-3 on a v5e
    # in every serve cell, PERF.md section 6, PR 35). The 10 was sized
    # for a slow device->host transport that is gone; held as a fixed
    # depth it made every first token wait ten device steps.
    pipeline_depth: int = 10
    # Decode steps fused into ONE dispatch via lax.scan: each dispatch
    # emits decode_block tokens per slot, dividing per-token host work
    # (dispatch + mask/rng prep + fetch) by the block size. 1 = the
    # classic one-token step.
    decode_block: int = 1
    # Waiting prompts that share a length bucket prefill TOGETHER in one
    # jitted call of up to this many rows (padded to a power of two via
    # the scratch slot) — one dispatch and one model pass instead of
    # per-prompt calls. 1 disables batching.
    max_prefill_batch: int = 4
    # Chunked prefill (vLLM-style): prompts longer than this split into
    # prefill_chunk-token chunks, one chunk dispatched per engine-loop
    # iteration, so active decodes keep stepping DURING a long prompt's
    # prefill instead of stalling behind one monolithic call.
    # 0 disables chunking.
    prefill_chunk: int = 0
    # Fetch each sampled token's log-probability (of the raw model
    # distribution) to the host and expose it via stream_detailed().
    # Off by default: it adds one small device->host array per step.
    logprobs: bool = False
    # Compile every prefill bucket + the decode step during __init__
    # (blocking) so the first real request never pays a jit compile —
    # the dominant term in cold TTFT (seconds even for toy models).
    precompile: bool = False
    # Prefix caching (vLLM's automatic-prefix-caching, made explicit
    # and static-shape for TPU): register_prefix() prefills a shared
    # prompt prefix ONCE into pinned pages of the pool; submits carrying
    # prefix_id share its full pages by page-table reference (zero
    # copy), copy only the final partial page and prefill only their
    # suffix. 0 disables.
    max_prefixes: int = 0
    # Tokens per page of the KV cache (vLLM's PagedAttention,
    # TPU-first): a shared pool of per-layer flat (n_pages * page_size)
    # token rows + per-slot page tables (static shapes; see
    # ops/attention.py:paged_cached_attention). Slots reserve
    # ceil((prompt+budget)/page_size) pages at admission, so a short
    # request strands no max_seq_len of HBM and concurrency is bounded
    # by the real token budget, not the slot count. Must be > 0.
    kv_page_size: int = 64
    # Total pool budget in KV tokens (rounded up to whole pages).
    # 0 = max_slots * max_seq_len (every slot can reach max_seq_len).
    kv_pool_tokens: int = 0
    # n-gram (prompt-lookup) speculative decoding: propose K tokens per
    # step by matching the trailing `ngram_order`-gram against the
    # request's own prompt+generation history, verify all K in ONE
    # forward (in-jit prefix acceptance), emit 1..K+1 tokens per
    # dispatch. Decode is weight-bandwidth-bound, so accepted tokens
    # amortize a full weight read — repetitive text (summaries, code,
    # RAG) decodes up to (1+K)x faster. Greedy (temp==0), non-guided
    # requests only; output is token-identical to plain decode.
    # Speculative traffic steps synchronously (proposals need the
    # previous step's tokens). 0 disables.
    ngram_speculation: int = 0
    ngram_order: int = 2
    # proposal lookback window (tokens of trailing history searched per
    # step, vLLM prompt-lookup style) — bounds host work per step
    ngram_lookback: int = 256
    # Wedged-engine watchdog: if the generation loop makes no forward
    # progress (no admit, no dispatch, no token drained) for this long
    # WHILE requests are admitted/waiting, the engine is declared
    # wedged — in-flight requests abort with EngineWedgedError (so the
    # serve handle can fail over) and health checks fail with a
    # `wedged` cause until the replica is replaced. None reads
    # RAY_TPU_ENGINE_WATCHDOG_S (default 30); <= 0 disables.
    watchdog_s: Optional[float] = None


@dataclass
class _Request:
    request_id: str
    prompt: np.ndarray              # (P,) int32
    max_new_tokens: int
    temperature: float
    top_p: float = 1.0
    stop_ids: frozenset = frozenset()
    # where this request's tokens, errors and end marker go: a blocking
    # _QueueSink until an awaitable consumer (astream_detailed) swaps in
    # its _LoopSink; sink_lock makes that swap atomic against a put
    sink: "_QueueSink | _LoopSink" = field(
        default_factory=lambda: _QueueSink())
    sink_lock: Any = field(default_factory=threading.Lock)
    # when the sink refused a token (consumer at its bound) and the
    # consumer had taken `stalled_taken` items: the stall clock
    stalled_since: Optional[float] = None
    stalled_taken: int = 0
    slot: int = -1
    generated: int = 0
    aborted: bool = False
    prefix_id: int = -1             # registered-prefix KV to adopt
    prefill_pos: int = 0            # next prompt index (chunked prefill)
    submit_ts: float = field(default_factory=time.time)
    # the same moment on the clock `slot.refill` is taken on
    submit_ns: int = field(default_factory=time.perf_counter_ns)
    admit_ts: Optional[float] = None       # slot assigned
    prefill_dispatch_ms: float = 0.0       # host time in the prefill
                                           # call (compile on first use)
    first_token_ts: Optional[float] = None
    # guided decoding (serve/llm/guided.py): host-side token FSM whose
    # per-state vocab mask constrains sampling; state advances at emit
    fsm: Optional[object] = None
    fsm_state: int = 0
    # n-gram speculation: prompt+generated history (proposal source);
    # None when this request is ineligible (sampled/guided)
    hist: Optional[list] = None
    # sampling penalties (OpenAI semantics): subtract presence once and
    # frequency*count per occurrence of a GENERATED token; logit_bias is
    # a static {token_id: float} addend. Counts live ON DEVICE and
    # update in-jit from last_tokens, so pipelining is preserved.
    presence_penalty: float = 0.0
    frequency_penalty: float = 0.0
    logit_bias: Optional[dict] = None
    # absolute deadline propagated from the serve plane; a request
    # whose deadline expires while still QUEUED is shed at admission
    # (DeadlineExceededError) instead of executed
    deadline_ts: Optional[float] = None
    # when the serve proxy received the HTTP request (its clock);
    # None for a direct submit
    recv_ts: Optional[float] = None


_END = ("__end__", None)


class _StepState(NamedTuple):
    """What one step program hands to the next (device arrays, donated
    with each call): every slot's cached length and last sampled token,
    and the sampling key. Nothing else writes them."""
    lengths: Any        # (n_slots,) int32
    last_tokens: Any    # (n_slots,) int32
    key: Any            # PRNG key; each program splits it once


def _pack(*parts) -> np.ndarray:
    """The host's arguments of one step program as one int32 vector (one
    upload): int32 parts as they are, float32 parts as their bits.
    LLMEngine._unpack takes it apart inside the program."""
    flat = [np.asarray(p).reshape(-1) for p in parts]
    assert all(a.dtype in (np.int32, np.float32) for a in flat), \
        [a.dtype for a in flat]
    return np.concatenate([a.view(np.int32) for a in flat])


class _InflightDepth:
    """How many dispatched programs the loop leaves in flight when it
    stops draining: 1 + ceil(H / P), at least FLOOR, at most the
    ceiling the configuration gives (`pipeline_depth`).

    H is a cautious reading of the host's own time an iteration (the
    loop's wall time less what it waited for the device): the largest
    of the last `block` to 2 x `block` iterations, so that an
    iteration that dispatched a prefill group or lost the interpreter
    lock is covered. Each reading is first cut to CLIP x the usual one
    (a moving mean, which learns the cut value), so that a stall of the
    whole machine for seconds lifts the depth by a step or two for a
    moment and is not learnt. P is the device's time a program: the
    interval between the returns of two fetches that had to wait, over
    the programs drained in it (a moving mean, cut the same way). While
    no fetch waits the host is the slower side: depth feeds the device
    no better, every result held back is lag, and the floor applies.
    O(1) an iteration and no clock of its own: the loop hands in what
    it measured (`fetched`, `iterated`, `idle`)."""
    FLOOR = 2
    CLIP = 4.0
    WAITED_NS = 200_000     # a fetch that was ready returns in ~20 us

    def __init__(self, block: int = 32):
        self.block = block
        self.host_ns = 0.0          # the usual host time an iteration
        self.period_ns = 0.0        # the device's time a program
        # this block's and the last block's: largest cut host time,
        # fetches that waited
        self._peak = [0.0, 0.0]
        self._waits = [0, 0]
        self._n = 0                 # iterations into this block
        self._waited_at = 0         # return of the last fetch that waited
        self._since = 0             # programs drained since then

    def _mean(self, mean: float, x: float) -> tuple:
        """(the moving mean after x cut to CLIP x the mean, x so cut)."""
        if mean <= 0:
            return x, x
        x = min(x, self.CLIP * mean)
        return mean + (x - mean) / 8, x

    def fetched(self, wait_ns: int, now_ns: int) -> None:
        """A drained program's fetch returned at `now_ns` after
        `wait_ns` in np.asarray."""
        self._since += 1
        if wait_ns < self.WAITED_NS:
            return
        if self._waited_at:
            self.period_ns, _ = self._mean(
                self.period_ns, (now_ns - self._waited_at) / self._since)
        self._waited_at, self._since = now_ns, 0
        self._waits[0] += 1

    def idle(self) -> None:
        """Nothing is in flight: the device's queue ran empty, so the
        next wait opens a new interval."""
        self._waited_at = 0

    def iterated(self, host_ns: int) -> None:
        """One iteration with work in flight took `host_ns` of the
        host's own time."""
        self.host_ns, cut = self._mean(self.host_ns, float(host_ns))
        if cut > self._peak[0]:
            self._peak[0] = cut
        self._n += 1
        if self._n >= self.block:
            self._n = 0
            self._peak = [0.0, self._peak[0]]
            self._waits = [0, self._waits[0]]

    def target(self, ceiling: int, active: bool = True,
               need_sync: bool = False) -> int:
        if need_sync or not active:
            # guided masks / n-gram proposals need the previous step's
            # tokens on the host; nothing active: drain fully
            return 0
        depth = self.FLOOR
        if self.period_ns > 0 and self._waits[0] + self._waits[1]:
            depth = max(depth,
                        1 + math.ceil(max(self._peak) / self.period_ns))
        return min(depth, ceiling)

    def readings(self, ceiling: int) -> Dict[str, float]:
        return {"target": self.target(ceiling),
                "host_peak_ms": max(self._peak) / 1e6,
                "host_usual_ms": self.host_ns / 1e6,
                "device_period_ms": self.period_ns / 1e6}


_engine_ids = itertools.count()
_metrics_singletons = None


def _engine_metrics():
    """Shared built-in registry metrics, resolved through the catalog
    (util/metrics_catalog.py) so names stay `ray_tpu_`-prefixed and
    documented in one place. Per-engine series ride the `engine` tag —
    re-instantiating per engine would clobber the registry entry and
    drop earlier engines' series. A cleared registry (tests do that)
    is detected and the metrics re-register fresh."""
    global _metrics_singletons
    from ...util import metrics as metrics_mod  # noqa: PLC0415
    from ...util import metrics_catalog as mcat  # noqa: PLC0415
    if (_metrics_singletons is not None
            and metrics_mod.get_metric(
                "ray_tpu_llm_engine_tokens_generated")
            is not _metrics_singletons["tokens"]):
        _metrics_singletons = None
    if _metrics_singletons is None:
        _metrics_singletons = {
            "tokens": mcat.get("ray_tpu_llm_engine_tokens_generated"),
            "active": mcat.get("ray_tpu_llm_engine_active_slots"),
            "waiting": mcat.get("ray_tpu_llm_engine_waiting_requests"),
            "occupancy": mcat.get("ray_tpu_llm_engine_batch_occupancy"),
            "kv_util": mcat.get(
                "ray_tpu_llm_engine_kv_page_utilization"),
            "ttft": mcat.get("ray_tpu_llm_engine_ttft_s"),
            "tpot": mcat.get("ray_tpu_llm_engine_tpot_s"),
        }
    return _metrics_singletons



# Items a sink may hold undelivered before it refuses tokens (the stall
# clock of LLMEngine._put_token starts there).
_SINK_BOUND = 4096


class _QueueSink:
    """Blocking sink: a bounded queue on which one consumer thread
    parks (stream_detailed, and through it stream, generate_sync,
    _collect). Every put wakes that thread."""

    loop = None     # no event loop: the engine may wait for this consumer
    taken = 0       # its progress shows as an offer that succeeds

    def __init__(self):
        self.q: queue_mod.Queue = queue_mod.Queue(maxsize=_SINK_BOUND)

    def drain(self) -> list:
        """Everything still in the queue, in order."""
        items = []
        while True:
            try:
                items.append(self.q.get_nowait())
            except queue_mod.Empty:
                return items

    def offer(self, item) -> bool:
        """Put a token, waiting up to a second for room; False = still
        full."""
        try:
            self.q.put(item, timeout=1.0)
            return True
        except queue_mod.Full:
            return False

    def force(self, item) -> None:
        """Publish a control item (error, end marker) without ever
        blocking the engine loop: on Full, drop one buffered token to
        make room. A second Full means the consumer raced a get between
        our get and put — then the queue has room on the next consumer
        cycle anyway and the item is dropped."""
        try:
            self.q.put_nowait(item)
            return
        except queue_mod.Full:
            pass
        try:
            self.q.get_nowait()
        except queue_mod.Empty:
            pass
        try:
            self.q.put_nowait(item)
        except queue_mod.Full:
            pass


class _LoopSink:
    """Awaitable sink: the consumer is an async generator on `loop`
    (astream_detailed). A put only appends to the engine's outbox;
    LLMEngine._hand_over carries everything one loop iteration put, for
    every request, to the loop in ONE call_soon_threadsafe, where
    `deliver` files the items and wakes the consumers that wait. No
    thread is parked per stream and none is woken per token."""

    def __init__(self, loop, request_id: str, outbox: collections.deque):
        self.loop = loop
        self.request_id = request_id
        self.closed = False     # the consumer is gone: drop what comes
        self._outbox = outbox
        self._items: collections.deque = collections.deque()  # loop side
        self._waiter: Optional[asyncio.Future] = None         # loop side
        self.offered = 0        # written by the producer
        self.taken = 0          # written by the consumer

    def offer(self, item) -> bool:
        """Never waits (the engine cannot park on an event loop); False
        = _SINK_BOUND items are handed over and not yet taken."""
        if self.offered - self.taken >= _SINK_BOUND:
            return False
        self.force(item)
        return True

    def force(self, item) -> None:
        if not self.closed:
            self.offered += 1
            self._outbox.append((self, item))

    @staticmethod
    def deliver(batch) -> None:
        """On the loop: file one hand-over's (sink, item) pairs."""
        for sink, item in batch:
            if sink.closed:
                continue
            sink._items.append(item)
            waiter = sink._waiter
            if waiter is not None and not waiter.done():
                waiter.set_result(None)

    async def take(self):
        while not self._items:
            self._waiter = self.loop.create_future()
            try:
                await self._waiter
            finally:
                self._waiter = None
        self.taken += 1
        return self._items.popleft()


def _next_pow2(n: int) -> int:
    """Smallest power of two >= max(n, 1) (bucketing helper: prefill
    group sizes, prefix pads, decode page windows)."""
    p = 1
    while p < n:
        p *= 2
    return p


class LLMEngine:
    """Continuous-batching engine over a ray_tpu Llama-family model.

    `model` must follow the ray_tpu/models/llama.py contract:
    apply({"params": params}, tokens, cache=..., positions=...) ->
    (logits, new_cache) with cache = one entry per layer; the engine
    passes ops/attention.py:PagedKV entries over its page pool.
    """

    def __init__(self, model, params, cfg: LLMEngineConfig):
        import jax
        import jax.numpy as jnp
        from ...util.jaxenv import enable_compile_cache  # noqa: PLC0415
        enable_compile_cache()
        self._jax, self._jnp = jax, jnp
        dev = jax.devices()[0]
        # every result names the device it ran on (get_stats()["device"])
        self.device = {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())}
        self.model = model
        self.params = params
        self.cfg = cfg
        mcfg = model.cfg
        if cfg.eos_token_id is None:
            cfg.eos_token_id = getattr(mcfg, "eos_token_id", None)
        model_max = getattr(mcfg, "max_seq_len", None)
        if model_max is not None and cfg.max_seq_len > model_max:
            # absolute-position models (GPT-2's learned wpe) would
            # silently reuse their last embedding past this; fail loudly
            raise ValueError(
                f"engine max_seq_len {cfg.max_seq_len} exceeds the "
                f"model's max_seq_len {model_max}")
        from ...ops.attention import kv_cache_spec  # noqa: PLC0415
        from .pages import PageAllocator  # noqa: PLC0415
        # where the cached tokens live; the pools it sizes are carried
        # here, donated from step to step
        self._pages = PageAllocator(
            kv_cache_spec(model), cfg.max_slots, cfg.max_seq_len,
            cfg.kv_page_size, cfg.kv_pool_tokens)
        self._n_slots = self._pages.n_slots
        self._scratch_slot = self._pages.scratch_slot
        if cfg.max_prefixes > 0 and (why := self._pages.refuses("share")):
            raise ValueError(
                "prefix caching (max_prefixes, register_prefix, "
                "cached_prefixes) copies pages; " + why)
        if cfg.ngram_speculation > 0 \
                and (why := self._pages.refuses("roll_back")):
            raise ValueError(
                "n-gram speculation (ngram_speculation) verifies "
                "proposals in one forward and drops the rejected; " + why)
        self._pools = self._pages.new_pools()
        # what the benchmark's sampler thread reads for the rooflines'
        # live context (benchmarks/harness/replica.py): the allocator's
        # own mirror, read-only
        self._disp_len = self._pages.dispatched_lengths
        # tokens a decode dispatch appends to each active slot
        self._decode_new = max(1, cfg.decode_block)
        self._state = _StepState(
            jnp.zeros((self._n_slots,), jnp.int32),
            jnp.zeros((self._n_slots,), jnp.int32),
            jax.random.PRNGKey(0))
        self._pending_head: Optional[_Request] = None
        self._free_slots = list(range(cfg.max_slots))
        self._active: Dict[int, _Request] = {}
        self._waiting: "queue_mod.Queue[_Request]" = queue_mod.Queue()
        self._requests: Dict[str, _Request] = {}
        self._req_counter = itertools.count()
        self._lock = threading.Lock()
        # (active mask, temperatures, top-p) over the slots, rebuilt
        # when the active set changed
        self._mask_temps = None
        self._temps_drawn = False   # some temperature of those is > 0
        self._guided_allow_buf = None
        self._guided_prev = None
        self._spec_idle = 0
        self._spec_retry = 0
        # penalties: device-resident per-slot token-count + static-bias
        # matrices, allocated on first use; seeded per slot assignment
        self._pen_counts = None
        self._pen_static = None
        self._pen_seeded: Dict[int, str] = {}
        self._pen_coef = None
        self._pen_coef_dirty = True
        self._mask_dirty = True
        self._shutdown = threading.Event()
        # no "preempted" stat: slots are statically sized for
        # prompt+budget at admission, so mid-stream KV eviction (vLLM's
        # preemption trigger) cannot occur by construction
        self.stats = {"prefills": 0, "decode_steps": 0,
                      # the decode steps whose temperatures had a value
                      # above 0: the sampler's draw ran in those alone
                      "decode_steps_drawn": 0,
                      "tokens_generated": 0, "prefix_tokens_saved": 0,
                      # decode rows: steps x max_slots of them ran; a
                      # row's token is emitted, or discarded (its slot
                      # was released, reused or over budget: the lag of
                      # the programs in flight), or the slot was empty
                      "decode_slot_steps": 0, "decode_tokens_emitted": 0,
                      "decode_tokens_discarded": 0,
                      # the decode kernel's pages, summed over decode
                      # dispatches: rows x the window's pages, and the
                      # pages the rows hold (what the kernel walks)
                      "decode_pages_window": 0, "decode_pages_live": 0,
                      # prefill programs: rows and tokens asked for
                      # against what the padded (bucket x group) call ran
                      "prefill_calls": 0, "prefill_rows_real": 0,
                      "prefill_rows_padded": 0, "prefill_tokens_real": 0,
                      "prefill_tokens_padded": 0, "prefill_shapes": {},
                      # the hand-off: _hand_over calls that carried
                      # something and the items they carried (awaitable
                      # consumers), tokens put on a blocking queue
                      "deliver_batches": 0, "deliver_items": 0,
                      "deliver_blocking_tokens": 0,
                      # calls the loop made into the JAX runtime: step
                      # programs, fetch starts, whatever else (_step,
                      # _start_fetch, _runtime): 2 a dispatch when
                      # nothing eager is on the path
                      "runtime_calls": 0,
                      # the in-flight target in force at each decode
                      # dispatch (_InflightDepth), summed and counted
                      "decode_inflight_target_sum": 0,
                      "decode_inflight_target_n": 0}
        if self._pages.n_state_layers:
            # per-slot state rows of decode dispatches, summed over the
            # layers that keep one: rows the step read and wrote (every
            # row of the pool: an idle row is written through
            # unchanged) and rows that were decoding
            self.stats["decode_state_rows_window"] = 0
            self.stats["decode_state_rows_live"] = 0
        # (sink, item) pairs for awaitable consumers since the last
        # _hand_over; appends and poplefts are atomic, so abort() and
        # the watchdog may put from their own threads
        self._outbox: collections.deque = collections.deque()
        # counters a model leaves per call (Mixtral: ops/moe.py MOE_STATS);
        # the step programs put them behind the tokens they return, so
        # they reach the host in the fetch the tokens need anyway
        self._step_stats = tuple(getattr(model, "step_stats", ()))
        self._counted = bool(self._step_stats)
        for name in self._step_stats:
            self.stats[name] = 0
        self._spans = SpanTable(
            _LOOP_SPANS + _REQUEST_SPANS + _RUNTIME_SPANS + _STEP_SPANS)
        self._spans.watch_gc()      # until shutdown()
        # the threads astream_detailed was called on (the replica's
        # actor loop): get_stats()["threads"]["consumers"]
        self._consumer_threads: set = set()
        self._slot_freed_ns: Dict[int, int] = {}    # slot -> _release
        self._decode_dispatches = 0     # the `step` of a decode's span
        self._depth = _InflightDepth()
        self._waited_ns = 0     # in fetches, this iteration of the loop
        # TTFT breakdown (VERDICT r4 ask): queue wait vs prefill
        # dispatch (compile on a bucket's first use) vs emit lag.
        self._ttft_samples: collections.deque = collections.deque(
            maxlen=512)
        # recent per-request mean time-per-output-token (seconds) —
        # feeds get_stats()["tpot_p50_ms"] and through it the serve
        # autoscaler's tpot_slo_ms term
        self._tpot_samples: collections.deque = collections.deque(
            maxlen=512)
        self._prefill_compile_ms: Dict[int, float] = {}  # bucket -> ms
        # surfaced on the shared metrics registry (/metrics, dashboard);
        # one labeled series per engine instance. The dict is cached
        # here and refreshed once per engine-loop step — the per-token
        # emit path must not take the registry lock for clear-detection
        self._mtags = {"engine": f"llm-{next(_engine_ids)}"}
        self._m = _engine_metrics()

        # lifecycle events (util/events.py): request admit / preempt /
        # finish / abort land on the cluster event plane — when this
        # engine runs inside an actor the worker's telemetry flush
        # ships them to the driver like sys.metrics
        def _event(etype, message="", req=None, **attrs):
            try:
                from ...util import events as events_mod  # noqa: PLC0415
                events_mod.emit(
                    etype, message,
                    request_id=req.request_id if req is not None
                    else None,
                    engine=self._mtags["engine"], **attrs)
            except Exception:
                pass
        self._event = _event

        # registered prefixes: host-side token records; their KV lives
        # in pinned pages of the pool (the allocator's)
        self._prefixes: Dict[int, np.ndarray] = {}   # pid -> tokens
        self._prefix_counter = itertools.count()

        self._prefilling: collections.deque = collections.deque()
        # the step programs: (params, pools, state, ctl, ...) ->
        # (fetch, logps, pools', state', ...); pools and state donated
        self._prefill_paged_jit = jax.jit(
            self._prefill_paged_step, static_argnames=("pad_len",),
            donate_argnums=(1, 2))
        self._chunk_paged_jit = jax.jit(
            self._chunk_paged_step,
            static_argnames=("chunk", "sample"), donate_argnums=(1, 2))
        self._decode_paged_jit = jax.jit(
            self._decode_paged_step, donate_argnums=(1, 2),
            static_argnames=("window_pages",))
        self._verify_paged_jit = jax.jit(
            self._verify_paged_step, donate_argnums=(1, 2),
            static_argnames=("window_pages",))
        self._decode_block_paged_jit = (
            jax.jit(self._decode_block_paged_step,
                    donate_argnums=(1, 2),
                    static_argnames=("window_pages",))
            if cfg.decode_block > 1 else None)
        self._copy_page_jit = jax.jit(self._pages.copy_page,
                                      donate_argnums=(0,))
        self._pen_seed_jit = jax.jit(self._pen_seed_impl,
                                     donate_argnums=(0, 1))
        # register_prefix must mutate the pools on the engine loop
        # thread — its dispatches donate them, so a concurrent
        # public-API mutation would race a stale buffer. Commands queue
        # here and the loop executes them between steps.
        self._control_q: "queue_mod.Queue" = queue_mod.Queue()
        # wedged-engine watchdog: _progress_ts advances on every admit /
        # token emit / idle tick; a separate thread observes staleness
        # (the loop thread itself may be stuck inside a device call, so
        # it cannot self-report)
        if cfg.watchdog_s is not None:
            self._watchdog_s = float(cfg.watchdog_s)
        else:
            self._watchdog_s = knobs.get_float(
                "RAY_TPU_ENGINE_WATCHDOG_S")
        self._progress_ts = time.time()
        self._wedged_since: Optional[float] = None
        # True while the loop thread is inside the admit/dispatch/drain
        # work section: a stall there can be a legitimate first-use jit
        # COMPILE (seconds..minutes for big models), so the watchdog
        # grants it _DISPATCH_GRACE x the budget. Host-side stalls —
        # a stuck control command, a lock deadlock, the loop wedged
        # between iterations — get the tight watchdog_s budget.
        self._in_dispatch = False
        self._loop_thread = threading.Thread(
            target=self._engine_loop, daemon=True, name="llm-engine")
        self._loop_thread.start()
        if self._watchdog_s > 0:
            self._watchdog_thread = threading.Thread(
                target=self._watchdog_loop, daemon=True,
                name="llm-engine-watchdog")
            self._watchdog_thread.start()
        if cfg.precompile:
            self.precompile()

    # ---- jitted kernels ---------------------------------------------------
    def _pen_bias(self, pen, last_tokens, active_mask):
        """In-jit penalty bias for one decode step. pen = (counts (S,V)
        i32 DEVICE state, static_bias (S,V) f32, presence (S,), freq
        (S,)). The previously-emitted token (last_tokens — incl. the
        prefill's first token) is counted here, so every generated
        token influences penalties from the NEXT step on, with no host
        round-trip: pipelining is preserved. The counts input is NOT
        donated (it shares one kwarg tuple with static_bias, which must
        survive across steps), so each penalized step allocates a fresh
        (S, V) i32 output — ~1 MB at 8x32k; split counts into its own
        donated arg if this ever shows at scale. Returns (bias or None,
        updated counts or None)."""
        if pen is None:
            return None, None
        jnp = self._jnp
        counts, static_bias, presence, freq = pen
        S = counts.shape[0]
        inc = active_mask.astype(counts.dtype)
        counts = counts.at[jnp.arange(S), last_tokens].add(inc)
        bias = (static_bias
                - presence[:, None] * (counts > 0)
                - freq[:, None] * counts)
        return bias, counts
    def _sample_tokens(self, logits, temps, top_ps, rng_key, allow=None,
                       bias=None):
        """Sample per row of logits (N, V): greedy when temp==0, else
        temperature + optional global top-k + per-row nucleus top-p.
        All on device; returns (tokens (N,) int32, logprobs (N,) f32 of
        the chosen token under the RAW model distribution).

        allow (N, V) bool, optional: guided-decoding mask — tokens
        outside it are impossible under every sampling mode (reported
        logprobs stay raw-model). bias (N, V) float, optional: additive
        logit adjustments (logit_bias + presence/frequency penalties).
        None at trace time keeps the plain compile identical."""
        jnp = self._jnp
        jax = self._jax
        # cfg.logprobs is a plain Python bool at trace time: disabled
        # engines compile WITHOUT the full-vocab log_softmax + gather
        raw_logp = (jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
                    if self.cfg.logprobs else None)
        if bias is not None:
            logits = logits + bias.astype(logits.dtype)
        if allow is not None:
            logits = jnp.where(allow, logits, -jnp.inf)
        if self.cfg.top_k and self.cfg.top_k > 0:
            kth = jnp.sort(logits, axis=-1)[:, -self.cfg.top_k][:, None]
            logits = jnp.where(logits < kth, -jnp.inf, logits)
        # every call needs the arg-max; each further pass over the
        # (N, V) logits runs only where a row of THIS call asks for it
        greedy = jnp.argmax(logits, axis=-1)

        def nucleus(scaled):
            # smallest prefix of the prob-sorted vocab whose mass reaches
            # top_p (always keeps the argmax)
            n, _v = scaled.shape
            sort_idx = jnp.argsort(-scaled, axis=-1)
            sorted_probs = jax.nn.softmax(
                jnp.take_along_axis(scaled, sort_idx, axis=-1), axis=-1)
            cum = jnp.cumsum(sorted_probs, axis=-1)
            keep_sorted = (cum - sorted_probs) < top_ps[:, None]
            keep = jnp.zeros_like(keep_sorted).at[
                jnp.arange(n)[:, None], sort_idx].set(keep_sorted)
            use_top_p = (top_ps < 1.0)[:, None]
            return jnp.where(use_top_p & ~keep, -jnp.inf, scaled)

        def drawn():
            # the divide stands INSIDE each branch: without nucleus it
            # fuses with the noise and the arg-max of the draw, and the
            # scaled logits are never written; the full-vocab sort only
            # runs when some row asked for top_p < 1
            t = jnp.maximum(temps, 1e-6)[:, None]
            sampled = jax.lax.cond(
                jnp.any(top_ps < 1.0),
                lambda: jax.random.categorical(
                    rng_key, nucleus(logits / t), axis=-1),
                lambda: jax.random.categorical(
                    rng_key, logits / t, axis=-1))
            return jnp.where(temps > 0, sampled, greedy)

        # all rows greedy (every empty slot is): no divide, no noise, no
        # second arg-max
        toks = jax.lax.cond(jnp.any(temps > 0), drawn,
                            lambda: greedy).astype(jnp.int32)
        if raw_logp is None:
            logps = jnp.zeros(toks.shape, jnp.float32)
        else:
            logps = jnp.take_along_axis(raw_logp, toks[:, None],
                                        axis=-1)[:, 0]
        return toks, logps

    # ---- step programs over the page pool ---------------------------------
    def _apply_counted(self, params, tokens, entries, positions, row_mask):
        """model.apply for a step program. A model that declares
        `step_stats` is told which rows are real and returns, summed
        over its layers, the int32 vector of those counters; any other
        model is called as ever and returns None for them."""
        if not self._counted:
            logits, new_entries = self.model.apply(
                {"params": params}, tokens, cache=entries,
                positions=positions)
            return logits, new_entries, None
        (logits, new_entries), sown = self.model.apply(
            {"params": params}, tokens, cache=entries,
            positions=positions, row_mask=row_mask,
            mutable=["step_stats"])
        leaves = self._jax.tree_util.tree_leaves(sown["step_stats"])
        return logits, new_entries, sum(leaves)

    def _prefill_paged_impl(self, params, pools, page_table, lengths,
                            tokens, slots, true_lens, temps, top_ps,
                            rng_key, pad_len: int, allow=None,
                            bias=None, n_real=None):
        """Prefill G prompts (single and batched unified): KV streams
        straight into each slot's pages — no small-cache copy-back.
        tokens: (G, pad_len); slots/true_lens/temps/top_ps: (G,).
        Padding rows target the scratch slot, whose page-table row is
        all-trash, so their writes vanish by construction. `n_real`
        (given for a model that counts its rows): the first n_real rows
        are prompts, the rest group padding."""
        jnp = self._jnp
        g = tokens.shape[0]
        rows_p = self._pages.narrow(               # pages covering pad
            page_table[slots], self._pages.pages_needed(pad_len))
        # fresh=True: pure prefill — attention runs straight over the
        # prompt (flash-eligible on TPU), no page gather; KV still
        # scatters into the pages
        entries = self._pages.entries(
            pools, rows_p, jnp.zeros((g,), jnp.int32), fresh=True,
            slots=slots, n_new=true_lens)
        positions = jnp.broadcast_to(jnp.arange(pad_len)[None, :],
                                     (g, pad_len))
        real = positions < true_lens[:, None]      # not bucket padding
        if n_real is not None:
            real &= (jnp.arange(g) < n_real)[:, None]
        logits, new_entries, counted = self._apply_counted(
            params, tokens, entries, positions, real)
        new_pools = [e.arrays for e in new_entries]
        lengths = lengths.at[slots].set(true_lens)
        last = logits[jnp.arange(g), true_lens - 1]
        toks, logps = self._sample_tokens(last, temps, top_ps, rng_key,
                                          allow=allow, bias=bias)
        if counted is not None:
            return (toks, logps, new_pools, lengths,
                    jnp.concatenate([toks, counted]))
        return toks, logps, new_pools, lengths

    def _chunk_paged_impl(self, params, pools, page_table, lengths,
                          tokens, slot, start, new_len, temp, top_p,
                          rng_key, chunk: int, sample: bool,
                          allow=None, bias=None):
        """One chunk of a long prompt: tokens (1, chunk) written at
        positions [start, start+chunk); the slot's length becomes
        `new_len` (start + true tokens in this chunk, so tail padding of
        the final chunk stays invisible — pad queries only ever attend
        pad keys and their outputs are discarded). The slot's true
        current length IS `start` — a reused slot's stored length would
        be stale from the previous occupant and leak its KV into the
        chunk's valid-mask. Gathers the slot's full page row (start is
        dynamic, so the attention window cannot be statically narrowed
        the way bucketed prefill narrows it). sample=True (final chunk)
        also samples the first generated token from the last true
        position."""
        jnp = self._jnp
        jax = self._jax
        row = jax.lax.dynamic_slice_in_dim(page_table, slot, 1, axis=0)
        l1 = jnp.reshape(start, (1,)).astype(jnp.int32)
        entries = self._pages.entries(
            pools, row, l1, slots=jnp.reshape(slot, (1,)),
            n_new=jnp.reshape(new_len - start, (1,)),
            restart=jnp.reshape(start == 0, (1,)))
        positions = start + jnp.arange(chunk)[None, :]
        logits, new_entries = self.model.apply(
            {"params": params}, tokens, cache=entries,
            positions=positions)
        new_pools = [e.arrays for e in new_entries]
        lengths = lengths.at[slot].set(new_len)
        if not sample:
            return jnp.int32(0), jnp.float32(0), new_pools, lengths
        last = logits[0, new_len - start - 1]
        toks, logps = self._sample_tokens(last[None, :], temp[None],
                                          top_p[None], rng_key,
                                          allow=allow, bias=bias)
        return toks[0], logps[0], new_pools, lengths

    def _decode_paged_impl(self, params, pools, page_table, lengths,
                           last_tokens, active_mask, temps, top_ps,
                           rng_key, window_pages: int = 0, allow=None,
                           pen=None):
        """One decode step for every slot over the page pool. Released
        slots' page-table rows point at the trash page, so their writes
        are inert; inactive lengths are restored so state never
        drifts.

        window_pages > 0 statically narrows the attention window to the
        first `window_pages` page-table columns (a power-of-2 bucket
        covering the longest ACTIVE sequence, host-tracked): decode
        cost then scales with real lengths, not max_seq_len — the
        XLA-gather path's analog of the Pallas kernel's page skipping.
        """
        jnp = self._jnp
        page_table = self._pages.narrow(page_table, window_pages)
        entries = self._pages.entries(
            pools, page_table, lengths,
            n_new=active_mask.astype(jnp.int32)
            if self._pages.n_state_layers else None)
        positions = lengths[:, None]
        logits, new_entries, counted = self._apply_counted(
            params, last_tokens[:, None], entries, positions,
            active_mask[:, None])
        logits = logits[:, 0, :]
        new_pools = [e.arrays for e in new_entries]
        new_lengths = jnp.where(
            active_mask, new_entries[self._pages.len_layer].lengths, lengths)
        bias, new_counts = self._pen_bias(pen, last_tokens, active_mask)
        nxt, logps = self._sample_tokens(logits, temps, top_ps, rng_key,
                                         allow=allow, bias=bias)
        nxt = jnp.where(active_mask, nxt, last_tokens)
        out = (nxt, logps, new_pools, new_lengths)
        if pen is not None:
            out += (new_counts,)
        if counted is not None:
            # last: the tokens again with the model's counters behind
            # them, which is what the engine fetches
            out += (jnp.concatenate([nxt, counted]),)
        return out

    def _decode_block_paged_impl(self, params, pools, page_table,
                                 lengths, last_tokens, active_mask,
                                 temps, top_ps, rng_key,
                                 window_pages: int = 0):
        """decode_block fused steps under one dispatch (lax.scan).
        Returns (tokens (K, S), logps, pools', lengths', last_tokens').
        Host-side termination decisions lag up to K-1 extra tokens;
        drain guards discard them."""
        jax = self._jax
        keys = jax.random.split(rng_key, self.cfg.decode_block)

        def body(carry, key):
            pools, lengths, last = carry
            nxt, logps, pools, lengths, *fetch = self._decode_paged_impl(
                params, pools, page_table, lengths, last, active_mask,
                temps, top_ps, key, window_pages=window_pages)
            return (pools, lengths, nxt), (fetch[0] if fetch else nxt,
                                           logps)

        (pools, lengths, last), (toks, logps) = jax.lax.scan(
            body, (pools, lengths, last_tokens), keys)
        return toks, logps, pools, lengths, last

    def _verify_paged_impl(self, params, pools, page_table, lengths,
                           last_tokens, proposals, active_mask, temps,
                           top_ps, rng_key, window_pages: int = 0):
        """n-gram speculation verify: ONE forward of [last, p1..pK] per
        slot; in-jit greedy prefix acceptance. proposals (S, K) int32,
        -1 = no proposal at that offset. Returns (out (S, K+1), n_emit
        (S,), logps (S, K+1), pools', lengths', last') — emit
        out[s, :n_emit[s]]. Rejected positions' KV is invisible
        (attention masks by length) and overwritten by later writes at
        the same positions. Accepted tokens always land in reserved
        pages (acceptance <= remaining budget); overshoot writes may hit
        the trash page, which is by-construction inert."""
        jnp = self._jnp
        K = proposals.shape[1]
        page_table = self._pages.narrow(page_table, window_pages)
        entries = self._pages.entries(pools, page_table, lengths)
        toks_in = jnp.concatenate(
            [last_tokens[:, None], jnp.maximum(proposals, 0)], axis=1)
        positions = lengths[:, None] + jnp.arange(K + 1)[None, :]
        logits, new_entries = self.model.apply(
            {"params": params}, toks_in, cache=entries,
            positions=positions)
        new_pools = [e.arrays for e in new_entries]
        out, n_emit, logps, last = self._verify_accept(
            logits, proposals, last_tokens, active_mask, temps, top_ps,
            rng_key)
        new_lengths = lengths + n_emit
        return out, n_emit, logps, new_pools, new_lengths, last

    def _verify_accept(self, logits, proposals, last_tokens, active_mask,
                       temps, top_ps, rng_key):
        """Shared in-jit acceptance: greedy chain for speculating rows,
        normal sampling (position 0 only) for sampled rows."""
        jnp = self._jnp
        jax = self._jax
        K = proposals.shape[1]
        S = logits.shape[0]
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (S,K+1)
        match = (proposals == greedy[:, :K]) & (proposals >= 0)
        acc = jnp.cumprod(match.astype(jnp.int32), axis=1)
        m = acc.sum(axis=1)                                     # (S,)
        out0, lp0 = self._sample_tokens(logits[:, 0], temps, top_ps,
                                        rng_key)
        out = greedy.at[:, 0].set(out0)  # _sample_tokens is greedy at
        #                                  temp==0, so this is uniform
        if self.cfg.logprobs:
            lsm = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
            logps = jnp.take_along_axis(
                lsm, out[..., None].astype(jnp.int32), -1)[..., 0]
            logps = logps.at[:, 0].set(lp0)
        else:
            logps = jnp.zeros(out.shape, jnp.float32)
        n_emit = jnp.where(temps > 0, 1, m + 1)
        n_emit = jnp.where(active_mask, n_emit, 0).astype(jnp.int32)
        last = out[jnp.arange(S), jnp.maximum(n_emit - 1, 0)]
        last = jnp.where(active_mask, last, last_tokens)
        return out, n_emit, logps, last

    # ---- the step programs as the loop dispatches them --------------------
    # Each takes the carried state and ONE int32 vector `ctl` from the
    # host (_pack; its first n_slots words are always the length edits),
    # does first what the host used to do with eager calls between two
    # programs, runs its *_impl and returns (fetch, logps, pools',
    # state', ...): `fetch` is the one vector the host reads back.
    def _unpack(self, ctl, *spec):
        """Inside a program: `ctl` cut into its parts; spec entries are
        (shape, dtype) in _pack's order, float32 parts bitcast back."""
        jnp, lax = self._jnp, self._jax.lax
        out, at = [], 0
        for shape, dtype in spec:
            n = int(np.prod(shape))
            part = ctl[at:at + n].reshape(shape)
            at += n
            if dtype == jnp.float32:
                part = lax.bitcast_convert_type(part, jnp.float32)
            out.append(part)
        assert at == ctl.shape[0], (at, ctl.shape)
        return out

    def _begin_step(self, state, ctl, *spec):
        """What every program does first: the lengths the host reset
        since the last dispatch (ctl's first n_slots words, -1 = leave),
        and the split the host used to make for each dispatch: the
        chain of keys and subkeys is the chain of that eager split, in
        the order of dispatches. Returns (lengths, last_tokens, key,
        sub) and the rest of `ctl` cut by `spec`."""
        jnp = self._jnp
        edits, *parts = self._unpack(ctl, ((self._n_slots,), jnp.int32),
                                     *spec)
        lengths = jnp.where(edits >= 0, edits, state.lengths)
        key, sub = self._jax.random.split(state.key)
        return (lengths, state.last_tokens, key, sub), parts

    def _begin_slots_step(self, state, ctl, window_pages: int):
        """_begin_step of the programs over all slots (_decode_ctl)."""
        jnp = self._jnp
        S = self._n_slots
        carried, (mask, temps, top_ps, table) = self._begin_step(
            state, ctl, ((S,), jnp.int32), ((S,), jnp.float32),
            ((S,), jnp.float32),
            (self._pages.rows_shape(window_pages), jnp.int32))
        return (*carried, mask != 0, temps, top_ps, table)

    def _prefill_paged_step(self, params, pools, state, ctl, pad_len: int,
                            allow=None, bias=None):
        """_prefill_paged_impl for a group of g rows (g from ctl's own
        length); the first n_real are prompts, and their first tokens
        go into last_tokens here. fetch: all g rows' tokens (the host
        reads the first n_real), a counting model's counters behind."""
        jnp = self._jnp
        S, P = self._pages.rows_shape()
        g = (ctl.shape[0] - S - 1 - S * P) // (4 + pad_len)
        (lengths, last_tokens, key, sub), (
            n_real, slots, lens, temps, top_ps, table, tokens) = \
            self._begin_step(
                state, ctl, ((), jnp.int32), ((g,), jnp.int32),
                ((g,), jnp.int32), ((g,), jnp.float32),
                ((g,), jnp.float32), ((S, P), jnp.int32),
                ((g, pad_len), jnp.int32))
        toks, logps, pools, lengths, *counted = self._prefill_paged_impl(
            params, pools, table, lengths, tokens, slots, lens, temps,
            top_ps, sub, pad_len=pad_len, allow=allow, bias=bias,
            n_real=n_real if self._counted else None)
        # group padding (the scratch slot's rows) writes nowhere
        real = jnp.where(jnp.arange(g) < n_real, slots, S)
        last_tokens = last_tokens.at[real].set(toks, mode="drop")
        return (counted[0] if counted else toks, logps, pools,
                _StepState(lengths, last_tokens, key))

    def _chunk_paged_step(self, params, pools, state, ctl, chunk: int,
                          sample: bool, allow=None, bias=None):
        """_chunk_paged_impl; the final chunk's token goes into
        last_tokens here. fetch and logps are (1,)."""
        jnp = self._jnp
        S, P = self._pages.rows_shape()
        (lengths, last_tokens, key, sub), (
            slot, start, new_len, temp, top_p, table, tokens) = \
            self._begin_step(
                state, ctl, ((), jnp.int32), ((), jnp.int32),
                ((), jnp.int32), ((), jnp.float32), ((), jnp.float32),
                ((S, P), jnp.int32), ((1, chunk), jnp.int32))
        tok, logp, pools, lengths = self._chunk_paged_impl(
            params, pools, table, lengths, tokens, slot, start, new_len,
            temp, top_p, sub, chunk=chunk, sample=sample, allow=allow,
            bias=bias)
        if sample:
            last_tokens = last_tokens.at[slot].set(tok)
        return (tok[None], logp[None], pools,
                _StepState(lengths, last_tokens, key))

    def _decode_paged_step(self, params, pools, state, ctl,
                           window_pages: int = 0, allow=None, pen=None):
        """_decode_paged_impl over the window's columns of the table.
        Returns (fetch, logps, pools', state'[, penalty counts'])."""
        lengths, last_tokens, key, sub, mask, temps, top_ps, table = \
            self._begin_slots_step(state, ctl, window_pages)
        nxt, logps, pools, lengths, *rest = self._decode_paged_impl(
            params, pools, table, lengths, last_tokens, mask, temps,
            top_ps, sub, allow=allow, pen=pen)
        fetch = rest.pop() if self._counted else nxt
        return (fetch, logps, pools, _StepState(lengths, nxt, key), *rest)

    def _decode_block_paged_step(self, params, pools, state, ctl,
                                 window_pages: int = 0):
        lengths, last_tokens, key, sub, mask, temps, top_ps, table = \
            self._begin_slots_step(state, ctl, window_pages)
        toks, logps, pools, lengths, last = self._decode_block_paged_impl(
            params, pools, table, lengths, last_tokens, mask, temps,
            top_ps, sub)
        return toks, logps, pools, _StepState(lengths, last, key)

    def _verify_paged_step(self, params, pools, state, ctl, proposals,
                           window_pages: int = 0):
        """Returns (out, logps, pools', state', n_emit)."""
        lengths, last_tokens, key, sub, mask, temps, top_ps, table = \
            self._begin_slots_step(state, ctl, window_pages)
        out, n_emit, logps, pools, lengths, last = self._verify_paged_impl(
            params, pools, table, lengths, last_tokens, proposals, mask,
            temps, top_ps, sub)
        return out, logps, pools, _StepState(lengths, last, key), n_emit

    def _pen_seed_impl(self, counts, static_bias, slot, row):
        """A penalized request took `slot`: its generated-token counts
        restart at zero and `row` is its static logit bias."""
        return (counts.at[slot].set(0), static_bias.at[slot].set(row))

    # ---- public API -------------------------------------------------------
    def register_prefix(self, prefix_ids) -> int:
        """Prefill a shared prompt prefix (e.g. a system prompt) once;
        returns a prefix_id for submit(prefix_id=...). Requires
        cfg.max_prefixes > 0. Prefix ids are append-only: registering
        more than max_prefixes raises. Thread-safe."""
        if why := self._pages.refuses("share"):
            raise ValueError("register_prefix copies pages; " + why)
        if self.cfg.max_prefixes <= 0:
            raise ValueError("engine built with max_prefixes=0")
        prefix = np.asarray(prefix_ids, np.int32).reshape(-1)
        if prefix.size == 0:
            raise ValueError("empty prefix")
        if prefix.size >= self.cfg.max_seq_len - 1:
            raise ValueError(f"prefix length {prefix.size} leaves no "
                             f"room in max_seq_len "
                             f"{self.cfg.max_seq_len}")
        pid = next(self._prefix_counter)
        if pid >= self.cfg.max_prefixes:
            raise ValueError(
                f"prefix slots exhausted ({self.cfg.max_prefixes})")
        self._run_on_loop(lambda: self._register_prefix_paged(pid, prefix))
        return pid

    def _run_on_loop(self, fn) -> None:
        """Execute `fn` on the engine loop thread (pool mutations must
        not race dispatches that donate the pool buffers); blocks until
        done and re-raises its exception. Shutdown-safe: the wait polls
        the shutdown event so a command the exiting loop never drains
        raises instead of hanging the caller forever."""
        from concurrent.futures import Future  # noqa: PLC0415
        from concurrent.futures import TimeoutError as FutTimeout
        if self._shutdown.is_set():
            raise RuntimeError("engine is shut down")
        fut: Future = Future()
        self._control_q.put((fn, fut))
        while True:
            try:
                fut.result(timeout=0.1)
                return
            except FutTimeout:
                if self._shutdown.is_set() and not fut.done():
                    raise RuntimeError(
                        "engine shut down before command ran") from None

    def _register_prefix_paged(self, pid: int, prefix: np.ndarray
                               ) -> None:
        """Prefill a prefix into freshly-allocated PINNED pages (loop
        thread only): the prefix lives in the pool; adopters share its
        full pages by reference."""
        if not self._pages.pin_prefix(pid, prefix.size):
            raise ValueError("page pool exhausted registering prefix")
        pad = min(_next_pow2(prefix.size), self.cfg.max_seq_len)
        tokens = np.zeros((1, pad), np.int32)
        tokens[0, :prefix.size] = prefix
        try:
            self._step(self._prefill_paged_jit, (
                np.int32(1), np.asarray([self._scratch_slot], np.int32),
                np.asarray([prefix.size], np.int32),
                np.zeros((1,), np.float32), np.ones((1,), np.float32),
                self._pages.rows(), tokens), pad_len=pad)
        except BaseException:
            self._pages.unpin_prefix(pid)
            raise
        finally:
            self._pages.reset_scratch()
        self._prefixes[pid] = prefix

    def _unregister_prefix_paged(self, pid: int) -> None:
        """Free a prefix's pinned pages (loop thread; internal — only
        safe once no active slot shares them, e.g. precompile's warm
        prefix after its streams drain)."""
        self._pages.unpin_prefix(pid)
        self._prefixes.pop(pid, None)

    def submit(self, prompt_ids, max_new_tokens: Optional[int] = None,
               temperature: float = 0.0, top_p: float = 1.0,
               stop_token_ids=None,
               prefix_id: Optional[int] = None,
               guided_fsm=None,
               presence_penalty: float = 0.0,
               frequency_penalty: float = 0.0,
               logit_bias: Optional[dict] = None,
               deadline_ts: Optional[float] = None,
               recv_ts: Optional[float] = None) -> str:
        """guided_fsm: a serve.llm.guided.TokenFSM constraining this
        request's output (per-step vocab masks; EOS only at accepting
        states). Guided traffic decodes synchronously (pipeline drains
        each step) so the mask can depend on the previous token.

        deadline_ts: absolute deadline (epoch seconds, propagated from
        the serve plane). A deadline that already cannot be met is
        rejected HERE — before any queueing — and one that expires
        while queued is shed at admission, both with
        DeadlineExceededError.

        recv_ts: when the serve proxy received the request (epoch
        seconds on the proxy's clock); recorded as `request.ingress`
        and `ttft_breakdown_p50_ms.ingress_ms`. Across hosts it is as
        good as the two clocks agree."""
        from ...exceptions import DeadlineExceededError  # noqa: PLC0415
        if self.wedged:
            from ...exceptions import EngineWedgedError  # noqa: PLC0415
            raise EngineWedgedError(
                "engine is wedged; replica awaiting replacement")
        if deadline_ts is not None and time.time() >= deadline_ts:
            # same shed-telemetry contract as the queued-expiry path:
            # every shed is visible, whichever gate catches it
            self._event("serve.request.shed", reason="deadline_expired",
                        stage="submit",
                        late_s=round(time.time() - deadline_ts, 3))
            from ...util import events as events_mod  # noqa: PLC0415
            events_mod.emit_safe(
                counter="ray_tpu_serve_requests_shed_total",
                counter_tags={"reason": "deadline_expired"})
            raise DeadlineExceededError(
                "deadline already expired at submit; request rejected "
                "at admission")
        prompt = np.asarray(prompt_ids, dtype=np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if not 0.0 < top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {top_p}")
        if not -2.0 <= presence_penalty <= 2.0 \
                or not -2.0 <= frequency_penalty <= 2.0:
            raise ValueError("presence/frequency penalties must be in "
                             "[-2, 2] (OpenAI semantics)")
        if guided_fsm is not None:
            vs = getattr(getattr(self.model, "cfg", None),
                         "vocab_size", None)
            if vs is not None and guided_fsm.vocab_size != vs:
                raise ValueError(
                    f"guided_fsm.vocab_size {guided_fsm.vocab_size} != "
                    f"model vocab_size {vs}")
            if (self.cfg.eos_token_id is not None
                    and guided_fsm.eos_id != self.cfg.eos_token_id):
                raise ValueError(
                    f"guided_fsm.eos_id {guided_fsm.eos_id} != engine "
                    f"eos_token_id {self.cfg.eos_token_id}")
            if not guided_fsm.allowed(guided_fsm.start).any():
                raise ValueError("guided_fsm allows no token at its "
                                 "start state (empty language)")
        if prefix_id is not None:
            prefix = self._prefixes.get(prefix_id)
            if prefix is None:
                raise ValueError(f"unknown prefix_id {prefix_id}")
            # prompt_ids is the SUFFIX; the engine re-attaches the
            # prefix tokens (for stop/position bookkeeping) but its KV
            # is adopted from the prefix's pages, never re-prefilled
            prompt = np.concatenate([prefix, prompt])
        elif not self._use_chunked(prompt.size):
            # chunked prompts bypass the buckets; all others must fit one
            self._bucket(prompt.size)  # validate in the caller, not loop
        budget = max_new_tokens or self.cfg.max_new_tokens_default
        if prompt.size + budget > self.cfg.max_seq_len:
            budget = self.cfg.max_seq_len - prompt.size
            if budget <= 0:
                raise ValueError(
                    f"prompt length {prompt.size} exceeds max_seq_len "
                    f"{self.cfg.max_seq_len}")
        never = self._pages.unservable(prompt.size + budget, pins=False)
        if never:
            raise ValueError(never)
        req = _Request(request_id=f"req-{next(self._req_counter)}",
                       prompt=prompt, max_new_tokens=budget,
                       temperature=temperature, top_p=float(top_p),
                       stop_ids=frozenset(stop_token_ids or ()),
                       prefix_id=-1 if prefix_id is None else prefix_id,
                       fsm=guided_fsm,
                       fsm_state=(guided_fsm.start
                                  if guided_fsm is not None else 0),
                       presence_penalty=float(presence_penalty),
                       frequency_penalty=float(frequency_penalty),
                       logit_bias=dict(logit_bias) if logit_bias
                       else None,
                       deadline_ts=deadline_ts, recv_ts=recv_ts,
                       hist=(list(map(int, prompt))
                             if (self.cfg.ngram_speculation > 0
                                 and temperature == 0.0
                                 and guided_fsm is None
                                 and not (presence_penalty
                                          or frequency_penalty
                                          or logit_bias)) else None))
        if recv_ts is not None:
            self._spans.add("request.ingress",
                            max(0, int((req.submit_ts - recv_ts) * 1e9)))
        with self._lock:
            self._requests[req.request_id] = req
        self._waiting.put(req)
        return req.request_id

    def stream(self, request_id: str):
        """Blocking generator of token ids for one request."""
        for tok, _lp in self.stream_detailed(request_id):
            yield tok

    def _taken(self, payload):
        """A consumer took a token: close its `stream.deliver` span, from
        the put time that rode with it."""
        tok, logp, put_ts = payload
        self._spans.add("stream.deliver", max(0, int(
            (time.time() - put_ts) * 1e9)))
        return tok, logp

    def stream_detailed(self, request_id: str):
        """Like stream() but yields (token_id, logprob) — logprob is
        None unless the engine was built with logprobs=True. Parks the
        calling thread between tokens; a consumer on a running event
        loop uses astream_detailed."""
        req = self._requests.get(request_id)
        if req is None:
            raise KeyError(request_id)
        sink = req.sink
        if sink.loop is not None:
            raise RuntimeError(f"{request_id} already streams to an "
                               "awaitable consumer")
        while True:
            # raylint: disable=RT003 the engine loop cannot exit with this
            # request registered: its catch-all errors every active
            # request's queue, failed admits error theirs, and the wedge
            # watchdog aborts stalled requests — while a timeout here
            # would kill legitimate multi-minute first-jit prefills
            kind, payload = sink.q.get()
            if kind == "token":
                yield self._taken(payload)
            else:  # an error or the end marker: the request is over
                with self._lock:
                    self._requests.pop(request_id, None)
                if kind == "error":
                    raise payload
                return

    def astream_detailed(self, request_id: str):
        """stream_detailed for a consumer on the RUNNING event loop: an
        async generator of (token_id, logprob), bound to the loop it was
        created on. From here on the request's items reach that loop in
        the engine's one hand-over per iteration (_hand_over) — no
        thread parks for this stream. Closing the generator before its
        end (the client went away) aborts the engine request."""
        req = self._requests.get(request_id)
        if req is None:
            raise KeyError(request_id)
        sink = _LoopSink(asyncio.get_running_loop(), request_id,
                         self._outbox)
        me = threading.current_thread()     # the loop's, as we run on it
        if me not in self._consumer_threads:
            # one that has ended has taken its clock with it
            self._consumer_threads = {
                t for t in self._consumer_threads if t.is_alive()} | {me}
        with req.sink_lock:
            old = req.sink
            if old.loop is not None:
                raise RuntimeError(f"{request_id} already streams to an "
                                   "awaitable consumer")
            # what the engine put before this consumer attached
            sink._items.extend(old.drain())
            sink.offered = len(sink._items)
            req.sink = sink

        async def consume():
            ended = False
            try:
                while True:
                    kind, payload = await sink.take()
                    if kind == "token":
                        yield self._taken(payload)
                        continue
                    ended = True    # an error or the end marker
                    if kind == "error":
                        raise payload
                    break
            finally:
                sink.closed = True
                if not ended:
                    self.abort(request_id)
                with self._lock:
                    self._requests.pop(request_id, None)
        return consume()

    def abort(self, request_id: str) -> None:
        """Best-effort early termination. Decoding requests collapse
        their budget to what they have already generated, so the engine
        releases the slot at the next drain (the consumer should keep
        draining to the end marker; a few lagged tokens may still
        arrive). Requests that have not produced a token yet — still
        queued or chunk-prefilling — are cancelled outright: no prefill
        runs, no token is forced."""
        req = self._requests.get(request_id)
        if req is None:
            return
        req.aborted = True
        self._event("llm_engine.request_abort", req=req,
                    generated=req.generated)
        if req.generated == 0 and req.slot == -1:
            # still in _waiting: the loop discards it at admission;
            # unblock the consumer immediately (a duplicate end marker
            # from a concurrent admission is harmless — the consumer
            # stops at the first one)
            self._put(req, _END)
        elif req.generated > 0:
            req.max_new_tokens = min(req.max_new_tokens, req.generated)
        # else: slot assigned but no token yet (chunk-prefilling / prefill
        # in flight) — the loop cancels it at its next touch point

    def precompile(self) -> None:
        """Warm every jitted path before real traffic: one dummy request
        per prefill bucket plus one chunked prompt when chunking is on,
        each generating 2 tokens (prefill sample + one decode step).
        Blocks until the dummy streams drain; afterwards all slots are
        free again (stats do count the dummy work)."""
        rids = []
        prev = 0
        for b in sorted(self.cfg.prefill_buckets):
            if b > self.cfg.max_seq_len:
                continue
            # smallest prompt length that maps to THIS bucket and takes
            # the bucket (non-chunked) path — a length-b dummy would be
            # routed through chunked prefill whenever b > prefill_chunk,
            # leaving the bucket's jit cold (review r4)
            n = min(b, self.cfg.max_seq_len - 2)
            if self.cfg.prefill_chunk > 0:
                n = min(n, self.cfg.prefill_chunk)
            n = max(1, n)
            if n <= prev:
                prev = b
                continue  # no non-chunked prompt can reach this bucket
            rids.append(self.submit(np.ones((n,), np.int32),
                                    max_new_tokens=2))
            prev = b
        if self.cfg.prefill_chunk > 0:
            n = max(1, min(self.cfg.prefill_chunk + 1,
                           self.cfg.max_seq_len - 2))
            rids.append(self.submit(np.ones((n,), np.int32),
                                    max_new_tokens=2))
        for rid in rids:
            for _ in self.stream(rid):
                pass
        if self.cfg.max_prefixes > 0:
            # Warm registration + adoption + the per-bucket chunk
            # kernels by EXECUTING dummy prefix'd requests against a
            # scratch prefix (pid == max_prefixes — never handed out),
            # one suffix length per reachable chunk width. AOT
            # lower().compile() would NOT populate the jit call cache.
            scratch = self.cfg.max_prefixes
            self._run_on_loop(lambda: self._register_prefix_paged(
                scratch, np.ones((2,), np.int32)))
            widths = ({self.cfg.prefill_chunk}
                      if self.cfg.prefill_chunk > 0 else
                      {b for b in self.cfg.prefill_buckets
                       if b <= self.cfg.max_seq_len})
            lens = {max(1, min(w, self.cfg.max_seq_len - 4))
                    for w in widths}
            if self.cfg.prefill_chunk <= 0 and widths:
                # a suffix LONGER than the largest bucket dispatches the
                # (largest, sample=False) multi-chunk variant — the one
                # width-suffix pairs above can never reach (per-dispatch
                # widths always cover the remaining suffix)
                lens.add(max(1, min(max(widths) + 1,
                                    self.cfg.max_seq_len - 4)))
            warm = []
            for n in sorted(lens):
                warm.append(self.submit(np.ones((n,), np.int32),
                                        max_new_tokens=2,
                                        prefix_id=scratch))
            for rid in warm:
                for _ in self.stream(rid):
                    pass
            self._run_on_loop(
                lambda: self._unregister_prefix_paged(scratch))
            self.stats["prefix_tokens_saved"] = 0   # dummy adoptions

    def generate_sync(self, prompt_ids, max_new_tokens=None,
                      temperature: float = 0.0, top_p: float = 1.0,
                      stop_token_ids=None,
                      prefix_id: Optional[int] = None,
                      guided_fsm=None, presence_penalty: float = 0.0,
                      frequency_penalty: float = 0.0,
                      logit_bias: Optional[dict] = None) -> List[int]:
        rid = self.submit(prompt_ids, max_new_tokens, temperature,
                          top_p=top_p, stop_token_ids=stop_token_ids,
                          guided_fsm=guided_fsm,
                          presence_penalty=presence_penalty,
                          frequency_penalty=frequency_penalty,
                          logit_bias=logit_bias,
                          prefix_id=prefix_id)
        return list(self.stream(rid))

    def get_stats(self) -> Dict[str, Any]:
        with self._lock:
            out = {**self.stats,
                   "prefill_shapes": dict(self.stats["prefill_shapes"]),
                   "device": dict(self.device),
                   "active": len(self._active),
                   "waiting": self._waiting.qsize(),
                   "prefilling": len(self._prefilling),
                   "free_slots": len(self._free_slots)}
            out["kv_pages"] = self._pages.kv_pages()
            # bytes a cached token takes over all layers (the pool's
            # rows as they are stored)
            out["kv_bytes_per_token"] = self._pages.kv_bytes_per_token
            # bytes a sequence's recurrent state takes over the layers
            # that keep one (0: every layer pages)
            out["state_bytes_per_slot"] = \
                self._pages.state_bytes_per_slot
            # what the in-flight target is derived from, as it reads now
            out["inflight_depth"] = self._depth.readings(
                self.cfg.pipeline_depth)
            samples = list(self._ttft_samples)
            tpots = sorted(self._tpot_samples)
        if tpots:
            out["tpot_p50_ms"] = round(
                tpots[len(tpots) // 2] * 1000, 2)
        if samples:
            def p50(key):
                # ingress_ms is there only for requests a proxy stamped
                vals = sorted(s[key] for s in samples if key in s)
                return round(vals[len(vals) // 2], 1) if vals else None
            medians = {k: p50(k) for k in (
                "queue_ms", "prefill_dispatch_ms", "emit_ms", "total_ms",
                "ingress_ms")}
            out["ttft_breakdown_p50_ms"] = {
                k: v for k, v in medians.items() if v is not None}
        out["prefill_compile_ms"] = dict(self._prefill_compile_ms)
        out["spans"] = self._spans.snapshot()
        out["threads"] = thread_clocks(engine=[self._loop_thread],
                                       consumers=self._consumer_threads)
        compiles = self._spans.compiles()
        out["compiles"] = {k: v[0] for k, v in compiles.items()}
        out["compile_ns"] = {k: v[1] for k, v in compiles.items()}
        mem = self._jax.devices()[0].memory_stats()  # None on the CPU
        if mem:
            out["peak_device_bytes"] = mem.get("peak_bytes_in_use")
        return out

    def shutdown(self):
        self._shutdown.set()
        self._spans.unwatch_gc()

    # ---- wedged-engine watchdog -------------------------------------------
    @property
    def wedged(self) -> bool:
        """True once the watchdog declared this engine wedged (sticky:
        the replica is about to fail health checks and be replaced —
        un-wedging a half-dead engine under traffic is not a state we
        try to recover)."""
        return self._wedged_since is not None

    def _note_progress(self) -> None:
        self._progress_ts = time.time()

    def _has_work(self) -> bool:
        return bool(self._active or self._prefilling
                    or not self._waiting.empty())

    # In-dispatch stall budget multiplier: a first-use jit compile is a
    # legitimate multi-second (big models: multi-minute — use
    # precompile=True) stall inside a dispatch, indistinguishable
    # in-flight from a hung device call. Give dispatches grace x the
    # budget so compiles pass and true device hangs are still caught.
    _DISPATCH_GRACE = 10.0

    # A consumer whose sink stays at its bound this long without taking
    # a single token is treated as gone and its request aborted (see
    # _put_token) — the bound that keeps per-request backpressure from
    # parking the shared loop, or piling up tokens, indefinitely.
    _CONSUMER_STALL_TTL_S = 60.0

    def _watchdog_loop(self) -> None:
        period = max(0.05, min(1.0, self._watchdog_s / 4.0))
        while not self._shutdown.is_set():
            self._shutdown.wait(period)
            if self._wedged_since is not None:
                continue
            if not self._has_work():
                # idle is not wedged; keep the clock fresh so the first
                # request after a quiet hour isn't instantly blamed
                self._note_progress()
                continue
            budget = self._watchdog_s * (
                self._DISPATCH_GRACE if self._in_dispatch else 1.0)
            stall = time.time() - self._progress_ts
            if stall <= budget:
                continue
            self._declare_wedged(stall)

    def _declare_wedged(self, stall_s: float) -> None:
        from ...exceptions import EngineWedgedError  # noqa: PLC0415
        self._wedged_since = time.time()
        self._event("llm_engine.wedged",
                    f"no forward progress for {stall_s:.1f}s "
                    f"(watchdog_s={self._watchdog_s}); aborting "
                    f"in-flight requests", stall_s=round(stall_s, 2),
                    active=len(self._active),
                    waiting=self._waiting.qsize())
        err = EngineWedgedError(
            f"engine wedged: no forward progress for {stall_s:.1f}s "
            f"(> RAY_TPU_ENGINE_WATCHDOG_S={self._watchdog_s}); "
            "request aborted for failover")
        # deliberately lock-free: if the loop wedged while HOLDING the
        # engine lock, taking it here would hang the watchdog too; a
        # snapshot of the dict values is safe to iterate in CPython
        reqs = list(self._requests.values())
        for req in reqs:
            req.aborted = True
            # error (not _END) so consumers raise and the serve handle
            # fails the stream over to a healthy replica; bounded put —
            # a full queue (slow consumer) must not swallow the error
            self._put(req, ("error", err))
        # the loop thread is stuck, so its hand-over will not come
        self._hand_over()

    def _chaos_stall(self, seconds: float) -> None:
        """Deterministic wedge injection (serve/chaos.py, tests): park
        the engine loop thread via the control queue — the real
        watchdog path then observes the stall exactly as it would a
        hung device call. Returns immediately."""
        from concurrent.futures import Future  # noqa: PLC0415
        self._control_q.put((lambda: time.sleep(seconds), Future()))

    # ---- engine loop ------------------------------------------------------
    def _bucket(self, n: int) -> int:
        for b in self.cfg.prefill_buckets:
            if n <= b and b <= self.cfg.max_seq_len:
                return b
        raise ValueError(f"prompt length {n} exceeds largest prefill "
                         f"bucket {self.cfg.prefill_buckets[-1]}")

    def _largest_bucket(self) -> int:
        """0 when NO bucket fits max_seq_len — callers that need a
        usable width must supply their own fallback (a non-zero default
        here would flip _use_chunked's always-chunk invariant)."""
        return max((b for b in self.cfg.prefill_buckets
                    if b <= self.cfg.max_seq_len), default=0)

    def _chunk_for(self, remaining: int) -> int:
        """Chunk width for one chunked-prefill dispatch. With chunking
        on, the configured chunk. Otherwise (prefix-adoption fallback)
        the SMALLEST bucket covering the remaining suffix — a short
        suffix after a long prefix must not pay a largest-bucket-wide
        model pass (that would out-cost the prefill the prefix cache
        saved)."""
        if self.cfg.prefill_chunk > 0:
            return self.cfg.prefill_chunk
        for b in sorted(self.cfg.prefill_buckets):
            if remaining <= b <= self.cfg.max_seq_len:
                return b
        return self._largest_bucket() or self.cfg.max_seq_len

    def _use_chunked(self, n: int) -> bool:
        """Chunked prefill serves prompts longer than prefill_chunk AND
        any prompt that overflows the largest bucket (so bucket coverage
        never rejects what the chunked path could handle)."""
        if self.cfg.prefill_chunk <= 0:
            return False
        return n > self.cfg.prefill_chunk or n > self._largest_bucket()

    def _take_slot(self, req: _Request) -> int:
        """Admission into a free slot; `slot.refill` is how long the
        slot stood empty since its last _release, `slot.refill.starved`
        the part of that before this request was submitted (0: it was
        waiting here and the admission pass was late; all of it: the
        engine had nothing to put into the slot)."""
        slot = self._free_slots.pop()
        req.slot = slot
        req.admit_ts = time.time()
        freed = self._slot_freed_ns.pop(slot, None)
        if freed is not None:
            now = time.perf_counter_ns()
            self._spans.add("slot.refill", now - freed)
            self._spans.add("slot.refill.starved",
                            max(0, min(req.submit_ns, now) - freed))
        return slot

    def _admit_paged(self, req: _Request) -> str:
        """Admission: reserve pages + a slot. Returns "ok",
        "nopages" (hold the request), or "failed" (stream errored).
        Prefix-carrying requests share the prefix's full pages by
        page-table reference and copy only its partial last page."""
        n_tokens = req.prompt.size + req.max_new_tokens
        # submit()'s check could not see pins made after it
        never = self._pages.unservable(n_tokens, req.prefix_id)
        if never:
            self._fail_admission(req, ValueError(never))
            return "failed"
        got = self._pages.reserve(self._free_slots[-1], n_tokens,
                                  req.prefix_id)
        if got is None:
            return "nopages"
        _row, restart, copy = got
        self._take_slot(req)
        if copy is not None:
            try:
                self._pools = self._runtime(
                    self._copy_page_jit, self._pools, *map(np.int32, copy))
            except BaseException as e:  # noqa: BLE001
                self._fail_admission(req, e)
                return "failed"
        req.prefill_pos = restart
        self.stats["prefix_tokens_saved"] += restart
        return "ok"

    def _fail_admission(self, req: _Request, error) -> None:
        """This request goes no further: its pages and slot (if it has
        them) go back, its stream gets the error and ends."""
        if req.slot >= 0:
            self._pages.release(req.slot)
            self._free_slots.append(req.slot)
            req.slot = -1
        self._put(req, ("error", error))
        self._put(req, _END)

    def _admit_all(self, inflight) -> None:
        """Dispatch prefills for every waiting request that can get a
        slot — back to back, NO host syncs. Requests sharing a length
        bucket prefill TOGETHER (up to max_prefill_batch per call); the
        sampled first tokens drain through the same pipeline as decode
        steps, preserving per-request emission order."""
        taken: List[tuple] = []
        while self._free_slots:
            if self._pending_head is not None:
                req, self._pending_head = self._pending_head, None
            else:
                try:
                    req = self._waiting.get_nowait()
                except queue_mod.Empty:
                    break
            if req.aborted:
                # cancelled before admission: abort() already unblocked
                # the consumer; never take a slot or prefill
                self._requests.pop(req.request_id, None)
                continue
            if (req.deadline_ts is not None
                    and time.time() >= req.deadline_ts):
                # deadline expired while queued: shed instead of
                # spending prefill+decode on an answer nobody waits for
                self._shed_expired(req)
                continue
            self._progress_ts = time.time()   # watchdog: admission
            outcome = self._admit_paged(req)
            if outcome == "nopages":
                # hold the head request (FIFO — Queue has no
                # push-front) until releases replenish the pool
                self._pending_head = req
                if not getattr(req, "preempt_emitted", False):
                    req.preempt_emitted = True
                    self._event("llm_engine.request_preempt",
                                "KV page pool exhausted; holding "
                                "at admission", req=req)
                break
            if outcome == "failed":
                continue
            self._event("llm_engine.request_admit", req=req,
                        slot=req.slot, prompt_len=int(
                            req.prompt.size))
            if req.prefix_id >= 0 or self._use_chunked(
                    req.prompt.size):
                # an adopted prefix's suffix, or a long prompt: prefill
                # in chunks interleaved with decode steps (one chunk per
                # loop iteration)
                self._prefilling.append(req)
            else:
                taken.append((self._bucket(req.prompt.size), req,
                              req.slot))
        if not taken:
            return
        groups: Dict[int, List[tuple]] = {}
        for pad_len, req, slot in taken:
            groups.setdefault(pad_len, []).append((req, slot))
        cap = max(1, self.cfg.max_prefill_batch)
        for pad_len, members in groups.items():
            for i in range(0, len(members), cap):
                group = members[i:i + cap]
                with self._spans.span(
                        "engine.prefill_dispatch", bucket=pad_len,
                        group=len(group),
                        group_padded=_next_pow2(len(group))):
                    self._dispatch_prefill(inflight, pad_len, group)

    def _dispatch_prefill(self, inflight, pad_len: int, members) -> None:
        """One prefill call for `members` = [(req, slot), ...] of a
        shared bucket; group size pads to a power of two (scratch slot
        rows) so compile count stays O(buckets * log2(cap))."""
        g_real = len(members)
        g = _next_pow2(g_real)
        t_dispatch = time.time()
        try:
            # padding rows hit the scratch slot, whose page row is
            # all-trash
            tokens = np.zeros((g, pad_len), np.int32)
            slots = np.full((g,), self._scratch_slot, np.int32)
            lens = np.ones((g,), np.int32)
            temps = np.zeros((g,), np.float32)
            top_ps = np.ones((g,), np.float32)
            for i, (req, slot) in enumerate(members):
                tokens[i, :req.prompt.size] = req.prompt
                slots[i] = slot
                lens[i] = req.prompt.size
                temps[i] = req.temperature
                top_ps[i] = req.top_p
            allow = self._guided_prefill_allow(
                [r for r, _ in members], g)
            kw = {} if allow is None else {"allow": allow}
            pbias = self._pen_prefill_bias(
                [r for r, _ in members], g)
            if pbias is not None:
                kw["bias"] = pbias
            # all g rows come back (then a counting model's counters);
            # the drain reads the first g_real
            toks_dev, lps_dev, _ = self._step(
                self._prefill_paged_jit, (
                    np.int32(g_real), slots, lens, temps, top_ps,
                    self._pages.rows(), tokens), pad_len=pad_len, **kw)
        except BaseException as e:  # noqa: BLE001
            for req, _slot in members:
                self._fail_admission(req, e)
            return
        dispatch_ms = (time.time() - t_dispatch) * 1000
        # first dispatch of a bucket blocks on its jit compile: record it
        self._prefill_compile_ms.setdefault(pad_len, round(dispatch_ms, 1))
        self.stats["prefills"] += g_real
        self._count_prefill(pad_len, g_real, g, sum(
            int(req.prompt.size) for req, _ in members))
        for req, slot in members:
            req.prefill_dispatch_ms = dispatch_ms
            self._pages.set_length(slot, req.prompt.size)
            self._active[slot] = req
        self._mask_dirty = True
        self._pen_coef_dirty = True
        self._start_fetch(toks_dev)
        if self.cfg.logprobs:
            self._start_fetch(lps_dev)
        inflight.append(("prefill_batch", [r for r, _ in members],
                         toks_dev, lps_dev if self.cfg.logprobs else None,
                         time.perf_counter_ns(), False))

    def _count_prefill(self, width: int, rows: int, rows_padded: int,
                       tokens: int) -> None:
        """One prefill program dispatched: `rows` prompts holding
        `tokens` prompt tokens ran as `rows_padded` x `width`."""
        st = self.stats
        st["prefill_calls"] += 1
        st["prefill_rows_real"] += rows
        st["prefill_rows_padded"] += rows_padded
        st["prefill_tokens_real"] += tokens
        st["prefill_tokens_padded"] += rows_padded * width
        shape = f"{width}x{rows_padded}"
        st["prefill_shapes"][shape] = st["prefill_shapes"].get(shape, 0) + 1

    def _dispatch_chunk(self, inflight) -> None:
        """Advance the oldest chunk-prefilling request by ONE chunk. The
        final chunk samples the first token and activates the slot."""
        req = self._prefilling[0]
        if req.aborted:
            # cancelled mid-chunk-prefill: drop remaining chunks, free
            # the slot, close the stream with no token forced
            self._prefilling.popleft()
            self._release(req)
            return
        start = req.prefill_pos
        C = self._chunk_for(req.prompt.size - start)
        true = min(C, req.prompt.size - start)
        is_last = start + true >= req.prompt.size
        tokens = np.zeros((1, C), np.int32)
        tokens[0, :true] = req.prompt[start:start + true]
        t_dispatch = time.time()
        try:
            kw = {}
            if is_last and req.fsm is not None:
                kw["allow"] = self._guided_prefill_allow([req], 1)
            if is_last and req.logit_bias:
                kw["bias"] = self._pen_prefill_bias([req], 1)
            toks_dev, lps_dev, _ = self._step(
                self._chunk_paged_jit, (
                    np.int32(req.slot), np.int32(start),
                    np.int32(start + true), np.float32(req.temperature),
                    np.float32(req.top_p), self._pages.rows(), tokens),
                chunk=C, sample=is_last, **kw)
        except BaseException as e:  # noqa: BLE001
            self._prefilling.popleft()
            self._fail_admission(req, e)
            return
        req.prefill_pos = start + true
        self._pages.set_length(req.slot, req.prefill_pos)
        req.prefill_dispatch_ms += (time.time() - t_dispatch) * 1000
        self._count_prefill(C, 1, 1, true)
        self._progress_ts = time.time()   # watchdog: chunk advanced
        if is_last:
            self._prefilling.popleft()
            self.stats["prefills"] += 1
            self._active[req.slot] = req
            self._mask_dirty = True
            self._pen_coef_dirty = True
            self._start_fetch(toks_dev)
            if self.cfg.logprobs:
                self._start_fetch(lps_dev)
            inflight.append(("prefill_chunk", [req], toks_dev,
                             lps_dev if self.cfg.logprobs else None,
                             time.perf_counter_ns(), False))

    def _runtime(self, fn, *args):
        """A call of the loop into the JAX runtime that is no step
        program (the prefix page copy, a penalty row's seeding)."""
        self.stats["runtime_calls"] += 1
        return self._spans.call("runtime.other", fn, *args)

    def _step(self, program, parts, *args, **kw):
        """Enqueue one step program on the carried state. `parts` are
        its host arguments in its own _unpack order; the allocator's
        pending length edits go in front.
        Returns (fetch, logps, what else the program returns)."""
        ctl = _pack(self._pages.take_length_edits(), *parts)
        self.stats["runtime_calls"] += 1
        fetch, logps, pools, state, *rest = self._spans.call(
            "runtime.step", program, self.params, self._pools,
            self._state, ctl, *args, **kw)
        self._spans.call("step.release", self._carry, pools, state)
        return fetch, logps, rest

    def _carry(self, pools, state):
        """Rebind the carried pools and state to a step's results. The
        leaves they held were donated into that step; here their last
        references go and the runtime destroys each (`step.release`)."""
        self._pools, self._state = pools, state

    def _start_fetch(self, arr):
        self.stats["runtime_calls"] += 1
        try:
            self._spans.call("runtime.fetch_start", arr.copy_to_host_async)
        except (AttributeError, NotImplementedError):
            pass  # fetch happens synchronously at drain time instead

    def _emit(self, req: _Request, tok: int,
              logp: Optional[float] = None):
        req.generated += 1
        self.stats["tokens_generated"] += 1
        # one clock read: the watchdog's forward progress, the first
        # token's stamp, and the put time that rides with the token
        # (stream_detailed takes `stream.deliver` from it)
        now = self._progress_ts = time.time()
        if req.first_token_ts is None:
            req.first_token_ts = now
            admit = req.admit_ts or req.submit_ts
            sample = {
                "queue_ms": (admit - req.submit_ts) * 1000,
                "prefill_dispatch_ms": req.prefill_dispatch_ms,
                "emit_ms": max(0.0, (now - admit) * 1000
                               - req.prefill_dispatch_ms),
                "total_ms": (now - req.submit_ts) * 1000}
            if req.recv_ts is not None:
                sample["ingress_ms"] = max(
                    0.0, (req.submit_ts - req.recv_ts) * 1000)
            self._ttft_samples.append(sample)
            self._m["ttft"].observe(now - req.submit_ts, tags=self._mtags)
        if req.hist is not None:
            req.hist.append(tok)
        self._put_token(req, ("token", (tok, logp, now)))
        if ((self.cfg.eos_token_id is not None
             and tok == self.cfg.eos_token_id)
                or tok in req.stop_ids):
            req.max_new_tokens = req.generated  # finish after EOS/stop
        if req.fsm is not None:
            # guided: advance the automaton; a dead state (can't happen
            # under the mask, but belt-and-braces) or a completed match
            # ends the request like EOS
            req.fsm_state = req.fsm.advance(req.fsm_state, tok)
            if (req.fsm_state < 0
                    or req.fsm.is_complete(req.fsm_state)):
                req.max_new_tokens = min(req.max_new_tokens,
                                         req.generated)

    # ---- hand-off to the consumers ----------------------------------------
    def _put(self, req: _Request, item) -> None:
        """Publish a control item (an error, the end marker) to the
        request's consumer: never blocks, never refused."""
        with req.sink_lock:
            req.sink.force(item)

    def _put_token(self, req: _Request, item) -> None:
        """Publish a token. A sink that refuses it is at its bound: the
        CONSUMER is slow or gone, not the engine wedged — refresh the
        watchdog clock meanwhile so per-request backpressure can't get
        the whole replica declared wedged and replaced. A blocking
        consumer is waited for, a second at a time; an awaitable one
        cannot be, so its token rides past the bound while the clock
        runs. The clock restarts whenever the consumer has taken
        something since it was last read (slow is not gone). Either way
        the stall is bounded: a consumer that takes nothing for
        _CONSUMER_STALL_TTL_S (abandoned generator, crashed client that
        never cancelled) gets its request aborted so one dead reader can
        neither stall the shared loop nor pile up tokens forever while
        keeping the watchdog green. What an awaitable sink may hold is
        therefore _SINK_BOUND plus one TTL of decoding for a consumer
        that stopped, and the request's own budget for one that is
        slower than the engine."""
        while True:
            with req.sink_lock:
                sink = req.sink
                # an awaitable sink answers at once: under the lock
                offered = sink.loop is not None and sink.offer(item)
            if sink.loop is None:
                # this may wait a second for room, so not under the lock
                # (abort() and astream_detailed take it on the actor's
                # event loop)
                offered = sink.offer(item)
                if offered:
                    self.stats["deliver_blocking_tokens"] += 1
                    with req.sink_lock:
                        if req.sink is not sink:
                            # an awaitable consumer attached meanwhile,
                            # and may have emptied the queue before this
                            # token landed in it: carry the rest over
                            for left in sink.drain():
                                req.sink.force(left)
            if offered:
                req.stalled_since = None
                return
            if req.aborted:
                return
            now = time.time()
            if req.stalled_since is None:
                req.stalled_since, req.stalled_taken = now, sink.taken
                # flag the stall while it is still LIVE so hangs the
                # TTL will later mitigate show up in `stuck` output
                # and post-mortems as they happen
                self._event("sched.hang.suspected",
                            "request output sink full; consumer "
                            "stalled (TTL abort after "
                            f"{self._CONSUMER_STALL_TTL_S:.0f}s)",
                            req=req, kind="consumer_stalled")
            elif sink.taken != req.stalled_taken:
                # behind its bound but still taking: the clock restarts,
                # as a blocking consumer's does with every token it
                # makes room for
                req.stalled_since, req.stalled_taken = now, sink.taken
            elif now - req.stalled_since > self._CONSUMER_STALL_TTL_S:
                req.aborted = True
                req.max_new_tokens = min(req.max_new_tokens,
                                         req.generated)
                self._event("llm_engine.request_abort", req=req,
                            generated=req.generated,
                            reason="consumer_stalled")
                # hang-mitigation telemetry: the TTL abort IS a
                # resolved hang — make it visible to the wait plane's
                # post-mortems, not just the engine log
                self._event("sched.hang.resolved",
                            f"consumer stalled "
                            f"{now - req.stalled_since:.0f}s; request "
                            "aborted by the consumer-stall TTL",
                            req=req, kind="consumer_stalled",
                            stalled_s=round(now - req.stalled_since, 1))
                return
            self._progress_ts = now
            if sink.loop is not None:
                self._put(req, item)
                return

    def _hand_over(self) -> None:
        """Carry what the outbox holds — every (request, item) put since
        the last hand-over: tokens, errors and end markers alike — to
        the consumers' event loops, one call_soon_threadsafe per loop
        (there is one: the replica's). The engine loop calls it once an
        iteration, after its drain; the watchdog once, for a wedged
        loop."""
        box = self._outbox
        if not box:
            return
        with self._spans.span("engine.deliver"):
            by_loop: Dict[Any, list] = {}
            while True:
                try:
                    pair = box.popleft()
                except IndexError:
                    break
                by_loop.setdefault(pair[0].loop, []).append(pair)
            for loop, batch in by_loop.items():
                self.stats["deliver_batches"] += 1
                self.stats["deliver_items"] += len(batch)
                try:
                    loop.call_soon_threadsafe(_LoopSink.deliver, batch)
                except RuntimeError:
                    # the loop was closed under its consumers: nobody
                    # will take these, so stop decoding for them
                    for sink in {s for s, _ in batch}:
                        sink.closed = True
                        self.abort(sink.request_id)
                        self._requests.pop(sink.request_id, None)

    def _shed_expired(self, req: _Request) -> None:
        """Queued request whose propagated deadline passed: error the
        consumer (typed, retriable upstream decision) without ever
        taking a slot. Load shedding, not failure containment."""
        from ...exceptions import DeadlineExceededError  # noqa: PLC0415
        self._requests.pop(req.request_id, None)
        self._event("serve.request.shed", req=req,
                    reason="deadline_expired",
                    late_s=round(time.time() - req.deadline_ts, 3))
        from ...util import events as events_mod  # noqa: PLC0415
        events_mod.emit_safe(
            counter="ray_tpu_serve_requests_shed_total",
            counter_tags={"reason": "deadline_expired"})
        self._put(req, ("error", DeadlineExceededError(
            f"deadline expired {time.time() - req.deadline_ts:.3f}s "
            f"before engine admission of {req.request_id}")))

    def _release(self, req: _Request):
        # Slot bookkeeping FIRST, end marker LAST: putting _END wakes the
        # consumer thread — publishing completion before the slot leaves
        # _active let clients observe (and act on) a request that looked
        # finished while still holding engine state (soak regression: a
        # drained request lingering in _active with its slot already
        # re-freed).
        # The finally guarantees the consumer ALWAYS unblocks, even if a
        # bookkeeping dispatch raises.
        try:
            if req.slot >= 0:
                self._pages.release(req.slot)
                self._free_slots.append(req.slot)
                self._slot_freed_ns[req.slot] = time.perf_counter_ns()
                self._active.pop(req.slot, None)
                self._mask_dirty = True
                self._pen_coef_dirty = True
                req.slot = -1
            if req.first_token_ts is not None and req.generated > 1:
                tpot = ((time.time() - req.first_token_ts)
                        / (req.generated - 1))
                self._tpot_samples.append(tpot)
                try:
                    self._m["tpot"].observe(tpot, tags=self._mtags)
                except Exception:
                    pass
        finally:
            self._event("llm_engine.request_finish", req=req,
                        generated=req.generated, aborted=req.aborted)
            # bounded end-marker publish: a full queue (stalled/gone
            # consumer, e.g. the _CONSUMER_STALL_TTL_S abort path)
            # must not park the loop on a blocking put
            self._put(req, _END)

    def _count_decode(self, window: int) -> None:
        """One decode dispatch's pages, as the allocator counts them,
        and the state rows of a model that keeps per-slot state."""
        st, layers = self.stats, self._pages.n_state_layers
        in_window, live = self._pages.decode_pages(window, self._decode_new)
        st["decode_pages_window"] += in_window
        st["decode_pages_live"] += live
        if layers:
            st["decode_state_rows_window"] += self._n_slots * layers
            st["decode_state_rows_live"] += len(self._active) * layers

    def _propose_ngram(self, req) -> "Optional[List[int]]":
        """Prompt-lookup proposal: the K tokens that followed the most
        recent earlier occurrence of the trailing `ngram_order`-gram in
        this request's own history. None = no match (plain decode)."""
        k = self.cfg.ngram_speculation
        g = max(1, self.cfg.ngram_order)
        h = req.hist
        if h is None or len(h) < g + 1:
            return None
        key = h[-g:]
        lo = max(0, len(h) - g - 1 - max(g + 1, self.cfg.ngram_lookback))
        for i in range(len(h) - g - 1, lo - 1, -1):
            if h[i:i + g] == key:
                prop = h[i + g:i + g + k]
                return prop or None
        return None

    def _guided_prefill_allow(self, reqs, g: int):
        """(g, V) bool mask rows for a prefill group (padding rows all
        True); None when no member is guided."""
        fsms = [r.fsm for r in reqs if r.fsm is not None]
        if not fsms:
            return None
        V = fsms[0].vocab_size
        A = np.ones((g, V), dtype=bool)
        for i, r in enumerate(reqs):
            if r.fsm is not None:
                A[i] = r.fsm.allowed(r.fsm_state)
        return A

    def _guided_decode_allow(self):
        """(S, V) bool mask over all slots for one decode step; None
        when no active request is guided (the unguided decode call then
        stays byte-identical to the ungated build). The host buffer is
        kept across steps and only rows whose FSM state moved are
        rewritten — per step the cost is one copy (the program's own
        upload may alias what it is given, so it never gets the buffer
        that the next step rewrites), not a full rebuild."""
        guided = {slot: r for slot, r in self._active.items()
                  if r.fsm is not None}
        if not guided:
            self._guided_prev = None
            return None
        V = next(iter(guided.values())).fsm.vocab_size
        buf = self._guided_allow_buf
        prev = self._guided_prev
        if buf is None or buf.shape != (self._n_slots, V) \
                or prev is None:
            buf = self._guided_allow_buf = np.ones(
                (self._n_slots, V), dtype=bool)
            prev = {}
        for slot in [sl for sl in prev if sl not in guided]:
            buf[slot] = True
            del prev[slot]
        for slot, r in guided.items():
            # key on the request_id, NOT id(r): a freed _Request's
            # address can be reused by a new guided request, which
            # would then silently inherit the stale mask row
            key = (r.request_id, r.fsm_state)
            if prev.get(slot) != key:
                buf[slot] = r.fsm.allowed(r.fsm_state)
                prev[slot] = key
        self._guided_prev = prev
        return buf.copy()

    def _spec_plan(self):
        """Proposals (S, K) int32 for one
        speculative verify step, or None when no active slot proposes
        anything or any slot is too close to max_seq_len (the verify
        forward writes K+1 positions). Spec-eligible requests exist
        only when cfg.ngram_speculation > 0 (req.hist gating)."""
        k = self.cfg.ngram_speculation
        if not k:
            return None
        eligible = [(slot, r) for slot, r in self._active.items()
                    if r.hist is not None]
        if not eligible:
            return None
        props = np.full((self._n_slots, k), -1, np.int32)
        any_prop = False
        for slot, r in self._active.items():
            if r.prompt.size + r.generated + k + 1 > self.cfg.max_seq_len:
                return None  # one overlong slot vetoes the step
        for slot, r in eligible:
            p = self._propose_ngram(r)
            if p:
                props[slot, :len(p)] = p
                any_prop = True
        if not any_prop:
            self._spec_idle += 1
            return None
        self._spec_idle = 0
        return props

    def _spec_sync_active(self) -> bool:
        """True when speculation wants synchronous stepping (any
        spec-eligible active request): proposals derive from tokens the
        host must have seen."""
        if not self.cfg.ngram_speculation or not any(
                r.hist is not None for r in self._active.values()):
            return False
        # backoff: after 8 consecutive no-proposal steps fall back to
        # pipelined plain decode (sync-only costs throughput for
        # nothing); periodically re-probe in case repetition develops
        self._spec_retry = (self._spec_retry + 1) % 64
        if self._spec_retry == 0:
            self._spec_idle = 0
        return self._spec_idle < 8

    @staticmethod
    def _bias_row(r, V: int) -> "np.ndarray":
        row = np.zeros((V,), np.float32)
        for tid, b in (r.logit_bias or {}).items():
            tid = int(tid)
            if 0 <= tid < V:
                row[tid] = float(b)
        return row

    @staticmethod
    def _req_has_pen(r) -> bool:
        return bool(r.presence_penalty or r.frequency_penalty
                    or r.logit_bias)

    def _pen_active(self) -> bool:
        return any(self._req_has_pen(r) for r in self._active.values())

    def _pen_args(self):
        """(counts, static_bias, presence, freq) for one decode step
        (the first two device state, the coefficients host rows), or
        None when no active request uses penalties. Seeds count/static
        rows exactly once per slot assignment, each by one small program
        (the engine loop is the only mutator, and always holds the
        LATEST arrays — prior ones were donated)."""
        if not self._pen_active():
            return None
        V = int(self.model.cfg.vocab_size)
        S = self._n_slots
        if self._pen_counts is None:
            jnp = self._jnp
            self._pen_counts = self._runtime(jnp.zeros, (S, V), jnp.int32)
            self._pen_static = self._runtime(jnp.zeros, (S, V),
                                             jnp.float32)
        for slot, r in self._active.items():
            if self._pen_seeded.get(slot) == r.request_id:
                continue
            self._pen_seeded[slot] = r.request_id
            self._pen_counts, self._pen_static = self._runtime(
                self._pen_seed_jit, self._pen_counts, self._pen_static,
                np.int32(slot), self._bias_row(r, V))
        for slot in [sl for sl in self._pen_seeded
                     if sl not in self._active]:
            del self._pen_seeded[slot]
        if self._pen_coef_dirty or self._pen_coef is None:
            pres = np.zeros((S,), np.float32)
            freq = np.zeros((S,), np.float32)
            for slot, r in self._active.items():
                pres[slot] = r.presence_penalty
                freq[slot] = r.frequency_penalty
            self._pen_coef = (pres, freq)
            self._pen_coef_dirty = False
        return (self._pen_counts, self._pen_static, *self._pen_coef)

    def _pen_prefill_bias(self, reqs, g: int):
        """(g, V) static logit_bias rows for a prefill group's first
        sampled tokens (presence/frequency are zero then); None when no
        member has a logit_bias."""
        if not any(r.logit_bias for r in reqs):
            return None
        V = int(self.model.cfg.vocab_size)
        B = np.zeros((g, V), np.float32)
        for i, r in enumerate(reqs):
            B[i] = self._bias_row(r, V)
        return B

    def _decode_ctl(self, window: int) -> tuple:
        """A decode or verify program's host arguments: (active mask,
        temperatures, top-p) over the slots, rebuilt only when the
        active set changed, and the window's columns of the page table
        as they stand now."""
        if self._mask_dirty or self._mask_temps is None:
            S = self._n_slots
            mask = np.zeros((S,), np.int32)
            temps = np.zeros((S,), np.float32)
            top_ps = np.ones((S,), np.float32)
            for slot, req in self._active.items():
                mask[slot] = 1
                temps[slot] = req.temperature
                top_ps[slot] = req.top_p
            self._mask_temps = (mask, temps, top_ps)
            self._temps_drawn = bool((temps > 0).any())
            self._mask_dirty = False
        return (*self._mask_temps, self._pages.rows(window))

    def _drain_verify(self, snapshot, out_dev, ne_lp, drawn):
        """Emit a speculative verify step's 1..K+1 tokens per slot.
        Host emission may stop early (EOS / budget) — those requests
        release immediately, so the device-side length overshoot is
        moot."""
        ne_dev, lp_dev = ne_lp
        try:
            with self._spans.span("engine.drain_wait"):
                t0 = time.perf_counter_ns()
                out = np.asarray(out_dev)
                n_emit = np.asarray(ne_dev)
                lps = np.asarray(lp_dev) if lp_dev is not None else None
                self._fetched(t0)
        except BaseException as e:  # noqa: BLE001
            for slot, req in snapshot:
                if req.slot == slot:
                    self._put(req, ("error", e))
                    self._release(req)
            return
        self.stats["decode_steps"] += 1
        self.stats["decode_steps_drawn"] += drawn
        self.stats["decode_slot_steps"] += self.cfg.max_slots
        for slot, req in snapshot:
            n = int(n_emit[slot])
            if req.slot != slot:
                self.stats["decode_tokens_discarded"] += n
                continue  # released/reused slot
            if req.generated >= req.max_new_tokens:
                self.stats["decode_tokens_discarded"] += n
                self._release(req)
                continue
            emitted = 0
            for j in range(n):
                if req.generated >= req.max_new_tokens:
                    break
                self._emit(req, int(out[slot, j]),
                           float(lps[slot, j]) if lps is not None
                           else None)
                emitted += 1
            self.stats["decode_tokens_emitted"] += emitted
            self.stats["decode_tokens_discarded"] += n - emitted
            self.stats["spec_accepted"] = (
                self.stats.get("spec_accepted", 0) + max(0, emitted - 1))
            if req.slot == slot:
                # resync the window mirror to the true length (the
                # dispatch bumped it by the K+1 upper bound)
                self._pages.set_length(slot,
                                       req.prompt.size + req.generated)
            full = (req.prompt.size + req.generated
                    >= self.cfg.max_seq_len)
            if req.generated >= req.max_new_tokens or full:
                self._release(req)

    def _fetched(self, t0: int) -> None:
        """A drained program's results are on the host; the fetch began
        at `t0`. What it waited is the device's side of this iteration."""
        now = time.perf_counter_ns()
        self._waited_ns += now - t0
        self._depth.fetched(now - t0, now)

    def _drain_one(self, inflight):
        """Fetch the oldest in-flight result and emit its tokens.
        Termination/EOS checks happen here, as many steps behind
        dispatch as are in flight (_InflightDepth); lagged tokens for
        finished/reused slots are discarded
        by the (req.slot == slot, generated < budget) guards; each is
        counted in stats["decode_tokens_discarded"]. Runs inside the
        loop's `engine.emit` span: only the fetch that blocks on the
        device is `engine.drain_wait`. An entry's last field says
        whether its decode or verify program was handed a temperature
        above 0 (stats["decode_steps_drawn"])."""
        kind, payload, arr, lp_arr, dispatched_ns, drawn = \
            inflight.popleft()
        if kind == "verify":
            self._drain_verify(payload, arr, lp_arr, drawn)
            return
        spans, st = self._spans, self.stats
        try:
            with spans.span("engine.drain_wait"):
                t0 = time.perf_counter_ns()
                host = np.asarray(arr)
                lps = np.asarray(lp_arr) if lp_arr is not None else None
                self._fetched(t0)
        except BaseException as e:  # noqa: BLE001  device-side failure
            targets = (list(payload) if kind != "decode"
                       else [r for _, r in payload])
            for req in targets:
                if req.slot >= 0:
                    self._put(req, ("error", e))
                    self._release(req)
            return
        if self._counted and kind != "prefill_chunk":
            # the step programs' tokens come with the model's
            # counters behind them (one vector a call, summed over its
            # layers; a block of decode steps brings one a step)
            n = len(self._step_stats)
            tail = host[..., -n:].reshape(-1, n).sum(0)
            for name, v in zip(self._step_stats, tail):
                st[name] += int(v)
            host = host[..., :-n]
        if kind != "decode":
            reqs = payload
            firsts = host.reshape(-1)
            flat_lps = lps.reshape(-1) if lps is not None else None
            for i, req in enumerate(reqs):
                if req.slot < 0:
                    continue
                if req.aborted and req.generated == 0:
                    # aborted while the prefill was in flight: discard
                    # its first token and release without emitting
                    self._release(req)
                    continue
                self._emit(req, int(firsts[i]),
                           float(flat_lps[i]) if flat_lps is not None
                           else None)
                spans.add("request.inflight_prefill",
                          time.perf_counter_ns() - dispatched_ns)
                if (req.generated >= req.max_new_tokens
                        or req.prompt.size + req.generated
                        >= self.cfg.max_seq_len):
                    self._release(req)
            return
        rows = host if host.ndim == 2 else host[None, :]  # (K, S)
        lp_rows = None
        if lps is not None:
            lp_rows = lps if lps.ndim == 2 else lps[None, :]
        spans.add("request.inflight_decode",
                  time.perf_counter_ns() - dispatched_ns)
        st["decode_steps"] += rows.shape[0]
        st["decode_steps_drawn"] += rows.shape[0] * drawn
        st["decode_slot_steps"] += rows.shape[0] * self.cfg.max_slots
        for ri, row in enumerate(rows):
            for slot, req in payload:
                if req.slot != slot:
                    # released/reused slot: lagged, discard
                    st["decode_tokens_discarded"] += 1
                    continue
                if req.generated >= req.max_new_tokens:
                    # budget shrank out-of-band (abort()): no further
                    # token will cross the threshold inside _emit, so
                    # release here or the slot decodes forever
                    st["decode_tokens_discarded"] += 1
                    self._release(req)
                    continue
                self._emit(req, int(row[slot]),
                           float(lp_rows[ri][slot])
                           if lp_rows is not None else None)
                st["decode_tokens_emitted"] += 1
                full = (req.prompt.size + req.generated
                        >= self.cfg.max_seq_len)
                if req.generated >= req.max_new_tokens or full:
                    self._release(req)

    def _engine_loop(self):
        inflight = collections.deque()
        while not self._shutdown.is_set():
            try:
                with self._spans.span("engine.loop"):
                    self._loop_once(inflight)
            except BaseException as e:  # noqa: BLE001  loop must survive
                import traceback
                traceback.print_exc()
                self._in_dispatch = False
                for req in list(self._active.values()):
                    self._put(req, ("error", e))
                    self._release(req)
                inflight.clear()
                self._hand_over()

    def _loop_once(self, inflight) -> None:
        """One iteration of the engine loop, each phase in its span (the
        caller holds `engine.loop`; self times, so the phases sum to it)."""
        span = self._spans.span
        began, self._waited_ns = time.perf_counter_ns(), 0
        with span("engine.control"):
            while True:
                # control commands (prefix registration) run HERE so
                # pool mutations never race a donated buffer
                try:
                    fn, done = self._control_q.get_nowait()
                except queue_mod.Empty:
                    break
                # commands are engine work too: a first-use prefix
                # prefill can jit-compile for >watchdog_s, so they
                # get the same compile grace as dispatches (a truly
                # stuck command still wedges after grace x budget —
                # the chaos stall exercises exactly that)
                self._in_dispatch = True
                try:
                    fn()
                    done.set_result(None)
                except BaseException as e:  # noqa: BLE001
                    done.set_exception(e)
                finally:
                    self._in_dispatch = False
        self._in_dispatch = True   # watchdog: compile grace on
        with span("engine.admit"):
            self._admit_all(inflight)
        if self._prefilling:
            with span("engine.chunk_dispatch"):
                self._dispatch_chunk(inflight)
        need_sync = ready = False
        if self._active:
            with span("engine.decode_prep"):
                allow = self._guided_decode_allow()
                pen = self._pen_args()
                # penalties pipeline fine but the verify kernels don't
                # thread them: speculation (and its sync stepping)
                # disables entirely while any penalized request is active
                spec_sync = pen is None and self._spec_sync_active()
                need_sync = allow is not None or spec_sync
                if not need_sync or not inflight:
                    # guided traffic with results in flight waits for
                    # the drain below: the next mask depends on tokens
                    # the host hasn't seen yet
                    props = (self._spec_plan()
                             if spec_sync and allow is None else None)
                    if props is not None:
                        self._pages.advance(
                            self._active, self.cfg.ngram_speculation + 1)
                    window = self._pages.decode_window(self._decode_new)
                    self._count_decode(window)
                    snapshot = list(self._active.items())
                    ready = True
        if ready:
            self._decode_dispatches += 1
            with span("engine.decode_dispatch",
                      step=self._decode_dispatches, active=len(snapshot),
                      window_pages=window):
                self._dispatch_decode(inflight, snapshot, props, allow,
                                      pen, window)
        with span("engine.bookkeep"):
            m = self._m = _engine_metrics()
            m["active"].set(float(len(self._active)),
                            tags=self._mtags)
            m["waiting"].set(float(self._waiting.qsize()),
                             tags=self._mtags)
            m["occupancy"].set(
                len(self._active) / max(1, self.cfg.max_slots),
                tags=self._mtags)
            m["kv_util"].set(self._pages.utilization(), tags=self._mtags)
        if not inflight:
            self._in_dispatch = False
            self._hand_over()   # what admission errored, shed or ended
            self._depth.idle()
            with span("engine.idle_sleep"):
                time.sleep(0.002)
            return
        # stay as far ahead as keeps the device fed (_InflightDepth),
        # at most `pipeline_depth` programs
        target = self._depth.target(self.cfg.pipeline_depth,
                                    bool(self._active), need_sync)
        if ready:
            self.stats["decode_inflight_target_sum"] += target
            self.stats["decode_inflight_target_n"] += 1
        emitted = self.stats["tokens_generated"]
        with span("engine.emit"):
            try:
                while len(inflight) > target:
                    self._drain_one(inflight)
            finally:
                # one registry update per drain, not one per token
                emitted = self.stats["tokens_generated"] - emitted
                if emitted:
                    m["tokens"].inc(float(emitted), tags=self._mtags)
        # one wake of the consumers' loop for everything this iteration
        # put, whichever phase put it
        self._hand_over()
        self._in_dispatch = False
        self._depth.iterated(
            time.perf_counter_ns() - began - self._waited_ns)

    def _dispatch_decode(self, inflight, snapshot, props, allow, pen,
                         window: int) -> None:
        """Enqueue one decode (or speculative verify) program over all
        slots, start its fetch and append it to `inflight`."""
        ctl = self._decode_ctl(window)
        if props is not None:
            out, logps, (n_emit,) = self._step(
                self._verify_paged_jit, ctl, props, window_pages=window)
            self._start_fetch(out)
            self._start_fetch(n_emit)
            if self.cfg.logprobs:
                self._start_fetch(logps)
            self.stats["spec_steps"] = \
                self.stats.get("spec_steps", 0) + 1
            inflight.append(
                ("verify", snapshot, out,
                 (n_emit, logps if self.cfg.logprobs else None),
                 time.perf_counter_ns(), self._temps_drawn))
            return
        if self._decode_block_paged_jit is not None \
                and allow is None and pen is None:
            toks, logps, _ = self._step(
                self._decode_block_paged_jit, ctl, window_pages=window)
            block = self.cfg.decode_block
        else:
            akw = {} if allow is None else {"allow": allow}
            if pen is not None:
                akw["pen"] = pen
            toks, logps, rest = self._step(
                self._decode_paged_jit, ctl, window_pages=window, **akw)
            if pen is not None:
                self._pen_counts, = rest
            block = 1
        self._pages.advance(self._active, block)
        self._start_fetch(toks)
        if self.cfg.logprobs:
            self._start_fetch(logps)
        inflight.append(("decode", snapshot, toks,
                         logps if self.cfg.logprobs
                         else None, time.perf_counter_ns(),
                         self._temps_drawn))
