"""SpmdTrainer: declarative model+mesh trainer (the TorchTrainer analogue
for the common LLM case).

Reference parity: TorchTrainer + its prepare_model/prepare_data_loader
utilities (python/ray/train/torch/). Instead of wrapping user torch code,
the common case is declared: model (name or module), mesh spec, optimizer,
data iterator — the trainer owns the jitted step, logging, checkpointing,
and restore.

ElasticSpmdTrainer is the multi-host, fault-tolerant variant: it drives
a supervised MultiHostSpmd gang and runs the recover cycle of the other
FT planes (PRs 4/5/6) for training — on a rank death the gang reforms
(replaced or resharded, train/elastic.py), every rank restores the last
COMMITTED checkpoint through `restore_pytree(shardings=...)` onto the
new (possibly smaller) mesh, and the loop continues from `state.step`.
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence

import numpy as np

from ..observability.profiler import SpanTable
from ..parallel.mesh import MeshSpec, build_mesh
from ..util import knobs
from .checkpoint import CheckpointManager, restore_pytree
from .config import RunConfig
from .optim import make_optimizer, warmup_cosine
from .spmd import make_train_step
from .result import Result


@dataclasses.dataclass
class SpmdTrainerConfig:
    model: Any                          # nn.Module or registry name
    mesh: MeshSpec = dataclasses.field(default_factory=MeshSpec)
    optimizer: str = "adamw"
    learning_rate: float = 3e-4
    warmup_steps: int = 100
    total_steps: int = 1000
    log_every: int = 10
    checkpoint_every: int = 0
    grad_clip: float = 1.0
    seed: int = 0


class SpmdTrainer:
    def __init__(self, config: SpmdTrainerConfig,
                 data_iter_fn: Callable[[], Iterator[Dict[str, Any]]],
                 run_config: Optional[RunConfig] = None,
                 report_fn: Optional[Callable[[Dict[str, Any]], None]] = None):
        self.cfg = config
        self.data_iter_fn = data_iter_fn
        self.run_config = run_config or RunConfig(name="spmd_trainer")
        self.report_fn = report_fn
        self.state = self.step = None     # set by fit()
        self.spans: Optional[SpanTable] = None   # fit()'s phase times

    def fit(self, resume_from: Optional[str] = None) -> Result:
        import jax
        import jax.numpy as jnp

        cfg = self.cfg
        model = cfg.model
        if isinstance(model, str):
            from ..models import get_model
            model = get_model(model)
        from ..util.jaxenv import enable_compile_cache
        enable_compile_cache()
        devices = jax.devices()
        spec = cfg.mesh
        if spec == MeshSpec():
            # only the untouched default adapts to the devices present;
            # a mesh the user wrote must fit them (build_mesh raises)
            spec = MeshSpec(dp=len(devices))
        mesh = build_mesh(spec, devices=devices)

        schedule = warmup_cosine(cfg.learning_rate, cfg.warmup_steps,
                                 cfg.total_steps)
        tx = make_optimizer(cfg.optimizer, schedule=schedule,
                            grad_clip=cfg.grad_clip)

        data = self.data_iter_fn()
        first = next(data)
        batch = {k: jnp.asarray(v) for k, v in first.items()}
        init_fn = make_train_step(model, tx, mesh)
        state, step_fn = init_fn(jax.random.PRNGKey(cfg.seed), batch)

        manager = CheckpointManager(
            self.run_config.run_dir() + "/checkpoints",
            self.run_config.checkpoint_config.num_to_keep)
        start_step = 0
        if resume_from:
            state = restore_pytree(resume_from, target=state,
                                   shardings=step_fn.state_shardings)
            start_step = int(state.step)
            bnp, data = _fast_forward_batches(
                data, {k: np.asarray(v) for k, v in first.items()},
                start_step, self.data_iter_fn)
            batch = {k: jnp.asarray(v) for k, v in bnp.items()}

        # the loop's phases on the profiler's clock (a capture's idle gaps
        # take these names) and as self times in `self.spans`
        spans = self.spans = SpanTable()
        history = []
        tokens_acc, t_last = 0, time.time()
        for i in range(start_step, cfg.total_steps):
            with spans.step("train.step", i):
                state, metrics = step_fn(state, batch)
            tokens_acc += int(np.prod(batch[next(iter(batch))].shape[:2]))
            if (i + 1) % cfg.log_every == 0 or i + 1 == cfg.total_steps:
                with spans.span("train.report"):
                    now = time.time()
                    m = {k: float(v) for k, v in metrics.items()}
                    m.update(step=i + 1, tokens_per_s=tokens_acc
                             / max(now - t_last, 1e-9))
                    tokens_acc, t_last = 0, now
                    history.append(m)
                    if self.report_fn:
                        self.report_fn(m)
            if cfg.checkpoint_every and (i + 1) % cfg.checkpoint_every == 0:
                manager.save(jax.device_get(state), i + 1)
            # only draw ahead if another step will run: finite streams
            # (e.g. a data-service iterator on its last epoch) end
            # exactly at total_steps and must not be over-drawn
            if i + 1 < cfg.total_steps:
                with spans.span("train.next_batch"):
                    try:
                        nxt = next(data)
                        batch = {k: jnp.asarray(v) for k, v in nxt.items()}
                    except StopIteration:
                        data = self.data_iter_fn()
                        batch = {k: jnp.asarray(v)
                                 for k, v in next(data).items()}

        final_ckpt = None
        if cfg.checkpoint_every:
            final_ckpt = manager.save(jax.device_get(state), cfg.total_steps)
        # the sharded TrainState and the SpmdStep that produced it, for
        # callers that go on (eval, export, inspecting the layout)
        self.state, self.step = state, step_fn
        return Result(metrics=history[-1] if history else {},
                      checkpoint=final_ckpt or manager.latest(),
                      metrics_history=history,
                      path=self.run_config.run_dir())


# ---------------------------------------------------------------------------
# Elastic multi-host training
# ---------------------------------------------------------------------------

def _fast_forward_batches(data: Iterator, first_np: Dict[str, Any],
                          start_step: int, data_iter_fn: Callable):
    """Resume semantics shared by SpmdTrainer and the elastic rank fn:
    step i always trains on batch i, so a resumed run SKIPS the
    `start_step` batches the crashed run already consumed instead of
    silently re-training on them. An iterator exposing
    `fast_forward(n)` (stateful loaders: seekable shards, the
    data-service snapshot hook) is asked to seek — absolute: the next
    batch drawn is batch index n. Otherwise batches are drawn and
    discarded, restarting the iterator on exhaustion exactly like the
    training loop's wrap-around (short repeating iterators keep their
    pre-resume alignment only per epoch). `first_np` is batch 0, which
    the caller already drew for init. Returns (batch_for_start_step,
    iterator) — the iterator may have been replaced by a restart."""
    if start_step <= 0:
        return first_np, data
    ff = getattr(data, "fast_forward", None)
    if callable(ff):
        ff(start_step)
        nxt = next(data)
        return {k: np.asarray(v) for k, v in nxt.items()}, data
    out = first_np
    for _ in range(start_step):
        try:
            nxt = next(data)
        except StopIteration:
            data = data_iter_fn()
            nxt = next(data)
        out = {k: np.asarray(v) for k, v in nxt.items()}
    return out, data


def _host_value(leaf):
    """Host copy of one (possibly multi-process) state leaf. Fully
    addressable arrays device_get; fully REPLICATED multi-process
    arrays read their local shard (it holds the whole value). Returns
    None for a leaf that is neither — cross-host sharded state needs a
    coordinated orbax multihost save, which the per-rank checkpoint
    path does not attempt."""
    import jax
    if not isinstance(leaf, jax.Array):
        return np.asarray(leaf)
    if leaf.is_fully_addressable:
        return np.asarray(jax.device_get(leaf))
    if leaf.sharding.is_fully_replicated:
        return np.asarray(leaf.addressable_data(0))
    return None


def _host_state(state):
    """(host_pytree, ok): ok is False when any leaf is cross-host
    sharded (dp/replicated state — the elastic default — is always
    ok)."""
    import jax
    ok = True

    def conv(x):
        nonlocal ok
        v = _host_value(x)
        if v is None:
            ok = False
        return v

    host = jax.tree_util.tree_map(conv, state)
    return host, ok


def _global_batch(batch_np: Dict[str, np.ndarray], bshard,
                  rank: int, world: int):
    """Turn the (identical-on-every-rank) host batch into global device
    arrays sharded per `bshard`: each process uploads only its share of
    the batch dimension (`jax.make_array_from_process_local_data`), so
    per-step input bandwidth scales with hosts. Single-process worlds
    take the plain asarray path."""
    import jax
    import jax.numpy as jnp
    if world <= 1:
        return {k: jnp.asarray(v) for k, v in batch_np.items()}
    out = {}
    for k, v in batch_np.items():
        v = np.asarray(v)
        n = v.shape[0]
        if n % world:
            raise ValueError(
                f"global batch dim {n} of '{k}' must divide the world "
                f"size {world} for per-process sharding")
        share = n // world
        local = v[rank * share:(rank + 1) * share]
        out[k] = jax.make_array_from_process_local_data(bshard[k], local)
    return out


def _sync_world(tag: str, generation: int,
                timeout_ms: int = 180_000) -> None:
    """Rendezvous every rank at the jax coordination service BEFORE the
    first collective computation of a generation. Gloo context init has
    a hard ~30 s store-rendezvous timeout, and ranks reach the first
    collective with wildly different skew (a cold worker pays the full
    flax/optax import + compile while a warm one forked them for free)
    — the coordination-service barrier is plain gRPC with a long
    timeout, so it absorbs the skew and the first collective starts
    aligned on all ranks."""
    try:
        from jax._src import distributed
        client = distributed.global_state.client
        if client is not None:
            client.wait_at_barrier(f"rtpu_{tag}_g{generation}",
                                   timeout_ms)
    except Exception:  # noqa: BLE001 — single-process / API drift: skip
        pass


def _elastic_rank_fn(rank: int, world: int, payload: Dict[str, Any]):
    """One rank's training loop for ElasticSpmdTrainer (runs inside an
    _SpmdHost actor after the jax.distributed join). Restores the last
    committed checkpoint onto THIS world's mesh — which may be smaller
    than the one that wrote it — trains to total_steps, and (rank 0)
    commits checkpoints every checkpoint_every steps."""
    import jax
    from ..util import events
    from ..util import metrics_catalog as mcat
    from .elastic import reshard_mesh_spec

    cfg: Dict[str, Any] = payload
    generation = cfg["generation"]

    trace_path = knobs.get_raw("RAY_TPU_ELASTIC_TRACE")

    def _trace(msg: str) -> None:
        if trace_path:
            with open(f"{trace_path}.r{rank}", "a") as f:
                f.write(f"{time.time():.3f} g{generation} {msg}\n")

    _trace(f"enter world={world} pid={os.getpid()}")
    model = cfg["model"]
    if isinstance(model, str):
        from ..models import get_model
        model = get_model(model)
    devices = jax.devices()
    spec = reshard_mesh_spec(cfg["mesh"], len(devices))
    mesh = build_mesh(spec, devices=devices)

    schedule = warmup_cosine(cfg["learning_rate"], cfg["warmup_steps"],
                             cfg["total_steps"])
    tx = make_optimizer(cfg["optimizer"], schedule=schedule,
                        grad_clip=cfg["grad_clip"])

    data = cfg["data_iter_fn"]()
    first = {k: np.asarray(v) for k, v in next(data).items()}
    init_fn = make_train_step(model, tx, mesh)
    _trace(f"devices={len(devices)} local={jax.local_device_count()} "
           f"sync start")
    if world > 1:
        _sync_world("elastic_warm", generation)
    _trace("init start")
    state, step_fn = init_fn(jax.random.PRNGKey(cfg["seed"]), first)
    _trace("init done")

    manager = CheckpointManager(cfg["ckpt_root"], cfg["num_to_keep"])
    start_step = 0
    latest = manager.latest()
    if latest is not None:
        t0 = time.monotonic()
        state = restore_pytree(latest.path, target=state,
                               shardings=step_fn.state_shardings)
        start_step = int(_host_value(state.step))
        took = time.monotonic() - t0
        if rank == 0:
            events.emit_safe(
                "train.restore",
                f"restored committed checkpoint step {start_step} onto "
                f"a {len(devices)}-device mesh (generation "
                f"{generation}) in {took:.2f}s",
                step=str(start_step), generation=str(generation),
                world=str(world), seconds=f"{took:.3f}")
            try:
                mcat.get("ray_tpu_train_restore_seconds").observe(took)
            except Exception:  # noqa: BLE001 — telemetry never fails work
                pass

    history: List[Dict[str, Any]] = []
    ckpt_every = cfg["checkpoint_every"]
    sharded_save_warned = False
    tokens_acc, t_last = 0, time.time()
    # resume must not re-train on consumed data; skipping is pointless
    # when the restore already reached total_steps (loop won't run)
    batch_np = first
    if start_step < cfg["total_steps"]:
        batch_np, data = _fast_forward_batches(
            data, first, start_step, cfg["data_iter_fn"])
    for i in range(start_step, cfg["total_steps"]):
        _trace(f"step {i}")
        batch = _global_batch(batch_np, step_fn.batch_shardings,
                              rank, world)
        state, metrics = step_fn(state, batch)
        key0 = next(iter(batch_np))
        tokens_acc += int(np.prod(batch_np[key0].shape[:2]))
        if (i + 1) % cfg["log_every"] == 0 or i + 1 == cfg["total_steps"]:
            now = time.time()
            m = {k: float(_host_value(v)) for k, v in metrics.items()}
            m.update(step=i + 1, generation=generation, world=world,
                     tokens_per_s=tokens_acc / max(now - t_last, 1e-9))
            tokens_acc, t_last = 0, now
            history.append(m)
        if ckpt_every and (i + 1) % ckpt_every == 0 and rank == 0:
            host, ok = _host_state(state)
            if ok:
                manager.save(host, i + 1,
                             metadata={"generation": generation,
                                       "world": world})
            elif not sharded_save_warned:
                sharded_save_warned = True
                import warnings
                warnings.warn(
                    "elastic checkpointing skipped: state has "
                    "cross-host sharded leaves (fsdp/tp across "
                    "processes); per-rank commit needs replicated or "
                    "locally-addressable state", stacklevel=1)
        if i + 1 < cfg["total_steps"]:
            try:
                batch_np = {k: np.asarray(v)
                            for k, v in next(data).items()}
            except StopIteration:
                data = cfg["data_iter_fn"]()
                batch_np = {k: np.asarray(v)
                            for k, v in next(data).items()}
    final = None
    if ckpt_every and rank == 0:
        done = manager.latest()
        if done is not None \
                and done.metadata().get("step") == cfg["total_steps"]:
            # restored AT the final step (death raced the last commit):
            # the checkpoint is already committed — re-saving the same
            # path would only re-open the overwrite window
            final = done.path
        else:
            host, ok = _host_state(state)
            if ok:
                final = manager.save(
                    host, cfg["total_steps"],
                    metadata={"generation": generation,
                              "world": world}).path
    # an already-complete restore (death raced the final commit) yields
    # an empty history; the metrics still name the terminal step
    last = history[-1] if history else {
        "step": start_step, "world": world, "generation": generation}
    return {"rank": rank, "world": world, "generation": generation,
            "start_step": start_step, "history": history,
            "metrics": last, "checkpoint": final}


class ElasticSpmdTrainer:
    """Gang-supervised multi-host SpmdTrainer with checkpoint-resume.

    fit() runs the recover cycle end-to-end: train on a supervised
    MultiHostSpmd gang; on a rank death (preempted host, killed worker)
    the supervisor flags it in ~RAY_TPU_GANG_PROBE_S, the gang reforms
    — replaced at full size when the cluster has capacity, otherwise
    RESHARDED onto the surviving world — and every new rank restores
    the last COMMITTED checkpoint onto the new mesh and continues from
    `state.step`. Emits the `train.gang.rank_death` -> `train.gang.
    reform` (/`train.gang.reshard`) -> `train.restore` event chain and
    the ray_tpu_train_gang_reforms_total / _restore_seconds metrics.

    `data_iter_fn` must be deterministic per process (every rank draws
    the same global batch stream and uploads only its shard); resume
    skips batches consumed before the last committed checkpoint.
    """

    def __init__(self, config: SpmdTrainerConfig,
                 data_iter_fn: Callable[[], Iterator[Dict[str, Any]]],
                 *, num_hosts: int,
                 resources_per_host: Optional[Dict[str, float]] = None,
                 env_per_host: Optional[Dict[str, str]] = None,
                 spread: bool = False,
                 run_config: Optional[RunConfig] = None,
                 max_failures: Optional[int] = None,
                 collective_groups: Sequence[str] = ()):
        self.cfg = config
        self.data_iter_fn = data_iter_fn
        self.num_hosts = num_hosts
        self.resources_per_host = resources_per_host
        self.env_per_host = env_per_host
        self.spread = spread
        self.run_config = run_config or RunConfig(name="elastic_spmd")
        if max_failures is None:
            mf = self.run_config.failure_config.max_failures
            max_failures = mf if mf > 0 \
                else knobs.get_int("RAY_TPU_TRAIN_MAX_FAILURES")
        self.max_failures = max_failures
        self.collective_groups = tuple(collective_groups)

    def _payload(self, gang) -> Dict[str, Any]:
        cfg = self.cfg
        ckpt_root = os.path.join(self.run_config.run_dir(), "checkpoints")
        return {
            "model": cfg.model, "mesh": cfg.mesh,
            "optimizer": cfg.optimizer,
            "learning_rate": cfg.learning_rate,
            "warmup_steps": cfg.warmup_steps,
            "total_steps": cfg.total_steps, "log_every": cfg.log_every,
            "checkpoint_every": cfg.checkpoint_every,
            "grad_clip": cfg.grad_clip, "seed": cfg.seed,
            "ckpt_root": ckpt_root,
            "num_to_keep": self.run_config.checkpoint_config.num_to_keep,
            "generation": gang.generation,
            "data_iter_fn": self.data_iter_fn,
        }

    def _await_round(self, gang, refs) -> bool:
        """True when every rank finished; False the moment the
        supervisor flags a death (the refs then belong to a doomed
        world and are abandoned)."""
        import ray_tpu
        pending = list(refs)
        while True:
            if gang.failure is not None:
                return False
            _done, pending = ray_tpu.wait(
                pending, num_returns=len(pending), timeout=0.5)
            if not pending:
                # all refs settled (a just-dead rank's ref settles as an
                # error); the get() in fit() decides success vs reform
                return True

    def fit(self) -> Result:
        import ray_tpu
        from ..exceptions import (ActorDiedError, TaskError,
                                  error_cause_is)
        from .multihost import MultiHostSpmd

        cfg = self.cfg
        if not cfg.checkpoint_every:
            raise ValueError(
                "ElasticSpmdTrainer needs checkpoint_every > 0: without "
                "committed checkpoints a reform would restart from "
                "step 0")
        run_dir = self.run_config.run_dir()
        gang = MultiHostSpmd(
            self.num_hosts, resources_per_host=self.resources_per_host,
            env_per_host=self.env_per_host, spread=self.spread,
            supervised=True, collective_groups=self.collective_groups)
        failures = 0
        try:
            while True:
                refs = gang.run_async(_elastic_rank_fn,
                                      self._payload(gang))
                if self._await_round(gang, refs):
                    try:
                        results = ray_tpu.get(refs, timeout=120)
                        break
                    except (ActorDiedError, TaskError) as e:
                        # A survivor's collateral failure (its collective
                        # died under it) can settle BEFORE the supervisor
                        # flags the rank death — give the 0.25s watch a
                        # grace before calling it a training bug.
                        if isinstance(e, TaskError) \
                                and not error_cause_is(
                                    e, "CollectiveRankDiedError",
                                    "CollectiveStaleGenerationError") \
                                and gang.wait_failure(timeout=3.0) is None:
                            raise   # a training error, not elasticity
                        pass        # gang failure: reform below
                failures += 1
                if failures > self.max_failures:
                    death = gang.failure
                    raise RuntimeError(
                        f"elastic training exceeded max_failures="
                        f"{self.max_failures}; last death: "
                        f"{death and death.cause}")
                gang.reform()
        finally:
            gang.shutdown()
        r0 = results[0]
        manager = CheckpointManager(
            os.path.join(run_dir, "checkpoints"),
            self.run_config.checkpoint_config.num_to_keep)
        from .checkpoint import Checkpoint
        ckpt = (Checkpoint(r0["checkpoint"]) if r0.get("checkpoint")
                else manager.latest())
        return Result(metrics=r0["metrics"], checkpoint=ckpt,
                      metrics_history=r0["history"], path=run_dir,
                      config={"num_hosts": self.num_hosts,
                              "final_world": r0["world"],
                              "generations": gang.generation,
                              "failures": failures})
