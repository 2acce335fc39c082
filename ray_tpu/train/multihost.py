"""Multi-host SPMD: one JAX process per host, one global device mesh.

Reference counterpart: ray.train.torch's NCCL world
(python/ray/train/torch/config.py:_setup_torch_process_group — each
worker joins a process group keyed by master address / world size /
rank). TPU-first inversion: the world is `jax.distributed` — every host
process sees its local chips, `jax.devices()` is the GLOBAL device
list, and jitted programs span the whole mesh with XLA emitting the
cross-host collectives (ICI within a slice, DCN across slices). No
NCCL, no per-step communication code.

The runtime provides the process fabric: one `_SpmdHost` actor per host
(gang-placed via STRICT_SPREAD when `spread=True`); rank 0 picks the
coordinator endpoint on its own host, every rank joins the world, then
the gang runs the user's SPMD function. On this image the same
machinery is exercised with multiple CPU processes (Gloo collectives) —
the TPU pod deployment only changes the per-host device count.

Elastic mode (`supervised=True`, train/elastic.py): a GangSupervisor
watches every rank's GCS actor state; when a rank dies (preempted host,
OOM-killed worker), `reform()` tears down the doomed jax.distributed
world — killing the remaining rank processes is the clean teardown:
survivors are parked inside collectives that can never complete — and
re-gangs under a bumped GENERATION: at full size when the cluster has
replacement capacity, otherwise resharded onto the surviving world.
Stale ranks of the old generation are fenced out of collectives like
PR-4 node incarnations.
"""
from __future__ import annotations

import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence


class _SpmdHost:
    """Actor hosting one rank of the jax.distributed world."""

    def __init__(self, rank: int, world: int, generation: int = 0):
        self.rank = rank
        self.world = world
        self.generation = generation

    def ping(self) -> Dict[str, int]:
        return {"rank": self.rank, "world": self.world,
                "generation": self.generation, "pid": os.getpid()}

    def pick_coordinator(self) -> str:
        """Rank 0 chooses the coordinator endpoint ON ITS OWN HOST —
        the jax.distributed coordinator service runs inside rank 0's
        process, which with gang placement is NOT the driver's host."""
        from ..util.netutil import free_port, routable_ip
        return f"{routable_ip()}:{free_port()}"

    def join(self, coordinator: str) -> Dict[str, int]:
        """Blocks until every rank has joined the world. Called on all
        ranks concurrently (each actor has its own process)."""
        import jax
        if (os.environ.get("JAX_PLATFORMS") or "").startswith("cpu"):
            # CPU cross-process worlds need an explicit collectives
            # implementation or every multi-process computation fails
            # with "Multiprocess computations aren't implemented on the
            # CPU backend"; must be set BEFORE the backend is created
            # (the env var alone is not read by this jax version).
            impl = os.environ.get(
                "JAX_CPU_COLLECTIVES_IMPLEMENTATION", "gloo")
            jax.config.update("jax_cpu_collectives_implementation", impl)
        jax.distributed.initialize(coordinator, num_processes=self.world,
                                   process_id=self.rank)
        return {"rank": self.rank, "world": self.world,
                "local_devices": jax.local_device_count(),
                "global_devices": jax.device_count()}

    def run(self, fn: Callable, *args, **kwargs) -> Any:
        return fn(self.rank, self.world, *args, **kwargs)


class MultiHostSpmd:
    """A gang of per-host JAX processes forming one distributed world.

    num_hosts: requested processes (= hosts on a pod; may share a host
        in tests). `world_size` is the CURRENT gang size — it equals
        num_hosts until a supervised gang reforms resharded.
    resources_per_host: what each rank's actor reserves (e.g.
        {"TPU": 4} so each rank owns its host's chips).
    env_per_host: env applied before the rank's first jax import —
        platform selection, XLA flags (CPU tests pass JAX_PLATFORMS=cpu
        + --xla_force_host_platform_device_count=N).
    spread: gang the ranks one-per-node via a STRICT_SPREAD placement
        group (requires that many alive nodes).
    supervised: start a GangSupervisor (train/elastic.py) that detects
        a dead rank within ~RAY_TPU_GANG_PROBE_S and arms `reform()`.
    collective_groups: names of util.collective groups whose rendezvous
        actors should learn about rank deaths (parked rounds then fail
        with CollectiveRankDiedError) and generation bumps.
    """

    def __init__(self, num_hosts: int, *,
                 resources_per_host: Optional[Dict[str, float]] = None,
                 env_per_host: Optional[Dict[str, str]] = None,
                 spread: bool = False,
                 supervised: bool = False,
                 collective_groups: Sequence[str] = (),
                 pg_timeout: float = 60.0,
                 _host_cls: Optional[type] = None):
        import ray_tpu
        self._ray = ray_tpu
        self.num_hosts = num_hosts
        self.world_size = 0
        self.generation = 0
        self._resources_per_host = dict(resources_per_host or {})
        self._env_per_host = dict(env_per_host or {})
        self._spread = spread
        self._supervised = supervised
        self._collective_groups = tuple(collective_groups)
        self._pg_timeout = pg_timeout
        self._host_cls = _host_cls or _SpmdHost
        self._pg = None
        self._supervisor = None
        self.hosts: List[Any] = []
        self._gang_up(num_hosts)
        if supervised:
            self._start_supervisor()

    # ------------------------------------------------------------------
    # construction / teardown
    # ------------------------------------------------------------------
    def _actor_cls(self):
        from ..api import remote
        opts: Dict[str, Any] = {}
        res = dict(self._resources_per_host)
        opts["num_cpus"] = res.pop("CPU", 1)
        tpus = res.pop("TPU", 0)
        if tpus:
            opts["num_tpus"] = tpus
        if res:
            opts["resources"] = res
        if self._env_per_host:
            opts["runtime_env"] = {"env_vars": dict(self._env_per_host)}
        return remote(**opts)(self._host_cls)

    def _gang_up(self, world: int) -> None:
        """Spawn `world` rank actors, gang-place them, and join the
        jax.distributed world. Failure anywhere (placement timeout, a
        rank crashing in join) kills every already-spawned actor and
        removes the placement group — a failed gang must not leak its
        partially-built world."""
        actor_cls = self._actor_cls()
        pg = None
        hosts: List[Any] = []
        try:
            if self._spread:
                from ..util.placement_group import placement_group
                pg = placement_group(
                    [dict(self._resources_per_host or {"CPU": 1})] * world,
                    strategy="STRICT_SPREAD")
                if not pg.wait(self._pg_timeout):
                    raise RuntimeError(
                        f"could not gang {world} hosts (placement group "
                        "not ready)")
            for rank in range(world):
                a = actor_cls
                if pg is not None:
                    a = actor_cls.options(placement_group=pg,
                                          bundle_index=rank)
                hosts.append(a.remote(rank, world, self.generation))
            # Rank 0 picks the coordinator endpoint on its own host, then
            # every rank joins concurrently (the join barrier resolves once
            # all are in). Failures surface through these gets.
            coordinator = self._ray.get(
                hosts[0].pick_coordinator.remote(), timeout=120)
            descs = self._ray.get(
                [h.join.remote(coordinator) for h in hosts],
                timeout=180)
        except BaseException:
            self._teardown_actors(hosts, pg)
            raise
        self.hosts = hosts
        self._pg = pg
        self.coordinator = coordinator
        self.world_size = world
        self.world_devices = descs[0]["global_devices"]

    def _teardown_actors(self, hosts, pg) -> None:
        for h in hosts:
            try:
                self._ray.kill(h)
            except Exception:  # noqa: BLE001 — best-effort teardown
                pass
        if pg is not None:
            from ..util.placement_group import remove_placement_group
            try:
                remove_placement_group(pg)
            except Exception:  # noqa: BLE001
                pass

    def _start_supervisor(self) -> None:
        from .elastic import GangSupervisor
        members = {rank: h.actor_id for rank, h in enumerate(self.hosts)}
        self._supervisor = GangSupervisor(
            members, generation=self.generation,
            collective_groups=self._collective_groups)

    # ------------------------------------------------------------------
    # supervision surface
    # ------------------------------------------------------------------
    @property
    def failure(self):
        """First RankDeath seen by the supervisor (None while healthy)."""
        return self._supervisor.first_death if self._supervisor else None

    def wait_failure(self, timeout: Optional[float] = None):
        """Block until a rank dies (or timeout); returns the RankDeath."""
        if self._supervisor is None:
            raise RuntimeError("gang is not supervised "
                               "(pass supervised=True)")
        return self._supervisor.wait(timeout)

    # ------------------------------------------------------------------
    # reform
    # ------------------------------------------------------------------
    def _fits(self, world: int, need: Dict[str, float]) -> bool:
        avail = self._ray.available_resources()
        for r, v in need.items():
            if v and avail.get(r, 0.0) + 1e-9 < v * world:
                return False
        if self._spread:
            alive = sum(1 for n in self._ray.nodes() if n.get("alive"))
            if alive < world:
                return False
        return True

    def _feasible_world(self, target: int, replace_deadline: float,
                        deadline: float) -> int:
        """Largest world the cluster can hold: wait up to the replace
        window for FULL capacity (a replacement host may be seconds from
        freeing/rejoining), then settle for the largest feasible size,
        polling until the reform deadline before giving up."""
        need = dict(self._resources_per_host)
        need.setdefault("CPU", 1)
        while time.monotonic() < replace_deadline:
            if self._fits(target, need):
                return target
            time.sleep(0.1)
        while time.monotonic() < deadline:
            for k in range(target, 0, -1):
                if self._fits(k, need):
                    return k
            time.sleep(0.25)
        return 0

    def reform(self, *, timeout: Optional[float] = None,
               min_hosts: int = 1) -> Dict[str, Any]:
        """Tear down the current (doomed) world and re-gang.

        Killing every rank process IS the clean teardown of the
        jax.distributed world: surviving ranks are parked inside
        collectives that can never complete, and a fresh world needs
        fresh processes anyway (jax.distributed binds once per
        process). The gang comes back at full size when the cluster has
        capacity for `num_hosts` ranks within RAY_TPU_GANG_REPLACE_WAIT_S,
        otherwise RESHARDED onto the largest feasible world (>=
        min_hosts). Collective groups are advanced to the new
        generation first, so zombie ranks of the old world fence out
        instead of corrupting the new world's rounds.

        Returns {"world_size", "generation", "resharded", "deaths"}.
        Raises GangReformError when nothing >= min_hosts fits within
        RAY_TPU_GANG_REFORM_TIMEOUT_S (or `timeout`).
        """
        from ..exceptions import GangReformError
        from ..util import events
        from ..util.collective import advance_group_generation
        from .elastic import reform_timeout_s, replace_wait_s

        t0 = time.monotonic()
        budget = timeout if timeout is not None else reform_timeout_s()
        deadline = t0 + budget
        deaths = []
        if self._supervisor is not None:
            deaths = list(self._supervisor.deaths)
            self._supervisor.stop()
            self._supervisor = None
        old_world = self.world_size
        self._teardown_actors(self.hosts, self._pg)
        self.hosts = []
        self._pg = None
        self.generation += 1

        replace_deadline = min(deadline, t0 + replace_wait_s())
        world = self._feasible_world(self.num_hosts, replace_deadline,
                                     deadline)
        if world < max(min_hosts, 1):
            raise GangReformError(
                f"gang reform failed: no feasible world >= "
                f"{max(min_hosts, 1)} hosts within {budget:.0f}s "
                f"(requested {self.num_hosts}, last world {old_world})")
        resharded = world < self.num_hosts
        for g in self._collective_groups:
            advance_group_generation(g, self.generation, world)
        try:
            self._gang_up(world)
        except BaseException as e:
            raise GangReformError(
                f"gang reform failed re-ganging {world} hosts "
                f"(generation {self.generation}): {e!r}") from e
        if self._supervised:
            self._start_supervisor()
        took = time.monotonic() - t0
        kind = "resharded" if resharded else "replaced"
        events.emit_safe(
            "train.gang.reform",
            f"gang reformed ({kind}) {old_world} -> {world} ranks in "
            f"{took:.2f}s", counter="ray_tpu_train_gang_reforms_total",
            counter_tags={"kind": kind},
            old_world=str(old_world), world=str(world),
            generation=str(self.generation), seconds=f"{took:.3f}")
        if resharded:
            events.emit_safe(
                "train.gang.reshard",
                f"no replacement capacity for {self.num_hosts} ranks; "
                f"gang resharded onto the surviving world ({world} "
                "ranks, dp axis shrunk)",
                world=str(world), requested=str(self.num_hosts),
                generation=str(self.generation))
        return {"world_size": world, "generation": self.generation,
                "resharded": resharded, "seconds": took,
                "deaths": [(d.rank, d.cause) for d in deaths]}

    # ------------------------------------------------------------------
    # execution
    # ------------------------------------------------------------------
    def run(self, fn: Callable, *args, **kwargs) -> List[Any]:
        """Execute fn(rank, world, *args) on every rank; returns results
        ordered by rank."""
        return self._ray.get(self.run_async(fn, *args, **kwargs),
                             timeout=600)

    def run_async(self, fn: Callable, *args, **kwargs) -> List[Any]:
        """Submit fn(rank, world, *args) on every rank; returns the
        per-rank refs (the elastic fit loop waits on these alongside
        the supervisor's failure signal)."""
        return [h.run.remote(fn, *args, **kwargs) for h in self.hosts]

    def run_sharded(self, fn: Callable, per_rank_args: List[Any],
                    timeout: float = 600.0) -> List[Any]:
        """Execute fn(rank, world, shard) with a DIFFERENT payload per
        rank (multihost data loading: each host gets its batch shard).
        Shards ship as object refs, so each rank's worker pulls its
        share straight from the holding node over the transfer plane
        (core/object_transfer.py) — the driver only brokers locations,
        and per-step input bandwidth scales with the number of hosts
        instead of the single controller socket."""
        if len(per_rank_args) != self.world_size:
            raise ValueError(
                f"need one shard per rank: got {len(per_rank_args)} "
                f"for {self.world_size} hosts")
        refs = [self._ray.put(a) for a in per_rank_args]
        try:
            return self._ray.get(
                [h.run.remote(fn, r) for h, r in zip(self.hosts, refs)],
                timeout=timeout)
        finally:
            try:
                self._ray.free(refs)
            except Exception:
                pass

    def shutdown(self) -> None:
        if self._supervisor is not None:
            self._supervisor.stop()
            self._supervisor = None
        self._teardown_actors(self.hosts, self._pg)
        self.hosts = []
        self._pg = None
