"""Elastic training fault tolerance (ISSUE 11): gang supervision,
preemption-safe collectives, and checkpoint-resume into a resharded
mesh.

Covers: the chaos chain — SIGKILL a rank mid-step -> train.gang.
rank_death -> train.gang.reform -> train.restore, zero steps lost past
the last committed checkpoint; reshard onto the surviving world when no
replacement capacity exists (node agent SIGKILL); CollectiveRankDiedError
raised promptly (<5 s, not the 60 s round timeout) on surviving ranks +
generation fencing; atomic checkpoint commit (torn saves never selected
by latest()); gang construction cleanup (no leaked actors/pg); resume
skipping already-consumed data.
"""
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.exceptions import (CollectiveRankDiedError,
                                CollectiveStaleGenerationError)
from ray_tpu.parallel import MeshSpec
from ray_tpu.train import (ElasticSpmdTrainer, MultiHostSpmd, RunConfig,
                           SpmdTrainerConfig)
from ray_tpu.train import checkpoint as ckpt_mod
from ray_tpu.train.checkpoint import CheckpointManager, is_committed
from ray_tpu.train.multihost import _SpmdHost
from ray_tpu.util import state as state_api

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ENV = {"JAX_PLATFORMS": "cpu",
       "XLA_FLAGS": "--xla_force_host_platform_device_count=4"}


def _data_fn():
    rng = np.random.RandomState(0)
    while True:
        yield {"tokens": rng.randint(0, 255, (8, 32))}


def _events_of(rt, *types):
    rt.drain_local_events()
    rows, _total = rt.cluster_events.query(types=list(types), limit=200)
    return rows


def _wait_first_commit(root: str, timeout: float = 150.0,
                       box: dict = None) -> str:
    deadline = time.time() + timeout
    while time.time() < deadline:
        if box is not None and "err" in box:
            raise box["err"]        # fit died before committing
        if os.path.isdir(root):
            done = [d for d in sorted(os.listdir(root))
                    if d.startswith("checkpoint_")
                    and is_committed(os.path.join(root, d))]
            if done:
                return done[0]
        time.sleep(0.2)
    raise AssertionError("no committed checkpoint appeared")


def _rank_worker_pids(rt):
    """{actor_id: worker pid} of the ALIVE _SpmdHost ranks."""
    rows = state_api.list_actors(
        filters=[("class_name", "=", "_SpmdHost"), ("state", "=", "ALIVE")],
        limit=100)
    by_wid = {w["worker_id"]: w["pid"]
              for w in state_api.list_workers(limit=1000)}
    return {r["actor_id"]: by_wid[r["worker_id"]] for r in rows
            if r["worker_id"] in by_wid}


# ---------------------------------------------------------------------------
# gang supervision / reform machinery (fast tier: no jax worlds)
# ---------------------------------------------------------------------------

class _LiteHost(_SpmdHost):
    """Rank host without jax.distributed: exercises the supervision /
    reform / fencing machinery at actor-process granularity without
    paying two jax worlds per test (the full-world chain runs in the
    slow tier + the train_ft bench)."""

    def join(self, coordinator):
        return {"rank": self.rank, "world": self.world,
                "local_devices": 0, "global_devices": self.world}


def _lite_park(rank, world):
    time.sleep(120)
    return rank


def _lite_echo(rank, world):
    return (rank, world, os.getpid())


def test_supervised_gang_kill_reform_machinery(rt):
    """SIGKILL one rank of a supervised gang mid-run: the supervisor
    flags the death within seconds (train.gang.rank_death), notifies
    the gang's collective group (parked rounds die typed), and
    reform() re-gangs at full size under a bumped generation with
    every old rank process gone."""
    from ray_tpu.util.collective import CollectiveGroup

    gang = MultiHostSpmd(2, resources_per_host={"CPU": 1},
                         supervised=True, collective_groups=["liteg"],
                         _host_cls=_LiteHost)
    try:
        pids = {d["rank"]: d["pid"]
                for d in ray_tpu.get([h.ping.remote() for h in gang.hosts],
                                     timeout=60)}
        # a driver-side handle parks a round the dead rank never joins
        g0 = CollectiveGroup("liteg", 2, 0, generation=gang.generation)
        gang.run_async(_lite_park)
        t_kill = time.time()
        os.kill(pids[1], signal.SIGKILL)
        death = gang.wait_failure(timeout=15)
        assert death is not None and death.rank == 1
        assert time.time() - t_kill < 10.0
        with pytest.raises(CollectiveRankDiedError):
            g0.barrier(timeout=30.0)
        info = gang.reform(timeout=60)
        assert info["world_size"] == 2 and not info["resharded"]
        assert gang.generation == 1
        assert info["deaths"] and info["deaths"][0][0] == 1
        # the reformed gang is fresh processes, all ranks answer
        out = gang.run(_lite_echo)
        assert [o[0] for o in out] == [0, 1]
        assert all(o[2] not in pids.values() for o in out)
        # the old-generation collective handle is fenced out
        with pytest.raises(CollectiveStaleGenerationError):
            CollectiveGroup("liteg", 2, 0, generation=0)
        evs = {e["type"] for e in _events_of(
            rt, "train.gang.rank_death", "train.gang.reform",
            "train.gang.reshard")}
        assert {"train.gang.rank_death", "train.gang.reform"} <= evs
        assert "train.gang.reshard" not in evs
    finally:
        gang.shutdown()


# ---------------------------------------------------------------------------
# tentpole chaos chain: rank SIGKILL mid-step -> reform -> restore
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_chaos_rank_kill_reform_restore_chain(rt, tmp_path):
    """SIGKILL one rank's worker mid-training: the supervisor flags the
    death in seconds, the gang reforms at FULL size (the freed CPU is
    replacement capacity), every rank restores the last committed
    checkpoint, and training finishes all steps — with the
    train.gang.rank_death -> train.gang.reform -> train.restore event
    chain on the driver and zero steps lost past the committed step.

    Slow tier (like the reshard variant): two jax.distributed worlds +
    three compiles cost ~45 s, and the fast tier is budget-bound; the
    supervision/reform/fencing machinery itself is covered in the fast
    tier by test_supervised_gang_kill_reform_machinery, and the bench
    (`--phase train_ft`) exercises this exact chain for MTTR."""
    cfg = SpmdTrainerConfig(model="llama-debug", mesh=MeshSpec(dp=8),
                            total_steps=12, log_every=2, warmup_steps=2,
                            checkpoint_every=2)
    tr = ElasticSpmdTrainer(
        cfg, _data_fn, num_hosts=2, env_per_host=ENV,
        resources_per_host={"CPU": 1},
        run_config=RunConfig(name="ft_chain", storage_path=str(tmp_path)))
    box = {}

    def run():
        try:
            box["res"] = tr.fit()
        except BaseException as e:  # noqa: BLE001 — surfaced below
            box["err"] = e

    th = threading.Thread(target=run, daemon=True)
    th.start()
    ckroot = str(tmp_path / "ft_chain" / "checkpoints")
    _wait_first_commit(ckroot, box=box)
    pids = _rank_worker_pids(rt)
    assert len(pids) == 2
    t_kill = time.time()
    os.kill(sorted(pids.values())[-1], signal.SIGKILL)
    th.join(300)
    assert not th.is_alive(), "elastic fit never finished after the kill"
    assert "err" not in box, box.get("err")
    res = box["res"]
    # every step ran; the reform resumed from a committed step
    assert res.metrics["step"] == 12
    assert res.config["failures"] == 1
    assert res.config["final_world"] == 2          # replaced, not resharded
    assert res.config["generations"] == 1
    # the resumed generation started at a committed checkpoint step and
    # re-ran everything after it — zero steps lost past the commit
    deaths = _events_of(rt, "train.gang.rank_death")
    reforms = _events_of(rt, "train.gang.reform")
    restores = _events_of(rt, "train.restore")
    assert deaths and reforms and restores
    assert not _events_of(rt, "train.gang.reshard")
    assert deaths[0]["ts"] <= reforms[-1]["ts"]
    restore = restores[-1]
    restored_step = int(restore["attrs"]["step"])
    assert restored_step % cfg.checkpoint_every == 0 and restored_step > 0
    assert int(restore["attrs"]["world"]) == 2
    # recovery was prompt: kill -> training-resumed bounded well under
    # the reform timeout (death detect + re-gang + restore)
    assert restore["ts"] - t_kill < 90.0
    # the final checkpoint is committed and selected by latest()
    latest = CheckpointManager(ckroot).latest()
    assert latest is not None and latest.metadata()["step"] == 12


# ---------------------------------------------------------------------------
# preemption-safe collectives
# ---------------------------------------------------------------------------

@ray_tpu.remote
class _Member:
    def pid(self):
        return os.getpid()

    def barrier_round(self, group, world, rank, timeout=60.0):
        from ray_tpu.util.collective import CollectiveGroup
        g = CollectiveGroup(group, world, rank, generation=0)
        t0 = time.monotonic()
        try:
            g.barrier(timeout=timeout)
            return ("ok", time.monotonic() - t0)
        except CollectiveRankDiedError as e:
            return ("rank_died", time.monotonic() - t0, str(e))

    def idle(self):
        return True


def test_collective_rank_death_fails_parked_poll_fast(rt):
    """A surviving rank parked in a collective round must get a typed
    CollectiveRankDiedError within seconds of its gang-mate's death —
    not spin out the 60 s round timeout."""
    from ray_tpu.train.elastic import GangSupervisor

    a = _Member.remote()
    b = _Member.remote()
    ray_tpu.get([a.idle.remote(), b.idle.remote()], timeout=60)
    sup = GangSupervisor({0: a.actor_id, 1: b.actor_id},
                         collective_groups=["ftgang"])
    try:
        ref = a.barrier_round.remote("ftgang", 2, 0)
        time.sleep(1.0)            # let rank 0 park in poll
        pid = ray_tpu.get(b.pid.remote(), timeout=30)
        t_kill = time.time()
        os.kill(pid, signal.SIGKILL)
        out = ray_tpu.get(ref, timeout=30)
        elapsed = time.time() - t_kill
        assert out[0] == "rank_died", out
        assert "rank 1" in out[2]
        assert elapsed < 5.0, f"took {elapsed:.1f}s (should be seconds)"
        death = sup.wait(timeout=10)
        assert death is not None and death.rank == 1
        evs = _events_of(rt, "train.gang.rank_death")
        assert any(e["attrs"]["rank"] == "1" for e in evs)
    finally:
        sup.stop()
        ray_tpu.kill(a)


def test_collective_generation_fencing(rt):
    """After a gang reform advances the group generation, verbs stamped
    with the old generation are fenced with
    CollectiveStaleGenerationError (zombie ranks of a dead world must
    not corrupt the new world's rounds) — and the new generation can
    rendezvous at a SMALLER world size."""
    from ray_tpu.util.collective import (CollectiveGroup,
                                         advance_group_generation,
                                         destroy_collective_group)

    g0 = CollectiveGroup("fence", 2, 0, generation=0)
    assert advance_group_generation("fence", 3, world_size=1)
    # the old-generation handle is fenced mid-round
    with pytest.raises(CollectiveStaleGenerationError):
        g0.barrier(timeout=5.0)
    # a stale rank can't even re-join under its old generation
    with pytest.raises(CollectiveStaleGenerationError):
        CollectiveGroup("fence", 1, 0, generation=0)
    # the reformed (resharded) world rendezvouses alone at world=1
    g1 = CollectiveGroup("fence", 1, 0, generation=3)
    g1.barrier(timeout=10.0)
    assert g1.allgather(7, timeout=10.0) == [7]
    destroy_collective_group("fence")
    # a FRESH rendezvous actor (the old one died with the preempted
    # host) must ADOPT a newer generation, not fence the new world out
    g2 = CollectiveGroup("fence2", 1, 0, generation=7)
    g2.barrier(timeout=10.0)
    with pytest.raises(CollectiveStaleGenerationError):
        CollectiveGroup("fence2", 1, 0, generation=6)
    destroy_collective_group("fence2")


# ---------------------------------------------------------------------------
# atomic checkpoint commit (satellite)
# ---------------------------------------------------------------------------

def test_torn_save_never_selected_by_latest(tmp_path):
    """latest()/_prune() must only consider COMMITTED checkpoints: a
    crash mid-save leaves a tmp- staging dir (or, for pre-atomic
    writers, a meta-less directory) that must never be restored."""
    root = str(tmp_path / "ckpts")
    mgr = CheckpointManager(root, num_to_keep=2)
    state = {"w": np.arange(8, dtype=np.float32)}
    mgr.save(state, 1)
    assert mgr.latest().metadata()["step"] == 1
    # a torn save: directory exists, data partially written, NO meta
    torn = os.path.join(root, "checkpoint_000000002")
    os.makedirs(torn)
    with open(os.path.join(torn, "partial.bin"), "wb") as f:
        f.write(b"\x00" * 16)
    assert not is_committed(torn)
    assert mgr.latest().metadata()["step"] == 1
    # an abandoned staging dir is also invisible
    os.makedirs(os.path.join(root, "tmp-checkpoint_000000003-dead"))
    assert mgr.latest().metadata()["step"] == 1
    # pruning keeps only committed dirs in its count and reclaims
    # STALE staging dirs (old mtime), never fresh in-flight ones
    old_tmp = os.path.join(root, "tmp-checkpoint_000000004-stale")
    os.makedirs(old_tmp)
    past = time.time() - 2 * CheckpointManager.TMP_TTL_S
    os.utime(old_tmp, (past, past))
    mgr.save(state, 5)
    mgr.save(state, 6)
    mgr.save(state, 7)
    kept = sorted(d for d in os.listdir(root)
                  if d.startswith("checkpoint_")
                  and is_committed(os.path.join(root, d)))
    assert kept == ["checkpoint_000000006", "checkpoint_000000007"]
    assert not os.path.exists(old_tmp)
    assert os.path.exists(os.path.join(
        root, "tmp-checkpoint_000000003-dead"))   # fresh: left alone


def test_crash_mid_save_preserves_previous_checkpoint(tmp_path,
                                                      monkeypatch):
    """A save that dies before the commit rename must leave the
    previous checkpoint at the SAME path fully intact (the old code
    rmtree'd the destination first)."""
    from ray_tpu.train.checkpoint import restore_pytree, save_pytree

    path = str(tmp_path / "ck")
    save_pytree({"w": np.ones(4, dtype=np.float32)}, path, step=1)
    assert is_committed(path)

    class _Boom:
        def save(self, directory, state):
            os.makedirs(directory, exist_ok=True)
            with open(os.path.join(directory, "half"), "wb") as f:
                f.write(b"x")
            raise RuntimeError("crash mid-save")

    monkeypatch.setattr(ckpt_mod, "_checkpointer", lambda: _Boom())
    with pytest.raises(RuntimeError, match="crash mid-save"):
        save_pytree({"w": np.zeros(4, dtype=np.float32)}, path, step=2)
    # the original checkpoint is still committed and restorable
    assert is_committed(path)
    restored = restore_pytree(path)
    np.testing.assert_array_equal(restored["w"],
                                  np.ones(4, dtype=np.float32))


def test_crash_between_overwrite_renames_recovers_previous(tmp_path):
    """Overwriting a checkpoint at an EXISTING path slides the old one
    aside before the commit rename; a crash in that window must not
    lose it — latest() promotes the slide-aside copy back."""
    root = str(tmp_path / "cw")
    mgr = CheckpointManager(root, num_to_keep=2)
    mgr.save({"w": np.ones(4, dtype=np.float32)}, 3)
    base = "checkpoint_000000003"
    # simulate the crash window: committed dir slid aside, target gone
    os.rename(os.path.join(root, base),
              os.path.join(root, f"tmp-old-{base}-deadbeef"))
    assert not os.path.exists(os.path.join(root, base))
    latest = mgr.latest()
    assert latest is not None and latest.metadata()["step"] == 3
    assert os.path.isdir(os.path.join(root, base))


# ---------------------------------------------------------------------------
# gang construction cleanup (satellite)
# ---------------------------------------------------------------------------

class _JoinBomb(_SpmdHost):
    def join(self, coordinator):
        raise RuntimeError("synthetic join failure")


def test_failed_gang_leaves_no_actors_or_pg(rt):
    """A gang whose join fails (or whose placement group can't be
    satisfied) must kill every already-spawned rank actor and remove
    the pg — partially-built worlds must not leak."""
    with pytest.raises(Exception, match="synthetic join failure"):
        MultiHostSpmd(2, resources_per_host={"CPU": 1},
                      _host_cls=_JoinBomb)
    deadline = time.time() + 20
    while time.time() < deadline:
        alive = state_api.list_actors(
            filters=[("class_name", "=", "_JoinBomb"),
                     ("state", "=", "ALIVE")], limit=10)
        if not alive:
            break
        time.sleep(0.1)
    assert not alive, "rank actors leaked after failed gang construction"

    # STRICT_SPREAD over more nodes than exist: the pg can't be placed;
    # the constructor must remove it instead of leaking a pending pg
    with pytest.raises(RuntimeError, match="placement group"):
        MultiHostSpmd(3, resources_per_host={"CPU": 1}, spread=True,
                      pg_timeout=1.0)
    deadline = time.time() + 15       # removal rides the dispatcher inbox
    while time.time() < deadline:
        pgs = state_api.list_placement_groups(limit=100)
        if all(p.get("state") == "REMOVED" for p in pgs):
            break
        time.sleep(0.1)
    assert all(p.get("state") == "REMOVED" for p in pgs), pgs


# ---------------------------------------------------------------------------
# resume skips consumed data (satellite)
# ---------------------------------------------------------------------------

class _RecordingIter:
    """Deterministic batch stream with the optional fast_forward(n)
    iterator-state hook: fast_forward(n) seeks so the NEXT batch is
    batch index n."""

    def __init__(self, log):
        self.i = 0
        self.log = log

    def __iter__(self):
        return self

    def __next__(self):
        i = self.i
        self.i += 1
        self.log.append(i)
        rng = np.random.RandomState(i)
        return {"tokens": rng.randint(0, 255, (8, 16))}

    def fast_forward(self, n):
        self.log.append(("ff", n))
        self.i = n


@pytest.mark.slow
def test_resume_fast_forwards_consumed_batches(tmp_path):
    """SpmdTrainer.fit(resume_from=...) must not re-train on batches
    the crashed run already consumed: step i trains on batch i, so a
    resume at start_step seeks the iterator there (via the
    fast_forward hook when the iterator has one)."""
    from ray_tpu.train import SpmdTrainer

    log1 = []
    cfg = SpmdTrainerConfig(model="llama-debug", mesh=MeshSpec(),
                            total_steps=4, log_every=2, warmup_steps=1,
                            checkpoint_every=2)
    tr = SpmdTrainer(cfg, lambda: _RecordingIter(log1),
                     run_config=RunConfig(name="ff1",
                                          storage_path=str(tmp_path)))
    res = tr.fit()
    assert res.metrics["step"] == 4

    log2 = []
    cfg2 = SpmdTrainerConfig(model="llama-debug", mesh=MeshSpec(),
                             total_steps=6, log_every=2, warmup_steps=1)
    tr2 = SpmdTrainer(cfg2, lambda: _RecordingIter(log2),
                      run_config=RunConfig(name="ff2",
                                           storage_path=str(tmp_path)))
    res2 = tr2.fit(resume_from=res.checkpoint.path)
    assert res2.metrics["step"] == 6
    # batch 0 drawn for init, then the hook seeks to start_step=4 and
    # steps 4..5 train on batches 4 and 5 (the loop prefetches one
    # more, never trained): batches 1..3 — consumed by the crashed run
    # — are NEVER re-drawn
    assert log2[0] == 0
    assert ("ff", 4) in log2
    drawn = [x for x in log2 if isinstance(x, int) and x > 0]
    assert drawn[:2] == [4, 5] and all(x >= 4 for x in drawn), log2
