"""Elastic training FT, reshard leg (ISSUE 11): killing a rank's node
agent when the cluster has NO spare capacity must reform the gang
RESHARDED onto the surviving world instead of dying.

Lives in its own module (not test_train_ft.py) because it builds its
own 2-node cluster topology — the shared module-scoped `rt` fixture of
a sibling test would still hold the process-global runtime.
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
from ray_tpu.parallel import MeshSpec
from ray_tpu.train import ElasticSpmdTrainer, RunConfig, SpmdTrainerConfig
from ray_tpu.train.checkpoint import is_committed

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


@pytest.mark.slow
def test_chaos_node_agent_kill_reshards_onto_survivors(tmp_path):
    """Kill a rank's NODE AGENT when the cluster has no spare capacity:
    the gang cannot be replaced at full size, so it reforms RESHARDED
    onto the surviving world (dp axis shrunk, world 2 -> 1) and still
    finishes from the last committed checkpoint."""
    os.environ["RAY_TPU_GANG_REPLACE_WAIT_S"] = "2"
    rt = ray_tpu.init(num_cpus=1, listen="127.0.0.1:0")
    agent = None
    try:
        env = dict(os.environ)
        # the agent's workers must be able to import THIS module: the
        # rank payload references functions defined here, and cloudpickle
        # ships importable-module functions by reference (real multihost
        # deployments ship user code via a shared filesystem or
        # runtime_env py_modules the same way)
        env["PYTHONPATH"] = os.pathsep.join(
            [REPO, os.path.dirname(os.path.abspath(__file__)),
             *env.get("PYTHONPATH", "").split(os.pathsep)])
        from ray_tpu.util.jaxenv import subprocess_env_cpu
        subprocess_env_cpu(env)
        agent = subprocess.Popen(
            [sys.executable, "-m", "ray_tpu.core.node", rt.tcp_address,
             "--num-cpus", "1"], env=env, cwd=REPO)
        deadline = time.time() + 60
        while time.time() < deadline and len(rt.cluster_nodes) < 2:
            time.sleep(0.05)
        assert len(rt.cluster_nodes) == 2, "agent failed to register"

        cfg = SpmdTrainerConfig(model="llama-debug", mesh=MeshSpec(dp=8),
                                total_steps=10, log_every=2,
                                warmup_steps=2, checkpoint_every=2)
        tr = ElasticSpmdTrainer(
            cfg, _data_fn, num_hosts=2, env_per_host=ENV,
            resources_per_host={"CPU": 1}, spread=True,
            run_config=RunConfig(name="ft_reshard",
                                 storage_path=str(tmp_path)))
        box = {}

        def run():
            try:
                box["res"] = tr.fit()
            except BaseException as e:  # noqa: BLE001
                box["err"] = e

        th = threading.Thread(target=run, daemon=True)
        th.start()
        _wait_first_commit(str(tmp_path / "ft_reshard" / "checkpoints"),
                           box=box)
        agent.send_signal(signal.SIGKILL)
        th.join(300)
        assert not th.is_alive(), "fit never finished after agent kill"
        assert "err" not in box, box.get("err")
        res = box["res"]
        assert res.metrics["step"] == 10
        assert res.config["final_world"] == 1       # resharded world
        assert res.metrics["world"] == 1
        reshards = _events_of(rt, "train.gang.reshard")
        assert reshards, "reshard event missing"
        assert int(reshards[-1]["attrs"]["world"]) == 1
        restores = _events_of(rt, "train.restore")
        assert restores and int(restores[-1]["attrs"]["world"]) == 1
    finally:
        os.environ.pop("RAY_TPU_GANG_REPLACE_WAIT_S", None)
        if agent is not None:
            try:
                agent.kill()
            except OSError:
                pass
        ray_tpu.shutdown()


