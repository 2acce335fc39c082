"""Ray-Client mode (reference: python/ray/util/client, ray.init("ray://")).

A standalone host process (`python -m ray_tpu.client.server`) owns the
real runtime; this test process connects with
`ray_tpu.init(address="ray://...")` and drives the public API through
the thin-client proxy: tasks, objects, actors (incl. named), generators,
wait/cancel, resources, placement groups, error propagation.
"""
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.core import runtime as runtime_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def client():
    assert not runtime_mod.runtime_initialized(), \
        "client tests need a fresh process-global runtime"
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    from ray_tpu.util.jaxenv import subprocess_env_cpu
    subprocess_env_cpu(env)  # the client host never owns a chip
    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu.client.server",
         "--listen", "127.0.0.1:0", "--num-cpus", "4"],
        cwd=REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL, text=True)
    try:
        address = proc.stdout.readline().strip()
        assert address.startswith("ray://"), f"bad server banner {address!r}"
        rt = ray_tpu.init(address=address)
        yield rt
    finally:
        ray_tpu.shutdown()
        proc.terminate()
        proc.wait(timeout=10)


def test_client_tasks_and_objects(client):
    @ray_tpu.remote
    def add(a, b):
        return a + b

    assert ray_tpu.get(add.remote(2, 3)) == 5

    # put/get round-trip incl. arrays; ref args resolve server-side
    big = np.arange(10000, dtype=np.float32)
    ref = ray_tpu.put(big)
    np.testing.assert_array_equal(ray_tpu.get(ref), big)

    @ray_tpu.remote
    def total(x):
        return float(x.sum())

    assert ray_tpu.get(total.remote(ref)) == pytest.approx(big.sum())

    # fan-out through the remote scheduler
    refs = [add.remote(i, i) for i in range(20)]
    assert ray_tpu.get(refs) == [2 * i for i in range(20)]


def test_client_wait_and_cancel(client):
    @ray_tpu.remote
    def slow(sec):
        time.sleep(sec)
        return sec

    fast = slow.remote(0.05)
    slower = slow.remote(5.0)
    ready, pending = ray_tpu.wait([fast, slower], num_returns=1,
                                  timeout=3.0)
    assert ready == [fast] and pending == [slower]
    ray_tpu.cancel(slower, force=True)
    with pytest.raises(Exception):
        ray_tpu.get(slower, timeout=10)


def test_client_error_propagation(client):
    @ray_tpu.remote
    def boom():
        raise ValueError("remote kaboom")

    with pytest.raises(Exception, match="remote kaboom"):
        ray_tpu.get(boom.remote())

    with pytest.raises(Exception):
        ray_tpu.get(ray_tpu.ObjectRef("obj-nonexistent"), timeout=0.5)


def test_client_actors(client):
    @ray_tpu.remote
    class Counter:
        def __init__(self, start=0):
            self.n = start

        def inc(self, k=1):
            self.n += k
            return self.n

        def get(self):
            return self.n

    c = Counter.remote(10)
    assert ray_tpu.get(c.inc.remote()) == 11
    assert ray_tpu.get(c.inc.remote(5)) == 16
    assert ray_tpu.get(c.get.remote()) == 16
    ray_tpu.kill(c)


def test_client_named_actors(client):
    @ray_tpu.remote
    class KV:
        def __init__(self):
            self.d = {}

        def set(self, k, v):
            self.d[k] = v

        def get(self, k):
            return self.d.get(k)

    KV.options(name="client-kv").remote()
    h = ray_tpu.get_actor("client-kv")
    ray_tpu.get(h.set.remote("x", 42))
    assert ray_tpu.get(h.get.remote("x")) == 42
    ray_tpu.kill(h)


def test_client_namespaced_get_actor(client):
    """A reconnect with a non-default namespace must resolve named actors
    in the CLIENT's namespace, not the host's default (r5 review fix)."""
    address = client.address
    ray_tpu.shutdown()
    rt = ray_tpu.init(address=address, namespace="ns2")
    try:
        @ray_tpu.remote
        class Flag:
            def get(self):
                return "ns2-flag"

        Flag.options(name="flag").remote()
        h = ray_tpu.get_actor("flag")   # default ns must be the client's
        assert ray_tpu.get(h.get.remote()) == "ns2-flag"
        ray_tpu.kill(h)
    finally:
        ray_tpu.shutdown()
        # restore the module fixture's default-namespace connection
        rt2 = ray_tpu.init(address=address)
        assert rt2.ping() == "pong"


def test_client_streaming_generator(client):
    @ray_tpu.remote(num_returns="streaming")
    def gen(n):
        for i in range(n):
            yield i * i

    got = [ray_tpu.get(r) for r in gen.remote(5)]
    assert got == [0, 1, 4, 9, 16]


def test_client_resources_and_pg(client):
    res = ray_tpu.cluster_resources()
    assert res.get("CPU") == 4.0
    avail = ray_tpu.available_resources()
    assert avail.get("CPU", 0) <= 4.0

    from ray_tpu.util.placement_group import (
        placement_group, remove_placement_group)
    pg = placement_group([{"CPU": 1}, {"CPU": 1}], strategy="PACK")
    ray_tpu.get(pg.ready(), timeout=15)

    @ray_tpu.remote
    def where():
        return os.getpid()

    pid = ray_tpu.get(where.options(
        placement_group=pg, bundle_index=0).remote())
    assert isinstance(pid, int)
    remove_placement_group(pg)


def test_client_data_pipeline(client):
    """ray_tpu.data pipelines run transparently through the client: map
    stages and the distributed shuffle submit their tasks over the
    proxied runtime."""
    from ray_tpu import data

    ds = data.range(1000).map_batches(lambda b: {"id": b["id"] * 2})
    assert ds.sum("id") == 2 * sum(range(1000))
    shuffled = data.range(100).random_shuffle(seed=1)
    assert sorted(r["id"] for r in shuffled.take_all()) == list(range(100))


def test_client_shutdown_reconnect(client):
    """shutdown() disconnects the client but leaves the host up; a new
    init(address=...) reconnects."""
    address = client.address
    ray_tpu.shutdown()
    assert not runtime_mod.runtime_initialized()
    rt2 = ray_tpu.init(address=address)

    @ray_tpu.remote
    def ping():
        return "alive"

    assert ray_tpu.get(ping.remote()) == "alive"
    # leave connected: the fixture's finalizer does the last shutdown
    assert rt2.ping() == "pong"
