"""Observability & util tests (parity model: python/ray/tests/test_state_api.py,
test_metrics_agent.py, test_queue.py, test_actor_pool.py)."""
import json
import time
import urllib.request

import numpy as np
import pytest

import ray_tpu
from ray_tpu.util import metrics as metrics_mod
from ray_tpu.util import state as state_mod
from ray_tpu.util.actor_pool import ActorPool
from ray_tpu.util.collective import init_collective_group
from ray_tpu.util.queue import Queue, Empty


@ray_tpu.remote
def _square(x):
    return x * x


@ray_tpu.remote
class _Doubler:
    def double(self, x):
        return 2 * x


# ---------- metrics ----------

def test_counter_gauge_histogram():
    metrics_mod.clear_registry()
    c = metrics_mod.Counter("req_total", "requests", ("route",))
    c.inc(tags={"route": "/a"})
    c.inc(2.0, tags={"route": "/a"})
    assert c.get({"route": "/a"}) == 3.0
    with pytest.raises(ValueError):
        c.inc(-1)

    g = metrics_mod.Gauge("inflight")
    g.set(5)
    g.dec()
    assert g.get() == 4.0

    h = metrics_mod.Histogram("latency_s", boundaries=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0):
        h.observe(v)
    text = metrics_mod.exposition()
    assert "req_total" in text and 'route="/a"' in text
    assert "latency_s_bucket" in text and "latency_s_count 4" in text
    assert 0.1 <= h.percentile(50) <= 1.0


def test_metrics_timer():
    metrics_mod.clear_registry()
    h = metrics_mod.Histogram("op_s", boundaries=(0.001, 1.0))
    with metrics_mod.timer(h):
        time.sleep(0.002)
    assert h._count[()] == 1


# ---------- state API ----------

def test_state_api_lists(rt):
    refs = [_square.remote(i) for i in range(3)]
    ray_tpu.get(refs)
    d = _Doubler.remote()
    assert ray_tpu.get(d.double.remote(4)) == 8

    tasks = state_mod.list_tasks(limit=1000)
    assert any(t["name"].startswith("_square") and t["state"] == "FINISHED"
               for t in tasks)
    actors = state_mod.list_actors()
    assert any(a["class_name"] == "_Doubler" and a["state"] == "ALIVE"
               for a in actors)
    nodes = state_mod.list_nodes()
    assert len(nodes) == 1 and nodes[0]["alive"]
    workers = state_mod.list_workers()
    assert any(w["state"] == "actor" for w in workers)
    objs = state_mod.list_objects(limit=1000)
    assert any(o["state"] == "ready" for o in objs)

    filtered = state_mod.list_actors(filters=[("state", "=", "ALIVE")])
    assert all(a["state"] == "ALIVE" for a in filtered)

    summ = state_mod.summarize_tasks()
    assert summ["total"] >= 4
    cs = state_mod.cluster_summary()
    assert cs["nodes"] == 1 and cs["actors"] >= 1


# ---------- timeline ----------

def test_timeline_export(rt, tmp_path):
    ray_tpu.get([_square.remote(i) for i in range(2)])
    from ray_tpu.observability import timeline
    path = timeline(str(tmp_path / "trace.json"))
    events = json.load(open(path))
    spans = [e for e in events if e.get("ph") == "X"]
    assert spans, "no task spans exported"
    assert all("ts" in e and "dur" in e for e in spans)


# ---------- dashboard ----------

def test_dashboard_endpoints(rt):
    from ray_tpu.observability import start_dashboard, stop_dashboard
    dash = start_dashboard()
    try:
        for route in ("/api/cluster", "/api/nodes", "/api/actors",
                      "/api/tasks", "/api/objects", "/api/workers",
                      "/api/timeline"):
            with urllib.request.urlopen(dash.url + route, timeout=5) as r:
                assert r.status == 200
                json.loads(r.read())
        with urllib.request.urlopen(dash.url + "/metrics", timeout=5) as r:
            assert r.status == 200
        with urllib.request.urlopen(dash.url + "/nope", timeout=5) as r:
            pass
    except urllib.error.HTTPError as e:
        assert e.code == 404
    finally:
        stop_dashboard()


# ---------- queue ----------

def test_queue_fifo_and_batch(rt):
    q = Queue(maxsize=4)
    for i in range(3):
        q.put(i)
    assert q.qsize() == 3
    assert [q.get() for _ in range(3)] == [0, 1, 2]
    assert q.empty()
    with pytest.raises(Empty):
        q.get_nowait()
    q.put_nowait_batch([7, 8])
    assert q.get_nowait_batch(5) == [7, 8]
    q.shutdown()


def test_queue_cross_task(rt):
    q = Queue()

    @ray_tpu.remote
    def producer(q, n):
        for i in range(n):
            q.put(i * 10)
        return n

    ray_tpu.get(producer.remote(q, 3))
    assert sorted(q.get() for _ in range(3)) == [0, 10, 20]
    q.shutdown()


# ---------- actor pool ----------

def test_actor_pool_ordered_and_unordered(rt):
    actors = [_Doubler.remote() for _ in range(2)]
    pool = ActorPool(actors)
    out = list(pool.map(lambda a, v: a.double.remote(v), range(5)))
    assert out == [0, 2, 4, 6, 8]
    out_u = sorted(pool.map_unordered(lambda a, v: a.double.remote(v),
                                      range(5)))
    assert out_u == [0, 2, 4, 6, 8]


def test_actor_pool_more_work_than_actors(rt):
    pool = ActorPool([_Doubler.remote()])
    for v in range(4):
        pool.submit(lambda a, v: a.double.remote(v), v)
    results = [pool.get_next() for _ in range(4)]
    assert results == [0, 2, 4, 6]
    assert not pool.has_next()


# ---------- collective ----------

def test_collective_allreduce_across_tasks(rt):
    @ray_tpu.remote
    def rank_worker(rank, world):
        from ray_tpu.util.collective import init_collective_group
        g = init_collective_group(world, rank, "testgrp")
        g.barrier()
        total = g.allreduce(np.array([rank + 1.0]), op="sum")
        gathered = g.allgather(rank)
        bc = g.broadcast(value="hello" if rank == 0 else None, src=0)
        return float(total[0]), sorted(gathered), bc

    world = 3
    outs = ray_tpu.get([rank_worker.remote(r, world) for r in range(world)])
    for total, gathered, bc in outs:
        assert total == 6.0            # 1+2+3
        assert gathered == [0, 1, 2]
        assert bc == "hello"


# ---------- memory monitor ----------

def test_memory_summary(rt):
    from ray_tpu.observability import memory_summary
    ray_tpu.get(_square.remote(3))
    s = memory_summary()
    assert s["host_total_bytes"] > 0
    assert s["driver_rss_bytes"] > 0
    assert s["store_capacity_bytes"] is not None


def test_actor_pool_survives_task_error(rt):
    @ray_tpu.remote
    def boom(a, v):
        raise ValueError("kaboom")

    pool = ActorPool([_Doubler.remote()])
    pool.submit(lambda a, v: a.double.remote(v), 1)
    pool.submit(lambda a, v: boom.remote(a, v), 2)
    pool.submit(lambda a, v: a.double.remote(v), 3)
    assert pool.get_next() == 2
    with pytest.raises(Exception):
        pool.get_next()
    assert pool.get_next() == 6          # actor released, pool still works
    assert not pool.has_next()


def test_collective_reinit_same_name_fresh_epoch(rt):
    @ray_tpu.remote
    def phase(rank, world, expected_sum):
        from ray_tpu.util.collective import init_collective_group
        g = init_collective_group(world, rank, "epochgrp")
        out = g.allreduce(np.array([float(expected_sum) / world]))
        return float(out[0])

    w = 2
    r1 = ray_tpu.get([phase.remote(r, w, 10.0) for r in range(w)])
    assert all(abs(v - 10.0) < 1e-6 for v in r1)
    # second phase, same group name: must compute fresh, not return cache
    r2 = ray_tpu.get([phase.remote(r, w, 20.0) for r in range(w)])
    assert all(abs(v - 20.0) < 1e-6 for v in r2)


def test_metrics_label_escaping():
    metrics_mod.clear_registry()
    c = metrics_mod.Counter("esc_total")
    c.inc(tags={"p": 'say "hi"\n'})
    text = metrics_mod.exposition()
    assert 'p="say \\"hi\\"\\n"' in text


def test_worker_logs_captured_and_streamed(capfd):
    """O7: worker prints land in per-worker files and stream to the driver
    prefixed with the worker id."""
    import subprocess, sys, textwrap
    code = textwrap.dedent("""
        import time
        import ray_tpu

        @ray_tpu.remote
        def noisy():
            print("hello-from-worker")
            return 1

        ray_tpu.init(num_cpus=2)
        ray_tpu.get(noisy.remote())
        time.sleep(0.6)         # let the streamer poll
        ray_tpu.shutdown()
    """)
    env = {**__import__('os').environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120, env=env)
    assert "hello-from-worker" in out.stdout
    assert "(worker-" in out.stdout       # prefixed streaming


def test_cli_against_dashboard(rt, tmp_path):
    """The `python -m ray_tpu` CLI reads the live dashboard endpoints."""
    import io
    from contextlib import redirect_stdout
    from ray_tpu.observability import start_dashboard, stop_dashboard
    from ray_tpu.cli import main as cli_main

    ray_tpu.get(_square.remote(2))
    dash = start_dashboard()
    try:
        buf = io.StringIO()
        with redirect_stdout(buf):
            cli_main(["--address", dash.url, "status"])
        assert json.loads(buf.getvalue())["nodes"] == 1

        buf = io.StringIO()
        with redirect_stdout(buf):
            cli_main(["--address", dash.url, "list", "tasks", "--json"])
        assert any(t["state"] == "FINISHED" for t in json.loads(buf.getvalue()))

        out_path = str(tmp_path / "tl.json")
        buf = io.StringIO()
        with redirect_stdout(buf):
            cli_main(["--address", dash.url, "timeline", "-o", out_path])
        assert json.load(open(out_path))

        buf = io.StringIO()
        with redirect_stdout(buf):
            cli_main(["--address", dash.url, "summary", "tasks"])
        assert json.loads(buf.getvalue())["total"] >= 1
    finally:
        stop_dashboard()


def test_dashboard_html_and_serve_endpoint(rt):
    import json as _json
    import urllib.request
    from ray_tpu.observability.dashboard import start_dashboard, \
        stop_dashboard
    dash = start_dashboard(port=0)
    try:
        with urllib.request.urlopen(dash.url + "/", timeout=10) as r:
            html = r.read().decode()
            assert "ray_tpu dashboard" in html
            assert "text/html" in r.headers.get("Content-Type", "")
        with urllib.request.urlopen(dash.url + "/api/serve",
                                    timeout=10) as r:
            out = _json.loads(r.read())
        assert out["running"] in (True, False)
    finally:
        stop_dashboard()


def test_profiler_trace_and_timing(tmp_path):
    import jax
    import jax.numpy as jnp
    from ray_tpu.observability import profiler

    @jax.jit
    def step(state, batch):
        s = state + batch.sum()
        return s, {"loss": s}

    table = profiler.SpanTable()
    with profiler.trace(str(tmp_path / "prof")):
        with table.span("demo-step"):
            out, _ = step(jnp.float32(0), jnp.ones((8, 8)))
            out.block_until_ready()
    produced = list((tmp_path / "prof").rglob("*"))
    assert produced, "no trace files written"
    # timing a jitted step: one span per fenced call
    state, batch = jnp.float32(0), jnp.ones((4, 4))
    for i in range(3):
        with table.step("timed-step", i):
            state, m = step(state, batch)
            m["loss"].block_until_ready()
    n, total_ns, max_ns, cpu_ns = table.snapshot()["timed-step"]
    assert n == 3 and 0 < max_ns <= total_ns and 0 < cpu_ns <= total_ns
    assert table.snapshot()["demo-step"][0] == 1


def test_an_async_actors_calls_are_rows_of_its_process_by_segment(rt):
    """core/worker.py adds each async actor call's own time on the
    actor's event loop to the process's table: entry to the first
    await, the reply, the telemetry; the awaited time is in none."""
    @ray_tpu.remote(max_concurrency=4)
    class Napper:
        async def nap(self, seconds):
            import asyncio
            await asyncio.sleep(seconds)
            return seconds

        async def fail(self):
            raise ValueError("no")

        async def rows(self):
            import sys
            from ray_tpu.observability.profiler import process_table
            return process_table().snapshot(), "jax" in sys.modules

        def rows_sync(self):
            from ray_tpu.observability.profiler import process_table
            return process_table().snapshot()

    a = Napper.remote()
    first, jax_loaded = ray_tpu.get(a.rows.remote(), timeout=60)
    assert not jax_loaded       # the table asked for no annotation
    # the call that reads the table has resolved; its reply is to come
    assert first["actor.call.resolve"][0] == 1
    assert first["actor.call.reply"][0] == 0
    assert ray_tpu.get([a.nap.remote(0.2) for _ in range(3)],
                       timeout=60) == [0.2] * 3
    with pytest.raises(Exception, match="no"):
        ray_tpu.get(a.fail.remote(), timeout=60)
    # a synchronous method runs on a pool thread, not on the loop
    rows = ray_tpu.get(a.rows_sync.remote(), timeout=60)
    for name in ("actor.call.resolve", "actor.call.reply",
                 "actor.call.telemetry"):
        n, total, longest, cpu = rows[name]
        # rows(), three naps, the failure (an error is a reply too)
        assert n == 5 and cpu == 0 and 0 < longest <= total, name
        # 0.6 s were slept: no segment holds an awaited interval
        assert total < 100_000_000, (name, total)
    assert rows["replica.stream_next"] == [0, 0, 0, 0]


def test_cli_serve_run(tmp_path):
    """`ray_tpu serve run module:app` serves over real HTTP."""
    import json as _json
    import subprocess
    import sys as _sys
    import time as _time
    import urllib.request
    app_py = tmp_path / "myapp.py"
    app_py.write_text(
        "from ray_tpu import serve\n"
        "@serve.deployment\n"
        "def hello(body):\n"
        "    return {'hello': body}\n"
        "app = hello.bind()\n")
    import os as _os
    env = dict(_os.environ)
    repo = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))
    env["PYTHONPATH"] = _os.pathsep.join(
        [repo, str(tmp_path), *env.get("PYTHONPATH", "").split(_os.pathsep)])
    from ray_tpu.util.jaxenv import subprocess_env_cpu
    subprocess_env_cpu(env)
    proc = subprocess.Popen(
        [_sys.executable, "-m", "ray_tpu", "serve", "run", "myapp:app",
         "--port", "0"],
        env=env, cwd=str(tmp_path), stdout=subprocess.PIPE, text=True)
    # watchdog: a wedged child must fail the test, not hang readline()
    import threading as _threading
    killer = _threading.Timer(60, proc.kill)
    killer.start()
    try:
        line = proc.stdout.readline()
        assert "serving myapp:app on http://" in line, line
        url = line.strip().rsplit(" ", 1)[-1]
        deadline = _time.time() + 20
        out = None
        while _time.time() < deadline:
            try:
                req = urllib.request.Request(
                    url + "/", data=_json.dumps(7).encode(),
                    headers={"Content-Type": "application/json"})
                out = _json.loads(
                    urllib.request.urlopen(req, timeout=5).read())
                break
            except Exception:
                _time.sleep(0.3)
        assert out == {"hello": 7}
    finally:
        killer.cancel()
        proc.terminate()
        proc.wait(timeout=10)
