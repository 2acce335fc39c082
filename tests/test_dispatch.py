"""Decentralized batched dispatch (ISSUE 10): submit coalescing, worker
leases, pipelined actor dispatch, driver-bypass actor calls, and the
chaos coverage that keeps PR-3/PR-4 recovery semantics intact with
leases enabled:

* fan-outs coalesce into api_submit_many batches and multi-slot lease
  frames (message amplification drops; counters assert it),
* a blocked lease head releases its unstarted slots (no deadlock on
  nested-ref waits, no serialization behind a blocked worker),
* killing a node agent holding an active lease mid-batch yields the
  task.lease.grant -> task.lease.revoke -> task.retry -> task.finish
  chain with ZERO lost tasks,
* steady-state actor-to-actor calls ride direct worker->worker
  channels: zero driver control messages per call (the PR-2
  relay_bytes==0 analogue), with escaped refs published and in-flight
  calls failing over to the driver path on actor death.
"""
import json
import os
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu.exceptions import ActorDiedError
from ray_tpu.util import state as state_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TASK_MSG_KINDS = ("submit", "submit_many", "task_done", "get_request",
                  "put")


@pytest.fixture()
def rt():
    ray_tpu.shutdown()
    r = ray_tpu.init(num_cpus=2)
    yield r
    ray_tpu.shutdown()


@pytest.fixture()
def rt_tcp():
    ray_tpu.shutdown()
    r = ray_tpu.init(num_cpus=2, listen="127.0.0.1:0")
    yield r
    ray_tpu.shutdown()


def _start_agent(rt, extra_res, num_cpus=2):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO, os.path.dirname(os.path.abspath(__file__)),
         *env.get("PYTHONPATH", "").split(os.pathsep)])
    from ray_tpu.util.jaxenv import subprocess_env_cpu
    subprocess_env_cpu(env)
    before = set(rt.cluster_nodes)
    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu.core.node", rt.tcp_address,
         "--num-cpus", str(num_cpus),
         "--resources", json.dumps(extra_res)],
        env=env, cwd=REPO)
    deadline = time.time() + 30
    while time.time() < deadline and len(rt.cluster_nodes) == len(before):
        time.sleep(0.05)
    new = set(rt.cluster_nodes) - before
    assert new, "agent failed to register"
    return proc, new.pop()


@ray_tpu.remote
def _noop(i=0):
    return i


@ray_tpu.remote
def _blocked_get(box):
    # box is a LIST holding a ref (not a top-level dep): this task
    # starts immediately and blocks inside get()
    return ray_tpu.get(box[0], timeout=60)


@ray_tpu.remote
def _sleep_then(v, sec):
    time.sleep(sec)
    return v


# ---------------- batching / leases ----------------

def test_fanout_coalesces_submits_and_dispatches(rt):
    ray_tpu.get([_noop.remote(i) for i in range(32)], timeout=60)  # warm
    sb0, dt0, df0, lg0 = (rt.submit_batches, rt.dispatched_tasks,
                          rt.dispatch_frames, rt.lease_grants)
    n = 256
    vals = ray_tpu.get([_noop.remote(i) for i in range(n)], timeout=120)
    assert vals == list(range(n))
    assert rt.submit_batches > sb0
    assert rt.batched_submits >= n
    # message amplification: far fewer dispatch frames than tasks
    frames = rt.dispatch_frames - df0
    tasks = rt.dispatched_tasks - dt0
    assert tasks >= n
    assert frames <= tasks / 4, (frames, tasks)
    assert rt.lease_grants > lg0
    s = state_mod.dispatch_summary()
    assert s["batching_enabled"] and s["lease_grants"] >= rt.lease_grants - lg0
    assert s["submit_batches"] >= 1


def test_lease_results_preserve_order_and_values(rt):
    # leased slots execute FIFO on one worker; results must map back to
    # the right refs regardless of batching
    refs = [_noop.remote(i * 7) for i in range(100)]
    assert ray_tpu.get(refs, timeout=60) == [i * 7 for i in range(100)]


@ray_tpu.remote
def _held_until(gate, v):
    """Returns once the driver has made the file `gate` (at most a
    minute): a chain that is unfinished for as long as the test says."""
    deadline = time.time() + 60
    while not os.path.exists(gate) and time.time() < deadline:
        time.sleep(0.02)
    return v


def test_blocked_lease_head_releases_slots(rt, tmp_path):
    """A lease head blocking in get() must not pin unstarted slots
    behind it: the driver reclaims them (task.lease.revoke) and other
    workers (or fresh spawns) run them."""
    gate = str(tmp_path / "gate")
    slow = _held_until.remote(gate, "s")
    time.sleep(0.3)   # let the sleeper occupy one worker
    rev0 = rt.lease_revokes
    # blocker waits on the sleeper via a NESTED ref (not a dep), then a
    # quick task lands behind it in the same submit burst
    blocker = _blocked_get.remote([slow])
    quick = [_noop.remote(i) for i in range(6)]
    try:
        # the quick tasks must NOT wait for the sleeper's chain, which
        # ends only when the gate opens below: pinned behind the
        # blocker they would not come back at all
        assert ray_tpu.get(quick, timeout=30) == list(range(6))
        ready, _ = ray_tpu.wait([blocker], timeout=0)
        assert not ready, "the blocked head finished before its gate"
        assert rt.lease_revokes > rev0, \
            "get()-blocked lease head kept its unstarted slots pinned"
    finally:
        open(gate, "w").close()
    assert ray_tpu.get(blocker, timeout=30) == "s"


@ray_tpu.remote
def _blocked_wait(box):
    ready, _ = ray_tpu.wait(box, num_returns=len(box), timeout=30)
    return sorted(ray_tpu.get(ready, timeout=30))


def test_blocked_lease_head_in_wait_releases_slots(rt):
    """Same reclaim contract for a head parking in ray_tpu.wait() as
    for get(): the unstarted slots leased behind it are revoked and
    re-queued for other capacity (wait() does not lend CPU — a
    pre-existing semantic — so unlike the get() case the quicks may
    still queue for a slot; the guarantee under test is that they are
    UNPINNED from the parked worker's lease, the deadlock ingredient)."""
    slow = _sleep_then.remote("s", 2.0)
    time.sleep(0.3)
    rev0 = rt.lease_revokes
    # one submit burst: the waiter leads a lease, quicks ride behind it
    waiter = _blocked_wait.remote([slow])
    quick = [_noop.remote(i) for i in range(6)]
    deadline = time.time() + 10
    while time.time() < deadline and rt.lease_revokes == rev0:
        time.sleep(0.05)
    assert rt.lease_revokes > rev0, \
        "wait()-parked lease head kept its unstarted slots pinned"
    assert ray_tpu.get(quick, timeout=30) == list(range(6))
    assert ray_tpu.get(waiter, timeout=30) == ["s"]


def test_gang_tasks_escape_shared_lease(rt):
    """Two tasks that rendezvous with EACH OTHER (collective allreduce:
    a user-space polling loop, never a driver-visible blocking verb)
    can land in one serial lease when submitted in a burst — the lease
    progress watchdog must reclaim the pinned peer so the gang
    completes instead of spinning to its rendezvous timeout."""
    import numpy as np

    @ray_tpu.remote
    def rank_task(rank):
        from ray_tpu.util.collective import init_collective_group
        g = init_collective_group(2, rank, "dispatchgang")
        out = g.allreduce(np.array([float(rank + 1)]))
        return float(out[0])

    refs = [rank_task.remote(0), rank_task.remote(1)] \
        + [_noop.remote(i) for i in range(6)]
    vals = ray_tpu.get(refs, timeout=60)
    assert vals[0] == vals[1] == 3.0
    assert vals[2:] == list(range(6))


def test_legacy_kill_switch_roundtrip():
    ray_tpu.shutdown()
    os.environ["RAY_TPU_BATCH"] = "0"
    try:
        rt = ray_tpu.init(num_cpus=2)
        assert rt._lease_cap == 1 and rt._actor_pipeline == 0
        vals = ray_tpu.get([_noop.remote(i) for i in range(20)],
                           timeout=60)
        assert vals == list(range(20))
        assert rt.submit_batches == 0      # legacy per-message path
        assert rt.lease_grants == 0
    finally:
        os.environ.pop("RAY_TPU_BATCH", None)
        ray_tpu.shutdown()


def test_gang_collective_liveness_at_capacity():
    """A polling rendezvous gang on a capacity-tight cluster: the second
    round leaves only ONE free CPU for a 2-rank gang (the rendezvous
    actor and a bystander actor hold the rest), so liveness depends on
    the parked rank lending its slot back to the scheduler. The
    collective pins its blocking verbs to the driver path
    (force_driver_path) for exactly this — each fast direct-call poll
    resolves inside the dwait grace window and would never lend,
    starving the unscheduled rank until the round timed out."""
    ray_tpu.shutdown()
    try:
        ray_tpu.init(num_cpus=3)

        @ray_tpu.remote
        class _Holder:
            def ping(self):
                return 1

        h = _Holder.remote()
        assert ray_tpu.get(h.ping.remote(), timeout=30) == 1  # 1 CPU held

        @ray_tpu.remote
        def rank_fn(rank, world, val):
            import numpy as np
            from ray_tpu.util.collective import init_collective_group
            g = init_collective_group(world, rank, "capgang")
            out = g.allreduce(np.array([val]), op="sum", timeout=30)
            return float(out[0])

        # warm round also creates the rendezvous actor (2nd held CPU)
        r1 = ray_tpu.get([rank_fn.remote(r, 2, 1.0) for r in range(2)],
                         timeout=60)
        assert r1 == [2.0, 2.0]
        # fresh-epoch round with 1 free CPU: rank 0 must lend while it
        # polls so rank 1 can schedule at all
        r2 = ray_tpu.get([rank_fn.remote(r, 2, 2.0) for r in range(2)],
                         timeout=60)
        assert r2 == [4.0, 4.0]
    finally:
        ray_tpu.shutdown()


# ---------------- pipelined actor dispatch ----------------

def test_actor_pipeline_serializes_and_orders(rt):
    @ray_tpu.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def bump(self):
            self.n += 1
            return self.n

    c = Counter.remote()
    vals = ray_tpu.get([c.bump.remote() for _ in range(64)], timeout=60)
    # max_concurrency=1 execution order survives pipelined dispatch
    assert vals == list(range(1, 65))


def test_async_actor_concurrency_enforced_in_worker(rt):
    """Pipelined dispatch sends past max_concurrency on purpose; for
    async actors the execution bound lives in the worker's lane
    semaphores now — overlap must still be capped."""
    @ray_tpu.remote(max_concurrency=2)
    class Gauge:
        def __init__(self):
            self.cur = 0
            self.peak = 0

        async def work(self):
            import asyncio
            self.cur += 1
            self.peak = max(self.peak, self.cur)
            await asyncio.sleep(0.05)
            self.cur -= 1
            return self.peak

        async def peak_seen(self):
            return self.peak

    g = Gauge.remote()
    ray_tpu.get([g.work.remote() for _ in range(12)], timeout=60)
    assert ray_tpu.get(g.peak_seen.remote(), timeout=30) <= 2


# ---------------- chaos: agent death mid-lease ----------------

def test_agent_death_mid_lease_zero_lost_tasks():
    """Kill a node agent whose worker holds an active multi-slot lease:
    the lease revokes, unstarted slots re-queue WITHOUT burning a
    retry, the head retries on its budget, and every task finishes once
    capacity returns — the task.lease.grant -> task.lease.revoke ->
    task.retry -> task.finish chain with zero lost tasks.

    Pinned to the per-worker lease path (RAY_TPU_NODE_LEASES=0): with
    two-level scheduling on, these tasks would ride a bulk NODE lease
    instead — that path's death chain is covered by
    test_agent_death_mid_bulk_node_lease_zero_lost."""
    ray_tpu.shutdown()
    os.environ["RAY_TPU_NODE_LEASES"] = "0"
    try:
        rt = ray_tpu.init(num_cpus=2, listen="127.0.0.1:0")
        _agent_death_mid_lease_body(rt)
    finally:
        os.environ.pop("RAY_TPU_NODE_LEASES", None)
        ray_tpu.shutdown()


def _agent_death_mid_lease_body(rt):
    proc, nid = _start_agent(rt, {"doomed": 4.0}, num_cpus=1)

    @ray_tpu.remote(resources={"doomed": 1}, max_retries=2)
    def held(i, sec=0.0):
        time.sleep(sec)
        return i

    # head sleeps past the kill window; followers ride the same lease
    # (same shape)
    refs = [held.remote(0, 4.0)] + [held.remote(i) for i in range(1, 6)]
    deadline = time.time() + 30
    while time.time() < deadline and rt.lease_grants == 0:
        time.sleep(0.05)
    assert rt.lease_grants >= 1, "no lease granted on the doomed node"
    time.sleep(0.3)
    proc.kill()
    # replacement capacity for the retried tasks
    proc2, _nid2 = _start_agent(rt, {"doomed": 4.0}, num_cpus=1)
    try:
        vals = ray_tpu.get(refs, timeout=120)
        assert vals == [0, 1, 2, 3, 4, 5]     # zero lost tasks
        assert rt.lease_revokes >= 1
        evs = state_mod.list_events(limit=10_000)
        types = {e["type"] for e in evs}
        for need in ("task.lease.grant", "task.lease.revoke",
                     "task.retry", "task.finish"):
            assert need in types, (need, sorted(types))
        # chain order: grant before revoke before a retry before the
        # last finish
        seq = [e["type"] for e in evs]
        assert seq.index("task.lease.grant") \
            < seq.index("task.lease.revoke") \
            < (len(seq) - 1 - seq[::-1].index("task.finish"))
    finally:
        proc2.kill()


# ---------------- two-level scheduling: bulk node leases ----------------

NLEASE_MSG_KINDS = TASK_MSG_KINDS + (
    "nlease_done", "nlease_spill", "nlease_want", "nlease_release")


@ray_tpu.remote(resources={"agent": 0.001})
def _agent_noop(i):
    return i


@ray_tpu.remote(resources={"agent": 0.001})
def _agent_fan(n):
    return sum(ray_tpu.get([_agent_noop.remote(i) for i in range(n)],
                           timeout=60))


def test_bulk_node_lease_fanout(rt_tcp):
    """A same-shape fan-out rides NODE-level bulk leases: the driver
    hands the agent whole batches (grant + refill extends) instead of
    per-worker lease frames, and the agent's local fan-out streams
    coalesced completions back."""
    rt = rt_tcp
    proc, nid = _start_agent(rt, {"agent": 4.0}, num_cpus=2)
    try:
        assert ray_tpu.get([_agent_noop.remote(i) for i in range(8)],
                           timeout=60) == list(range(8))  # warm: spawns
        g0, t0 = rt.node_lease_grants, rt.node_lease_tasks
        n = 128
        vals = ray_tpu.get([_agent_noop.remote(i) for i in range(n)],
                           timeout=120)
        assert vals == list(range(n))
        assert rt.node_lease_grants + rt.node_lease_extends > 0
        assert rt.node_lease_tasks - t0 >= n, rt.node_lease_tasks
        assert not rt.node_leases, "leases must settle after the drain"
        s = state_mod.dispatch_summary()
        assert s["node_leases_enabled"]
        assert s["node_lease_tasks"] >= n
        evs = state_mod.list_events(limit=10_000)
        assert "task.lease.node_grant" in {e["type"] for e in evs}
        assert g0 == 0 or True  # grants counted from the warm round on
    finally:
        proc.kill()


def test_node_lease_kill_switch():
    """RAY_TPU_NODE_LEASES=0 falls back to the per-worker lease path:
    same results, zero node-lease grants."""
    ray_tpu.shutdown()
    os.environ["RAY_TPU_NODE_LEASES"] = "0"
    try:
        rt = ray_tpu.init(num_cpus=2, listen="127.0.0.1:0")
        proc, _nid = _start_agent(rt, {"agent": 4.0}, num_cpus=2)
        try:
            vals = ray_tpu.get(
                [_agent_noop.remote(i) for i in range(32)], timeout=120)
            assert vals == list(range(32))
            assert rt.node_lease_grants == 0
            assert rt.lease_grants > 0   # per-worker path took over
        finally:
            proc.kill()
    finally:
        os.environ.pop("RAY_TPU_NODE_LEASES", None)
        ray_tpu.shutdown()


def test_agent_death_mid_bulk_node_lease_zero_lost(rt_tcp):
    """SIGKILL a node agent holding a bulk lease mid-fan-out: the
    driver revokes the lease (task.lease.revoke), charges a retry to
    the one possibly-STARTED slot (the lease has one worker, so only
    the oldest outstanding task can be executing) and re-pends every
    unstarted slot WITHOUT burning a retry — the batch completes on
    replacement capacity with zero lost tasks and no double-settled
    results."""
    rt = rt_tcp
    proc, nid = _start_agent(rt, {"doomed2": 4.0}, num_cpus=1)

    @ray_tpu.remote(resources={"doomed2": 1}, max_retries=0)
    def held(i, sec=0.0):
        time.sleep(sec)
        return i

    # head occupies the lease's worker (STARTED when the agent dies,
    # so it needs a retry budget); followers queue agent-side at
    # max_retries=0 — their completion proves unstarted slots re-pend
    # for free
    refs = [held.options(max_retries=1).remote(0, 3.0)] \
        + [held.remote(i) for i in range(1, 8)]
    deadline = time.time() + 30
    while time.time() < deadline and rt.node_lease_grants == 0:
        time.sleep(0.05)
    assert rt.node_lease_grants >= 1, "no bulk lease granted"
    time.sleep(0.3)
    rev0 = rt.lease_revokes
    proc.kill()
    proc2, _nid2 = _start_agent(rt, {"doomed2": 4.0}, num_cpus=1)
    try:
        # followers at max_retries=0: their completion PROVES the
        # revoke path re-pended unstarted slots without burning
        # retries; the head completes on its one-retry budget (it
        # never produced a result, so its re-run cannot double-settle)
        vals = ray_tpu.get(refs, timeout=120)
        assert vals == list(range(8)), vals
        assert rt.lease_revokes > rev0
        evs = state_mod.list_events(limit=10_000)
        types = {e["type"] for e in evs}
        for need in ("task.lease.node_grant", "task.lease.revoke",
                     "task.finish"):
            assert need in types, (need, sorted(types))
        seq = [e["type"] for e in evs]
        assert seq.index("task.lease.node_grant") \
            < seq.index("task.lease.revoke") \
            < (len(seq) - 1 - seq[::-1].index("task.finish"))
    finally:
        proc2.kill()


def test_nested_fanout_zero_driver_frames(rt_tcp):
    """Steady-state nested fan-out from a remote worker submits to its
    OWN node agent: with standing capacity established, the inner
    tasks touch the driver ZERO times — no submit, no task_done, no
    spillback (the PR-13 ctrl_msgs-delta style assertion)."""
    rt = rt_tcp
    proc, nid = _start_agent(rt, {"agent": 4.0}, num_cpus=3)
    try:
        # warm rounds: spawn workers, establish the standing lease for
        # the nested shape (same size as the measured round so no
        # fresh capacity request fires mid-measurement)
        for _ in range(3):
            assert ray_tpu.get(_agent_fan.remote(20),
                               timeout=60) == sum(range(20))
        time.sleep(0.3)
        before = {k: rt.ctrl_msgs.get(k, 0) for k in NLEASE_MSG_KINDS}
        assert ray_tpu.get(_agent_fan.remote(20),
                           timeout=60) == sum(range(20))
        delta = {k: rt.ctrl_msgs.get(k, 0) - before[k]
                 for k in NLEASE_MSG_KINDS}
        # the inner 20 tasks must produce NO driver traffic: zero
        # forwarded submits, zero spillbacks; the only frames allowed
        # belong to the outer task itself (its completion, plus at
        # most one standing-capacity re-request)
        assert delta["submit"] == 0, delta
        assert delta["submit_many"] == 0, delta
        assert delta["task_done"] == 0, delta
        assert delta["nlease_spill"] == 0, delta
        assert sum(delta.values()) <= 3, delta
    finally:
        proc.kill()


# ---------------- driver-bypass actor calls ----------------

@ray_tpu.remote
class _Echo:
    def ping(self, x):
        return x + 1


@ray_tpu.remote
class _Caller:
    def __init__(self, echo):
        self.echo = echo

    def run(self, n):
        return sum(ray_tpu.get(self.echo.ping.remote(i), timeout=30)
                   for i in range(n))

    def fanout(self, n):
        return sum(ray_tpu.get(
            [self.echo.ping.remote(i) for i in range(n)], timeout=60))

    def escape(self, i):
        return self.echo.ping.remote(i)


def test_actor_to_actor_zero_driver_messages(rt):
    """Steady-state A2A calls must produce ZERO driver control messages
    per call (the PR-2 relay_bytes == 0 analogue, asserted through the
    driver's per-kind message counters)."""
    echo = _Echo.remote()
    caller = _Caller.remote(echo)
    assert ray_tpu.get(caller.run.remote(3), timeout=60) == 6  # warm
    before = {k: rt.ctrl_msgs.get(k, 0) for k in TASK_MSG_KINDS}
    n = 200
    total = ray_tpu.get(caller.run.remote(n), timeout=120)
    assert total == sum(i + 1 for i in range(n))
    delta = {k: rt.ctrl_msgs.get(k, 0) - before[k]
             for k in TASK_MSG_KINDS}
    # only the caller.run() call itself may touch the driver
    assert sum(delta.values()) <= 6, delta
    # worker-side counters ship on the 1s telemetry heartbeat
    deadline = time.time() + 10
    seen = 0
    while time.time() < deadline:
        seen = state_mod.dispatch_summary().get("direct_actor_calls", 0)
        if seen >= n:
            break
        time.sleep(0.2)
    assert seen >= n, seen


def test_direct_call_fanout_and_escaped_ref(rt):
    echo = _Echo.remote()
    caller = _Caller.remote(echo)
    assert ray_tpu.get(caller.fanout.remote(50), timeout=60) == \
        sum(i + 1 for i in range(50))
    # a direct-call ref escaping to the driver must publish its value
    ref = ray_tpu.get(caller.escape.remote(41), timeout=30)
    assert ray_tpu.get(ref, timeout=30) == 42


@ray_tpu.remote
def _consume_boxed(box):
    return ray_tpu.get(box[0], timeout=30) + 1


@ray_tpu.remote
def _escape_resolved_ref(echo):
    # plain-task caller (lends its CPU while parked, so the nested task
    # can schedule on the 2-CPU fixture); the direct-call ref is
    # RESOLVED before it escapes into the nested spec
    ref = echo.ping.remote(6)
    assert ray_tpu.get(ref, timeout=30) == 7
    nested = _consume_boxed.remote([ref])
    return ray_tpu.get(nested, timeout=30)


def test_resolved_direct_ref_escapes_via_nested_submit(rt):
    """A RESOLVED direct-call result ref serialized into a nested
    task's spec pickles at frame-encode time, i.e. INSIDE the batcher's
    flush: the escape publication must go straight to the socket — a
    batched urgent send would re-enter the flush lock on the same
    thread and wedge the worker's outbound plane permanently."""
    echo = _Echo.remote()
    assert ray_tpu.get(_escape_resolved_ref.remote(echo), timeout=60) == 8


def test_inflight_direct_call_fails_over_to_driver_path(rt):
    """Kill the callee with a direct call in flight: the channel dies,
    the spec fails over to the driver path, and the driver's actor
    semantics surface (ActorDiedError with the death cause)."""
    @ray_tpu.remote
    class Victim:
        def slow(self):
            time.sleep(30)
            return "done"

        def quick(self):
            return "q"

    @ray_tpu.remote
    class C2:
        def __init__(self, victim):
            self.victim = victim

        def call_slow(self):
            try:
                return ray_tpu.get(self.victim.slow.remote(), timeout=60)
            except ActorDiedError as e:
                return f"ActorDiedError:{e}"

    v = Victim.remote()
    assert ray_tpu.get(v.quick.remote(), timeout=30) == "q"
    c = C2.remote(v)
    fut = c.call_slow.remote()
    time.sleep(1.5)    # the direct call is in flight on the channel
    ray_tpu.kill(v)
    out = ray_tpu.get(fut, timeout=60)
    assert out.startswith("ActorDiedError"), out


def test_direct_calls_kill_switch():
    ray_tpu.shutdown()
    os.environ["RAY_TPU_DIRECT_CALLS"] = "0"
    try:
        ray_tpu.init(num_cpus=2)
        echo = _Echo.remote()
        caller = _Caller.remote(echo)
        rt = ray_tpu.init()
        before = rt.ctrl_msgs.get("submit", 0)
        assert ray_tpu.get(caller.run.remote(10), timeout=60) == \
            sum(i + 1 for i in range(10))
        # every call went through the driver
        assert rt.ctrl_msgs.get("submit", 0) - before >= 10
    finally:
        os.environ.pop("RAY_TPU_DIRECT_CALLS", None)
        ray_tpu.shutdown()
