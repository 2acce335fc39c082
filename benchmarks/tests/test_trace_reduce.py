"""The reduction from a trace to numbers: interval arithmetic on made-up
intervals, and the whole reduction on a small recorded `.xplane.pb`
(recorded on the CPU backend, where the XLA client's thread stands in
for a device's operation line: the arithmetic is the same)."""
import os

import pytest

from benchmarks.harness import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "cpu_small.xplane.pb")


def test_union_total_gaps():
    busy = tr.union([(0.0, 1.0), (0.5, 2.0), (3.0, 4.0), (3.2, 3.4)])
    assert busy == [(0.0, 2.0), (3.0, 4.0)]
    assert tr.total(busy) == pytest.approx(3.0)
    assert tr.gaps(busy) == [(2.0, 3.0)]


def test_collective_time_not_hidden_behind_compute():
    coll = tr.union([(0.0, 2.0), (5.0, 6.0)])
    comp = tr.union([(1.0, 3.0), (5.2, 5.4)])
    exposed = tr.subtract(coll, comp)
    assert exposed == [(0.0, 1.0), (5.0, 5.2), (5.4, 6.0)]
    assert tr.total(exposed) == pytest.approx(1.8)


def test_gaps_take_the_name_of_the_innermost_host_event():
    idle = [(2.0, 3.0), (5.0, 5.00001), (8.0, 9.0)]
    host = [(1.5, 3.5, "engine-step"), (2.2, 2.8, "fetch-tokens")]
    named = tr.name_gaps(idle, host, "unattributed")
    assert named["fetch-tokens"] == pytest.approx(1.0)
    assert named["unattributed"] == pytest.approx(1.0)
    assert named["between-ops-under-20us"] == pytest.approx(1e-5)


def test_category_strips_instance_numbers():
    assert tr.category("fusion.123") == "fusion"
    assert tr.category("%all-gather-start.4") == "all-gather-start"
    assert tr.category("attention") == "attention"


def test_recorded_trace_reduces_to_consistent_numbers():
    from jax.profiler import ProfileData
    out = tr.reduce_file(DATA, device_plane_re=r"^/host:CPU$",
                         ops_line="tf_XLAPjRtCpuClient",
                         modules_line="python")
    assert out["devices"] == 1
    assert 0 < out["busy_s"] <= out["window_s"]
    plane = next(p for p in ProfileData.from_file(DATA).planes
                 if p.name == "/host:CPU")
    line = next(ln for ln in plane.lines
                if ln.name.startswith("tf_XLAPjRtCpuClient"))
    evs = list(line.events)
    assert sum(out["ops"].values()) == pytest.approx(
        sum(e.duration_ns for e in evs) * 1e-9)
    # nested events overlap: the union is what the device was busy
    assert out["busy_s"] <= sum(out["ops"].values()) + 1e-12
    py = next(ln for ln in plane.lines if ln.name == "python")
    calls = [e for e in py.events if e.name.startswith("PjitFunction(")]
    assert out["modules"]["PjitFunction"]["count"] == len(calls) >= 5
    assert len(out["device_ops"]) <= 10 and len(out["idle_gaps"]) <= 10
    idle = out["window_s"] - out["busy_s"]
    assert sum(s for _n, s in out["idle_gaps"]) <= idle + 1e-9


def test_a_trace_without_device_planes_says_so():
    out = tr.reduce_file(DATA)
    assert out["devices"] == 0 and "/host:CPU" in out["planes"]
