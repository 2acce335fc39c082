"""`engine_runtime_calls_per_step`: the engine thread's calls into the
JAX runtime over the decode steps of the window; nothing, and no raise,
from a program that does not count them."""
import json
import os

import pytest

from benchmarks import run as runmod

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
METRIC = "engine_runtime_calls_per_step"
SERVE_CELLS = ["mistral7b_decode_sat", "mistral7b_short_burst",
               "olmoe7b_decode_sat", "sarvam105b_decode_sat"]


def _run(calls0, calls1, steps0=100, steps1=2_500):
    s0 = {"decode_steps": steps0, "prefill_calls": 20}
    s1 = {"decode_steps": steps1, "prefill_calls": 500}
    if calls0 is not None:
        s0["runtime_calls"], s1["runtime_calls"] = calls0, calls1
    return {"stats0": s0, "stats1": s1}


def test_calls_a_decode_step_inside_the_window():
    """2 400 decode steps and 480 prefill calls, a program and a fetch
    each: 2.4 calls a decode step."""
    run = _run(240, 240 + 2 * (2_400 + 480))
    assert runmod.read_metric(BENCH, METRIC, run) == pytest.approx(2.4)


@pytest.mark.parametrize("run", [
    _run(None, None),                       # a tree before the counter
    _run(240, 6_000, steps0=100, steps1=100),   # no decode step drained
    {"stats0": None, "stats1": None},
], ids=["no_counter", "no_steps", "no_stats"])
def test_nothing_to_read_reads_none(run):
    assert runmod.read_metric(BENCH, METRIC, run) is None


def test_the_manifest_reports_it_in_the_serve_cells_and_only_there():
    manifest = runmod.load_manifest()
    entry, = [m for m in manifest["per_layer"] if m["name"] == METRIC]
    assert entry["workloads"] == SERVE_CELLS
    assert (entry["source"], entry["moves"], entry["better"]) == (
        "program_counter", "out_tok_s", "lower")
    with open(os.path.join(BENCH, "metrics", METRIC + ".json")) as f:
        assert json.load(f) == {"reader": "runtime_calls"}
    for w in manifest["workloads"]:
        names = [m["name"] for m in runmod.cell_metrics(
            manifest, w["name"], "per_layer")]
        assert (METRIC in names) == (w["name"] in SERVE_CELLS)
