"""The reader of window deltas of `get_stats()`: sums over paths, and
None, never a raise, where the program has no such span or counter."""
import json
import os
import sys

import pytest

from benchmarks import run as runmod

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def stats(discarded, emitted, prefill, emit, wait, steps):
    return {"decode_tokens_discarded": discarded,
            "decode_tokens_emitted": emitted, "decode_steps": steps,
            "spans": {"request.inflight_prefill": list(prefill),
                      "engine.emit": list(emit),
                      "engine.drain_wait": list(wait)}}


RUN = {"stats0": stats(10, 90, (4, 8_000_000, 3_000_000),
                       (5, 1_000_000, 1), (5, 4_000_000, 1), 100),
       "stats1": stats(40, 360, (10, 1_208_000_000, 300_000_000),
                       (25, 7_000_000, 1), (25, 28_000_000, 1), 400)}


def read(run, **args):
    readers = os.path.join(BENCH, "readers")    # where run.py finds them
    if readers not in sys.path:
        sys.path.insert(0, readers)
    import stats_delta
    return stats_delta.read(run, **args)


def test_a_ratio_of_sums_of_window_deltas():
    share = read(RUN, num=[["decode_tokens_discarded"]],
                 den=[["decode_tokens_discarded"],
                      ["decode_tokens_emitted"]], scale=100.0)
    assert share == pytest.approx(100.0 * 30 / (30 + 270))
    lag = read(RUN, num=[["spans", "request.inflight_prefill", 1]],
               den=[["spans", "request.inflight_prefill", 0]], scale=1e-6)
    assert lag == pytest.approx(200.0)             # 1 200 ms over 6 requests
    host = read(RUN, num=[["spans", "engine.emit", 1],
                          ["spans", "engine.drain_wait", 1]],
                den=[["decode_steps"]], scale=1e-6)
    assert host == pytest.approx(30.0 / 300)


def test_complement_is_one_minus_the_ratio():
    pad = read(RUN, num=[["decode_tokens_emitted"]],
               den=[["decode_tokens_discarded"],
                    ["decode_tokens_emitted"]], scale=100.0, complement=True)
    assert pad == pytest.approx(10.0)


@pytest.mark.parametrize("path", [
    ["no_such_counter"], ["spans", "no.such.span", 1],
    ["spans", "engine.emit", 7], ["spans", "engine.emit", "total"],
    ["decode_steps", "deeper"], ["spans"]])
def test_a_missing_key_reads_none(path):
    assert read(RUN, num=[path], den=[["decode_steps"]]) is None
    assert read(RUN, num=[["decode_steps"]], den=[path]) is None


def test_a_parent_without_the_counters_reads_none():
    old = {"stats0": {"decode_steps": 1, "tokens_generated": 5},
           "stats1": {"decode_steps": 9, "tokens_generated": 50}}
    assert read(old, num=[["decode_tokens_discarded"]],
                den=[["decode_steps"]]) is None
    assert read({}, num=[["decode_steps"]], den=[["decode_steps"]]) is None


def test_a_denominator_that_did_not_grow_reads_none():
    still = dict(RUN, stats1=RUN["stats0"])
    assert read(still, num=[["decode_tokens_discarded"]],
                den=[["decode_steps"]]) is None
    assert read(RUN, num=[["decode_steps"]], den=[]) is None


def test_every_metric_file_of_the_reader_names_paths_the_engine_seeds():
    """The files' paths against the keys a fresh engine reports: a span
    name misspelt in a metric file would read None for ever."""
    from ray_tpu.serve.llm import engine
    seeded = set(engine._LOOP_SPANS + engine._REQUEST_SPANS)
    found = 0
    for name in sorted(os.listdir(os.path.join(BENCH, "metrics"))):
        with open(os.path.join(BENCH, "metrics", name)) as f:
            spec = json.load(f)
        if spec["reader"] != "stats_delta":
            continue
        found += 1
        for path in spec["args"]["num"] + spec["args"]["den"]:
            if path[0] == "spans":
                assert path[1] in seeded and path[2] in (0, 1), (name, path)
            else:
                assert len(path) == 1 and path[0].startswith(
                    ("decode_", "prefill_")), (name, path)
        assert runmod.read_metric(BENCH, name[:-5], RUN) is None or \
            isinstance(runmod.read_metric(BENCH, name[:-5], RUN), float)
    assert found == 8
