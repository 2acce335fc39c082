"""The seed may move which request carries which length, never how much
work arrives when."""
import json
import os
from collections import Counter

import pytest

from benchmarks.harness import schedule

TRAFFIC = os.path.join(os.path.dirname(__file__), "..", "traffic")
MIXES = ["decode_sat", "short_burst"]
BIG_SEED = 2 ** 31 + 12345


def mix(name):
    with open(os.path.join(TRAFFIC, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_bytes(name):
    t = mix(name)
    a = schedule.serialize(schedule.build(t, BIG_SEED, 20), BIG_SEED, 32768)
    b = schedule.serialize(schedule.build(t, BIG_SEED, 20), BIG_SEED, 32768)
    assert a == b
    c = schedule.serialize(schedule.build(t, 7, 20), 7, 32768)
    assert a != c


@pytest.mark.parametrize("name", MIXES)
def test_two_seeds_offer_the_same_work_in_every_block(name):
    t = mix(name)
    block = t["block"]
    runs = [schedule.build(t, s, 51) for s in (1, BIG_SEED)]
    steady = [[r for r in run if r.kind == "steady"] for run in runs]
    # the horizon cuts the last block, whose order the seed chose: only
    # whole blocks are the same work
    assert abs(len(steady[0]) - len(steady[1])) < block
    n_blocks = min(len(s) for s in steady) // block
    assert n_blocks >= 3
    for b in range(n_blocks):
        x, y = (s[b * block:(b + 1) * block] for s in steady)
        assert Counter(r.prompt_len for r in x) == \
            Counter(r.prompt_len for r in y)
        assert Counter(r.max_tokens for r in x) == \
            Counter(r.max_tokens for r in y)
        # every block ends at the same time: the gaps are one multiset
        assert x[-1].due_s == pytest.approx(y[-1].due_s, abs=1e-9)
        assert x[-1].due_s == pytest.approx(
            (b + 1) * block / schedule.steady_rate(t), abs=1e-6)
    bursts = [[r for r in run if r.kind == "burst"] for run in runs]
    assert [r.due_s for r in bursts[0]] == [r.due_s for r in bursts[1]]
    if t.get("bursts"):
        size = t["bursts"]["size"]
        assert len(bursts[0]) % size == 0 and bursts[0]
        for i in range(0, len(bursts[0]), size):
            assert Counter(r.prompt_len for r in bursts[0][i:i + size]) == \
                Counter(r.prompt_len for r in bursts[1][i:i + size])


@pytest.mark.parametrize("name", MIXES)
def test_offered_tokens_per_5s_agree_within_one_block(name):
    t = mix(name)
    a, b = (schedule.offered(schedule.build(t, s, 51)) for s in (3, 4))
    block_tokens = sum(schedule.length_multiset(t["output_len"], t["block"]))
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert abs(x["output"] - y["output"]) <= block_tokens
    # and over the whole run they are the same to within one block
    assert abs(sum(x["output"] for x in a)
               - sum(y["output"] for y in b)) <= block_tokens


def test_bursts_sit_at_fixed_offsets_from_the_window():
    t = mix("short_burst")
    reqs = schedule.build(t, 5, 51)
    starts = sorted({round(r.due_s - t["ramp_s"], 6) for r in reqs
                     if r.kind == "burst"
                     and abs((r.due_s - t["ramp_s"] - 2.5) % 5.0) < 1e-6})
    in_window = [s for s in starts if 0 <= s < 51]
    assert in_window == [2.5 + 5 * k for k in range(10)]


def test_heavy_tail_is_in_every_block():
    t = mix("decode_sat")
    lens = schedule.length_multiset(t["prompt_len"], 32)
    assert min(lens) == t["prompt_len"]["min"]
    assert max(lens) == t["prompt_len"]["max"]


def test_prompt_tokens_depend_on_seed_and_index_only():
    r = schedule.Request(4, 1.0, 16, 8, "steady")
    assert (r.prompt(9, 1000) == r.prompt(9, 1000)).all()
    assert (r.prompt(9, 1000) != r.prompt(10, 1000)).any()
    assert r.prompt(BIG_SEED, 1000).min() >= 1
