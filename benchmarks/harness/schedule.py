"""The one traffic generator: a traffic file of parameters in, a schedule out.

A schedule is a list of requests, each with the time it is due (seconds
from the start of traffic), a prompt length, an output length and its
prompt's token ids. The traffic file fixes *how much work arrives when*:
lengths and inter-arrival gaps are taken at evenly spaced quantiles of the
stated distributions, one fixed multiset per block of `block` arrivals, and
bursts come at times and sizes the file states. `--seed` only permutes the
lengths and gaps inside each block and draws the token ids. Two seeds
therefore offer the same requests, prompt tokens and output tokens in every
block, and every block spans the same time.

Traffic starts `ramp_s` seconds before the measured window opens, so the
window opens at schedule time `ramp_s` and closes at `ramp_s + seconds`.
"""
from __future__ import annotations

import dataclasses
import json
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    idx: int
    due_s: float          # from the start of traffic
    prompt_len: int
    max_tokens: int
    kind: str             # "steady" or "burst"

    def prompt(self, seed: int, vocab: int) -> np.ndarray:
        """Token ids of this request's prompt: a function of (seed, idx)
        alone, so that the order of generation cannot change them. Id 0
        is left out (padding rows of a prefill are zeros)."""
        rng = np.random.default_rng([int(seed), 7, self.idx])
        return rng.integers(1, vocab, self.prompt_len, dtype=np.int64)


def quantile_values(dist: dict, n: int) -> List[float]:
    """The distribution's values at the n evenly spaced quantiles
    (i + 0.5) / n: the fixed multiset one block draws from."""
    qs = [(i + 0.5) / n for i in range(n)]
    kind = dist["dist"]
    if kind == "lognormal":
        mu, sigma = math.log(dist["median"]), dist["sigma"]
        vals = [math.exp(mu + sigma * NormalDist().inv_cdf(q)) for q in qs]
    elif kind == "uniform":
        vals = [dist["min"] + q * (dist["max"] - dist["min"]) for q in qs]
    elif kind == "exponential":
        vals = [-math.log(1.0 - q) for q in qs]        # mean ~1, rescaled
    elif kind == "constant":
        vals = [float(dist["value"])] * n
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    if "min" in dist:
        vals = [max(v, dist["min"]) for v in vals]
    if "max" in dist:
        vals = [min(v, dist["max"]) for v in vals]
    return vals


def length_multiset(dist: dict, n: int) -> List[int]:
    return [int(round(v)) for v in quantile_values(dist, n)]


def gap_multiset(dist: dict, n: int, rate_rps: float) -> List[float]:
    """n gaps whose sum is exactly n / rate: every block lasts the same."""
    raw = quantile_values(dist, n)
    scale = (n / rate_rps) / sum(raw)
    return [g * scale for g in raw]


def steady_rate(traffic: dict, rate_rps: float | None = None) -> float:
    """Rate of the steady arrivals: the mean rate less the bursts' share."""
    rate = traffic["rate_rps"] if rate_rps is None else rate_rps
    b = traffic.get("bursts")
    if b:
        rate -= b["size"] / b["period_s"]
    if rate <= 0:
        raise ValueError("the bursts alone exceed the mean rate")
    return rate


def build(traffic: dict, seed: int, seconds: float,
          rate_rps: float | None = None) -> List[Request]:
    """Requests due in [0, ramp_s + seconds), sorted by due time."""
    block = int(traffic.get("block", 32))
    horizon = traffic["ramp_s"] + seconds
    rate = steady_rate(traffic, rate_rps)
    prompts = length_multiset(traffic["prompt_len"], block)
    outputs = length_multiset(traffic["output_len"], block)
    gaps = gap_multiset(traffic.get("gaps", {"dist": "exponential"}),
                        block, rate)
    out: List[tuple] = []
    t, b = 0.0, 0
    while t < horizon:
        rng = np.random.default_rng([int(seed), 1, b])
        # one permutation for each of the three: which request carries
        # which prompt, which answer, and which gap comes first
        pp, po, pg = (rng.permutation(block) for _ in range(3))
        for i in range(block):
            t += gaps[pg[i]]
            if t >= horizon:
                break
            out.append((t, prompts[pp[i]], outputs[po[i]], "steady"))
        b += 1
    bursts = traffic.get("bursts")
    if bursts:
        size = int(bursts["size"])
        bp = length_multiset(traffic["prompt_len"], size)
        bo = length_multiset(traffic["output_len"], size)
        # burst k is due at ramp_s + offset + k * period: fixed against
        # the window, so every window holds the same bursts
        k = -int((traffic["ramp_s"] + bursts["offset_s"])
                 // bursts["period_s"])
        while True:
            t0 = traffic["ramp_s"] + bursts["offset_s"] \
                + k * bursts["period_s"]
            if t0 + bursts["spread_s"] >= horizon:
                break
            if t0 >= 0:
                rng = np.random.default_rng([int(seed), 2, k + 10_000])
                pp, po = rng.permutation(size), rng.permutation(size)
                for i in range(size):
                    out.append((t0 + bursts["spread_s"] * i / size,
                                bp[pp[i]], bo[po[i]], "burst"))
            k += 1
    out.sort(key=lambda r: r[0])
    return [Request(i, *r) for i, r in enumerate(out)]


def serialize(reqs: List[Request], seed: int, vocab: int) -> bytes:
    """The schedule as bytes, token ids included (the same seed must
    give the same bytes)."""
    return json.dumps([
        [r.idx, round(r.due_s, 9), r.prompt_len, r.max_tokens, r.kind,
         r.prompt(seed, vocab).tolist()] for r in reqs]).encode()


def offered(reqs: List[Request], every_s: float = 5.0) -> List[dict]:
    """Requests, prompt tokens and output tokens offered in each
    `every_s` seconds of the schedule."""
    bins: dict = {}
    for r in reqs:
        b = bins.setdefault(int(r.due_s // every_s),
                            {"requests": 0, "prompt": 0, "output": 0})
        b["requests"] += 1
        b["prompt"] += r.prompt_len
        b["output"] += r.max_tokens
    return [dict(bins[k], t_s=k * every_s) for k in sorted(bins)]
