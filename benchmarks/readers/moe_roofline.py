"""Roofline shares of a sparse-expert decoder's decode step, from
`costs_moe` against the peaks table.

`what="step"`: the least time of one decode step (dense weights, the
weights of the experts a step touched, every live sequence's cache;
contexts as `decode_roofline` takes them) over the decode program's
measured device time.

`what="experts"`: the least time of the expert layers' grouped matmuls
over their measured device time (operations matching `name_re`), both
per decode step: assignments and experts touched inside the window come
from the engine's counters, which cover prefill calls as well, and so
does the trace's sum over those operations. The least time is the larger
of all FLOPs over the peak and all bytes over the bandwidth, which is no
more than the sum of the calls' own least times: the share errs low.

Experts touched per layer and call: the window's `moe_experts_touched`
over (decode steps + prefill calls) x layers. None where the program
has no such counter.
"""
import re

import stats_delta
from benchmarks.harness import costs, costs_moe
from benchmarks.harness.trace_reduce import program_mean_seconds


def _delta(run, key):
    return stats_delta._delta(run, [[key]])


def read(run, what, module_re, name_re=None, **_):
    tr, peaks, m = run.get("trace"), run.get("peaks"), run.get("model")
    if not tr or not peaks or not m or "num_experts" not in m:
        return None
    steps, calls = _delta(run, "decode_steps"), _delta(run, "prefill_calls")
    touched = _delta(run, "moe_experts_touched")
    assigned = _delta(run, "moe_assignments")
    if not steps or calls is None or touched is None or assigned is None:
        return None
    layers = m["num_hidden_layers"]
    if what == "step":
        contexts = run.get("trace_contexts")
        step_s = program_mean_seconds(tr, module_re)
        if not contexts or step_s is None:
            return None
        per_layer = min(touched / ((steps + calls) * layers),
                        m["num_experts"])
        least = costs.least_seconds(
            costs_moe.decode_step(m, contexts, per_layer), peaks)
        run.setdefault("notes", {})["moe_step_roofline_bound"] = \
            least["bound"]
        return 100.0 * least["seconds"] / step_s
    runs = sum(v["count"] for n, v in (tr.get("modules") or {}).items()
               if re.search(module_re, n))
    measured = sum(s for n, s in (tr.get("ops") or {}).items()
                   if re.search(name_re, n))
    if not runs or not measured:
        return None
    least = costs.least_seconds(
        costs_moe.expert_matmuls(m, assigned / steps, touched / steps),
        peaks)
    run.setdefault("notes", {})["expert_matmul_bound"] = least["bound"]
    return 100.0 * least["seconds"] / (measured / runs)
