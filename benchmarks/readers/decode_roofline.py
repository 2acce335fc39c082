"""The decode step's share of its roofline: the least time the chip
could take for one step (every matmul weight and every live sequence's
cache read once, benchmarks/harness/costs.py, against the peaks table)
over the step's measured device time. The live contexts are those of
the slots decoding at the middle of the traced window, as the replica's
sampler read them from the engine."""
from benchmarks.harness import costs
from benchmarks.harness.trace_reduce import program_mean_seconds


def read(run, module_re, **_):
    tr, peaks = run.get("trace"), run.get("peaks")
    contexts = run.get("trace_contexts")
    if not tr or not peaks or not contexts:
        return None
    step_s = program_mean_seconds(tr, module_re)
    if step_s is None:
        return None
    least = costs.least_seconds(costs.decode_step(run["model"], contexts),
                                peaks)
    run.setdefault("notes", {})["decode_roofline_bound"] = least["bound"]
    return 100.0 * least["seconds"] / step_s
