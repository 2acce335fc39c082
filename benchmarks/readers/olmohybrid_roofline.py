"""Roofline shares of a hybrid decoder's decode step (linear-attention
layers with a per-slot recurrent state beside full-attention layers that
page K and V), from `costs_olmohybrid` against the peaks table. Per
decode step: the window's counters over `decode_steps`, the trace's sums
over the runs of the decode program.

`what="gdn_kernel"`: the least time to read and write once the state of
the rows that were decoding, in every linear layer
(`decode_state_rows_live`: decoding rows x linear layers, summed over
the window's steps), over the step kernel's measured device time
(operations matching `name_re`). A kernel that walks every row of the
pool, idle ones too, shows the idle rows as lost roofline
(`decode_live_state_share`, a `stats_delta` file, says how many they
are).

`what="paged_kernel"`: `paged_roofline` with K and V counted in the full
layers only (`decode_pages_live` x the page size tokens a step).

`what="step"`: the least time of one decode step (every matmul weight
once, the live rows' state once in and once out, K and V of the live
sequences at the middle of the traced window in the full layers) over
the decode program's measured device time.

None where the program has no such counters or the trace no such
operation (a program without this family)."""
from sarvam_roofline import _delta, _per_decode_run
from benchmarks.harness import costs_olmohybrid
from benchmarks.harness.trace_reduce import program_mean_seconds


def read(run, what, module_re=None, name_re=None, **_):
    tr, peaks, m = run.get("trace"), run.get("peaks"), run.get("model")
    if not tr or not peaks or not m or "linear_key_head_dim" not in m:
        return None
    notes = run.setdefault("notes", {})
    if what == "step":
        contexts = run.get("trace_contexts")
        step_s = program_mean_seconds(tr, module_re)
        if not contexts or step_s is None:
            return None
        least = costs_olmohybrid.least_seconds(
            costs_olmohybrid.decode_step(m, contexts), peaks)
        notes["hybrid_step_bound"] = least["bound"]
        return 100.0 * least["seconds"] / step_s
    steps = _delta(run, "decode_steps")
    measured = _per_decode_run(tr, module_re, name_re)
    if not steps or measured is None:
        return None
    if what == "gdn_kernel":
        live = _delta(run, "decode_state_rows_live")
        if not live:
            return None
        least = costs_olmohybrid.least_seconds(
            costs_olmohybrid.gdn_step(m, live / steps), peaks)
        notes["gdn_kernel_bound"] = least["bound"]
        return 100.0 * least["seconds"] / measured
    if what == "paged_kernel":
        live = _delta(run, "decode_pages_live")
        if not live:
            return None
        least = costs_olmohybrid.least_seconds(
            costs_olmohybrid.paged_attention(
                m, live / steps, run["config"]["engine"]["kv_page_size"]),
            peaks)
        notes["hybrid_paged_kernel_bound"] = least["bound"]
        return 100.0 * least["seconds"] / measured
    raise ValueError(f"olmohybrid_roofline: what={what!r}")
