"""Roofline shares of an LFM2-MoE decoder's decode step (short
convolutions, full layers that page narrow heads, routed experts), from
`costs_lfm2moe` against the peaks table. Per decode step: the window's
counters over `decode_steps`, the trace's sums over the runs of the
decode program.

`what="experts"`: the grouped matmuls (operations matching `name_re`),
from `moe_assignments` and `moe_experts_touched`; the counters cover
prefill calls as well, and so does the trace's sum: as `moe_roofline`,
the share errs low.

`what="paged_kernel"`: the least time to read a step's live pages at
the PUBLISHED bytes a token (K and V of the full layers' heads at their
own width; `decode_pages_live` x the page size tokens a step) over the
paged kernel's measured time: a pool padded to 128 lanes reads twice
that and cannot pass 50 %.

`what="step"`: the least time of one decode step (the weights every
step reads, the experts touched and pairs run as counted per layer call,
K and V of the live sequences at the middle of the traced window, the
live rows' conv state in and out) over the decode program's measured
device time.

None where the program has no such counters or the trace no such
operation (a program without this family)."""
from sarvam_roofline import _delta, _per_decode_run
from benchmarks.harness import costs_lfm2moe
from benchmarks.harness.trace_reduce import program_mean_seconds


def read(run, what, module_re=None, name_re=None, **_):
    tr, peaks, m = run.get("trace"), run.get("peaks"), run.get("model")
    if not tr or not peaks or not m or "conv_L_cache" not in m:
        return None
    steps, calls = _delta(run, "decode_steps"), _delta(run, "prefill_calls")
    if not steps:
        return None
    notes = run.setdefault("notes", {})
    if what == "paged_kernel":
        live = _delta(run, "decode_pages_live")
        measured = _per_decode_run(tr, module_re, name_re)
        if not live or measured is None:
            return None
        least = costs_lfm2moe.least_seconds(costs_lfm2moe.paged_attention(
            m, live / steps, run["config"]["engine"]["kv_page_size"]), peaks)
        notes["packed_paged_kernel_bound"] = least["bound"]
        return 100.0 * least["seconds"] / measured
    touched = _delta(run, "moe_experts_touched")
    assigned = _delta(run, "moe_assignments")
    if touched is None or assigned is None or calls is None:
        return None
    if what == "experts":
        measured = _per_decode_run(tr, module_re, name_re)
        if measured is None:
            return None
        least = costs_lfm2moe.least_seconds(costs_lfm2moe.expert_matmuls(
            m, assigned / steps, touched / steps), peaks)
        notes["lfm2moe_expert_matmul_bound"] = least["bound"]
        return 100.0 * least["seconds"] / measured
    if what == "step":
        contexts = run.get("trace_contexts")
        step_s = program_mean_seconds(tr, module_re)
        if not contexts or step_s is None:
            return None
        layers = costs_lfm2moe.expert_layers(m)
        per_call = touched / (steps + calls)      # summed over the layers
        least = costs_lfm2moe.least_seconds(costs_lfm2moe.decode_step(
            m, contexts, min(per_call, layers * m["num_experts"]),
            len(contexts) * m["num_experts_per_tok"] * layers), peaks)
        notes["lfm2moe_step_bound"] = least["bound"]
        return 100.0 * least["seconds"] / step_s
    raise ValueError(f"lfm2moe_roofline: what={what!r}")
