"""Roofline shares of a Xing4.0 decoder's decode step (residual streams
through `hc_mix_in` / `hc_mix_out`, latent attention at 32 heads over
padded pool rows, routed experts of 3 584 x 1 024), from `costs_xing`
against the peaks table. Per decode step: the window's counters over
`decode_steps`, the trace's sums over the runs of the decode program.

`what="hc_kernels"`: the least time to move a step's residual streams
through both kernels (`hc_rows` real (row, sub-layer) pairs a step, phi
once a sub-layer) over the kernels' measured time (operations matching
`name_re`). The counter covers prefill calls as well, and so does the
trace's sum; rows a kernel ran for padding are not counted, so the share
errs low.

`what="latent_kernel"`: the least time to read the live pool rows of one
decode step once in every layer (1 280 B a token a layer: 576 columns
padded to 640) and to make 32 heads' two products, over the kernel's
measured time.

`what="experts"`: the grouped matmuls (operations matching `name_re`),
from `moe_assignments` and `moe_experts_touched`; errs low as
`moe_roofline` does.

`what="step"`: the least time of one decode step (the weights every step
reads, the experts touched and pairs run as counted per layer call, the
pool rows of the live sequences at the middle of the traced window, the
streams of every row through both kernels) over the decode program's
measured device time.

None where the program has no such counters or the trace no such
operation (a program without this family)."""
from sarvam_roofline import _delta, _per_decode_run
from benchmarks.harness import costs_xing
from benchmarks.harness.trace_reduce import program_mean_seconds


def read(run, what, module_re=None, name_re=None, **_):
    tr, peaks, m = run.get("trace"), run.get("peaks"), run.get("model")
    if not tr or not peaks or not m or "hc_mult" not in m:
        return None
    steps, calls = _delta(run, "decode_steps"), _delta(run, "prefill_calls")
    if not steps:
        return None
    notes = run.setdefault("notes", {})
    if what == "hc_kernels":
        rows = _delta(run, "hc_rows")
        measured = _per_decode_run(tr, module_re, name_re)
        if not rows or measured is None:
            return None
        least = costs_xing.least_seconds(costs_xing.hc_kernels(
            m, rows / steps, costs_xing.sub_layers(m)), peaks)
        notes["hc_kernel_bound"] = least["bound"]
        return 100.0 * least["seconds"] / measured
    if what == "latent_kernel":
        live = _delta(run, "decode_pages_live")
        measured = _per_decode_run(tr, module_re, name_re)
        if not live or measured is None:
            return None
        tokens = live / steps * run["config"]["engine"]["kv_page_size"]
        least = costs_xing.least_seconds(
            costs_xing.latent_attention(m, tokens), peaks)
        notes["xing_latent_kernel_bound"] = least["bound"]
        return 100.0 * least["seconds"] / measured
    touched = _delta(run, "moe_experts_touched")
    assigned = _delta(run, "moe_assignments")
    if touched is None or assigned is None or calls is None:
        return None
    if what == "experts":
        measured = _per_decode_run(tr, module_re, name_re)
        if measured is None:
            return None
        least = costs_xing.least_seconds(costs_xing.expert_matmuls(
            m, assigned / steps, touched / steps), peaks)
        notes["xing_expert_matmul_bound"] = least["bound"]
        return 100.0 * least["seconds"] / measured
    if what == "step":
        contexts = run.get("trace_contexts")
        step_s = program_mean_seconds(tr, module_re)
        if not contexts or step_s is None:
            return None
        layers = costs_xing.expert_layers(m)
        per_call = touched / (steps + calls)      # summed over the layers
        least = costs_xing.least_seconds(costs_xing.decode_step(
            m, contexts, min(per_call, layers * m["n_routed_experts"]),
            len(contexts) * m["num_experts_per_tok"] * layers), peaks)
        notes["xing_step_bound"] = least["bound"]
        return 100.0 * least["seconds"] / step_s
    raise ValueError(f"xing_roofline: what={what!r}")
