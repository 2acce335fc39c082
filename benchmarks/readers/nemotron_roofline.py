"""Roofline shares of a Nemotron-3-Super decoder's decode step (Mamba-2
layers with a 4 MiB state a slot, one NoPE grouped-query layer that
pages K and V, expert layers that hold a share of relu^2 experts
computed in a latent), from `costs_nemotron` against the peaks table:
the work counted from the configuration and the window's live rows, not
from what the program happens to do. Per decode step: the window's
counters over `decode_steps`, the trace's sums over the runs of the
decode program.

`what="ssm_kernel"`: the least time to read and write once the state of
the rows that were decoding, in every `M` layer
(`decode_state_rows_live`: decoding rows x state layers, summed over
the window's steps), over the step kernel's measured device time
(operations matching `name_re`). A kernel that walks every row of the
pool, idle ones too, shows the idle rows as lost roofline.

`what="experts"`: the grouped matmuls over the experts held (operations
matching `name_re`), two an expert at 1 024 x 2 688, from
`moe_assignments` (pairs that ran here) and `moe_experts_touched`; the
counters cover prefill calls as well, and so does the trace's sum: as
`moe_roofline`, the share errs low.

`what="paged_kernel"`: the least time to read a step's live pages at
the PUBLISHED bytes a token (K and V of 2 heads of 128;
`decode_pages_live` x the page size tokens a step) over the paged
kernel's measured time: the pool lays out 8 heads, so the share shows
the padding as lost roofline.

`what="step"`: the least time of one decode step (the weights every
step reads, the experts touched and pairs run as counted per layer
call, K and V of the live sequences at the middle of the traced window,
the live rows' state in and out) over the decode program's measured
device time: the bound on any later claim in the cell.

`what="ssm_scan"`: the device time of the operations traced under the
named scope `ssm.scan` (the chunkwise form in the prefill programs; the
replica reads them by the scope's name, out of the HloProto the profile
keeps of every program that ran: `replica_nemotron.scope_seconds`) over
the device's busy time: what a chunk-scan kernel would buy.

None where the program has no such counters or the trace no such
operation (a program without this family)."""
from sarvam_roofline import _delta, _per_decode_run
from benchmarks.harness import costs_nemotron
from benchmarks.harness.trace_reduce import program_mean_seconds


def read(run, what, module_re=None, name_re=None, **_):
    tr, peaks, m = run.get("trace"), run.get("peaks"), run.get("model")
    if not tr or not peaks or not m or "moe_latent_size" not in m:
        return None
    if what == "ssm_scan":
        return 100.0 * tr["ssm_scan_s"] / tr["busy_s"] \
            if "ssm_scan_s" in tr and tr.get("busy_s") else None
    steps, calls = _delta(run, "decode_steps"), _delta(run, "prefill_calls")
    if not steps:
        return None
    notes = run.setdefault("notes", {})
    if what in ("ssm_kernel", "paged_kernel"):
        live = _delta(run, "decode_state_rows_live" if what == "ssm_kernel"
                      else "decode_pages_live")
        measured = _per_decode_run(tr, module_re, name_re)
        if not live or measured is None:
            return None
        cost = (costs_nemotron.ssm_step(m, live / steps)
                if what == "ssm_kernel" else costs_nemotron.paged_attention(
                    m, live / steps,
                    run["config"]["engine"]["kv_page_size"]))
        least = costs_nemotron.least_seconds(cost, peaks)
        notes[f"nemotron_{what}_bound"] = least["bound"]
        return 100.0 * least["seconds"] / measured
    touched = _delta(run, "moe_experts_touched")
    assigned = _delta(run, "moe_assignments")
    if touched is None or assigned is None or calls is None:
        return None
    if what == "experts":
        measured = _per_decode_run(tr, module_re, name_re)
        if measured is None:
            return None
        least = costs_nemotron.least_seconds(costs_nemotron.expert_matmuls(
            m, assigned / steps, touched / steps), peaks)
        notes["nemotron_expert_matmul_bound"] = least["bound"]
        return 100.0 * least["seconds"] / measured
    if what == "step":
        contexts = run.get("trace_contexts")
        step_s = program_mean_seconds(tr, module_re)
        if not contexts or step_s is None:
            return None
        layers = costs_nemotron.layers(m, "E")
        per_call = touched / (steps + calls)      # summed over the layers
        least = costs_nemotron.least_seconds(costs_nemotron.decode_step(
            m, contexts, min(per_call, layers * m["num_experts"]),
            len(contexts) * m["num_experts_per_tok"] * layers
            * m["num_experts"] / m["router_width"]), peaks)
        notes["nemotron_step_bound"] = least["bound"]
        return 100.0 * least["seconds"] / step_s
    raise ValueError(f"nemotron_roofline: what={what!r}")
