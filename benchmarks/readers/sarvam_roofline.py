"""Roofline shares of a latent-attention expert-share decoder's decode
step, from `costs_sarvam` against the peaks table. Per decode step: the
window's counters over `decode_steps`, the trace's sums over the runs of
the decode program.

`what="latent_kernel"`: the least time to read the live latents of one
decode step once in every layer (1 152 B a token a layer as published,
whatever the pool pads) and to make the kernel's two products, over the
kernel's measured device time (operations matching `name_re`). Live
tokens a step are the window's `decode_pages_live` x the page size (a
partly filled page counted whole, as the pool is read in pages).

`what="experts"`: the grouped matmuls over the experts held (operations
matching `name_re`), from `moe_assignments` (pairs that ran here) and
`moe_experts_touched`; the counters cover prefill calls as well, and so
does the trace's sum: as `moe_roofline`, the share errs low.

`what="step"`: the least time of one decode step (the weights every step
reads, the experts touched and pairs run as counted per layer call,
the latents of the live sequences at the middle of the traced window)
over the decode program's measured device time.

None where the program has no such counters or the trace no such
operation (a program without this family)."""
import re

import stats_delta
from benchmarks.harness import costs_sarvam
from benchmarks.harness.trace_reduce import program_mean_seconds


def _delta(run, key):
    return stats_delta._delta(run, [[key]])


def _per_decode_run(tr, module_re, name_re):
    runs = sum(v["count"] for n, v in (tr.get("modules") or {}).items()
               if re.search(module_re, n))
    measured = sum(s for n, s in (tr.get("ops") or {}).items()
                   if re.search(name_re, n))
    return measured / runs if runs and measured else None


def read(run, what, module_re, name_re=None, **_):
    tr, peaks, m = run.get("trace"), run.get("peaks"), run.get("model")
    if not tr or not peaks or not m or "kv_lora_rank" not in m:
        return None
    steps, calls = _delta(run, "decode_steps"), _delta(run, "prefill_calls")
    if not steps:
        return None
    notes = run.setdefault("notes", {})
    if what == "latent_kernel":
        live = _delta(run, "decode_pages_live")
        measured = _per_decode_run(tr, module_re, name_re)
        if not live or measured is None:
            return None
        tokens = live / steps * run["config"]["engine"]["kv_page_size"]
        least = costs_sarvam.least_seconds(
            costs_sarvam.latent_attention(m, tokens), peaks)
        notes["latent_kernel_bound"] = least["bound"]
        return 100.0 * least["seconds"] / measured
    touched = _delta(run, "moe_experts_touched")
    assigned = _delta(run, "moe_assignments")
    if touched is None or assigned is None or calls is None:
        return None
    if what == "experts":
        measured = _per_decode_run(tr, module_re, name_re)
        if measured is None:
            return None
        least = costs_sarvam.least_seconds(costs_sarvam.expert_matmuls(
            m, assigned / steps, touched / steps), peaks)
        notes["expert_matmul_share_bound"] = least["bound"]
        return 100.0 * least["seconds"] / measured
    if what == "step":
        contexts = run.get("trace_contexts")
        step_s = program_mean_seconds(tr, module_re)
        if not contexts or step_s is None:
            return None
        layers = costs_sarvam.expert_layers(m)
        per_call = touched / (steps + calls)      # summed over the layers
        least = costs_sarvam.least_seconds(costs_sarvam.decode_step(
            m, contexts, min(per_call, layers * m["num_experts"]),
            len(contexts) * m["num_experts_per_tok"] * layers
            * m["num_experts"] / m["router_width"]), peaks)
        notes["latent_moe_step_bound"] = least["bound"]
        return 100.0 * least["seconds"] / step_s
    raise ValueError(f"sarvam_roofline: what={what!r}")
