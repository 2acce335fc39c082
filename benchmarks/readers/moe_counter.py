"""Ratios of the expert layers' counters inside the window
(`get_stats()["moe_*"]`, ops/moe.py:MOE_STATS), by `stats_delta`'s
arithmetic. `what="load_max_over_mean"`: the busiest expert's load,
summed over calls and layers, over the mean load an expert had
(assignments / experts, the model section's count); 1.0 is a perfectly
even router. `what="pad_row_share"`: rows that were bucket padding or
empty slots, given to no expert, as a share of all rows the layers were
handed. None where the program has no such counters."""
import stats_delta


def read(run, what, **_):
    if what == "load_max_over_mean":
        experts = (run.get("model") or {}).get("num_experts")
        if not experts:
            return None
        return stats_delta.read(run, num=[["moe_expert_load_max"]],
                                den=[["moe_assignments"]],
                                scale=float(experts))
    if what == "pad_row_share":
        return stats_delta.read(run, num=[["moe_pad_rows"]],
                                den=[["moe_pad_rows"], ["moe_rows"]],
                                scale=100.0)
    raise ValueError(f"moe_counter: what={what!r}")
