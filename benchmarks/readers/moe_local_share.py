"""Share of the (row, expert) pairs the router handed out inside the
window that ran on experts held here (`moe_assignments` over
`moe_routed_assignments`, ops/moe.py:MOE_STATS): 100 where the layer
holds every expert, 25 for a quarter of them under an even router. None
where the program does not count what was routed."""
import stats_delta


def read(run, **_):
    return stats_delta.read(run, num=[["moe_assignments"]],
                            den=[["moe_routed_assignments"]], scale=100.0)
