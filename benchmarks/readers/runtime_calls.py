"""Calls the engine thread made into the JAX runtime inside the window
(`get_stats()["runtime_calls"]`: step programs, fetch starts, and what
else `_loop_once` calls there) over the decode steps drained in it, by
`stats_delta`'s arithmetic: 2 a decode step and 2 more for every prefill
call when nothing eager stands between two programs. None where the
program has no such counter (a tree before PR 32)."""
import stats_delta


def read(run, **_):
    return stats_delta.read(run, num=[["runtime_calls"]],
                            den=[["decode_steps"]])
