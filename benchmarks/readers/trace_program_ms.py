"""Mean device time of one run of the programs whose name matches."""
from benchmarks.harness.trace_reduce import program_mean_seconds


def read(run, module_re, **_):
    tr = run.get("trace")
    mean = program_mean_seconds(tr, module_re) if tr else None
    return None if mean is None else 1000.0 * mean
