"""A percentile on the client's clock. `what`: "ttft" (first token
received minus the time the request was due, over requests due in the
window; a failed one is worse than any), "gap" (between consecutive
token events of a stream) or "late" (sent minus due: the generator)."""
import math

from benchmarks.harness import window


def read(run, what, q, **_):
    if run.get("kind") != "serve":
        return None
    s, t0, t1 = run["streams"], run["t0"], run["t1"]
    values = {"ttft": lambda: window.ttft_ms(s, t0, t1),
              "gap": lambda: window.gaps_ms(s, t0, t1),
              "late": lambda: run["late_ms"]}[what]()
    if not values:
        return None
    v = window.percentile(values, q)
    # a tail that falls among failed requests has no finite value: say
    # so with a value no run can reach by being slow
    return 1e9 if math.isinf(v) else v
