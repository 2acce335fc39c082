"""A mean on the client's clock over everything of one kind in the
window. `what`: "gap" (between consecutive token events of a stream,
later event in the window: the time a stream's reader waits per output
token, every gap weighing the same)."""
import statistics

from benchmarks.harness import window


def read(run, what, **_):
    if run.get("kind") != "serve":
        return None
    values = {"gap": lambda: window.gaps_ms(run["streams"], run["t0"],
                                            run["t1"])}[what]()
    return statistics.fmean(values) if values else None
