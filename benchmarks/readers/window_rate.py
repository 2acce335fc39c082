"""Output tokens received by the client inside the window, over the
window's length. Every token event counts, whichever request it is of."""
from benchmarks.harness import window


def read(run, **_):
    if run.get("kind") != "serve":
        return None
    return window.tokens_in_window(
        run["streams"], run["t0"], run["t1"]) / run["seconds"]
