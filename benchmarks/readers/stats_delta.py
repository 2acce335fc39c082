"""A ratio of what `LLMEngine.get_stats()` counted inside the window:
`scale` x (sum over the `num` paths of stats1 - stats0) over (the same
over the `den` paths). A path walks into nested dicts and lists
(`["spans", "engine.emit", 1]` is a span's total nanoseconds, index 0
its count). `complement` gives `scale` x (1 - ratio). None where either
reading lacks a path (a program without that span or counter) or the
denominator is not positive."""


def _at(node, path):
    for key in path:
        try:
            node = node[key]
        except (KeyError, IndexError, TypeError):
            return None
    return node if isinstance(node, (int, float)) else None


def _delta(run, paths):
    total = 0.0
    for path in paths:
        a, b = _at(run.get("stats0"), path), _at(run.get("stats1"), path)
        if a is None or b is None:
            return None
        total += b - a
    return total


def read(run, num, den, scale=1.0, complement=False, **_):
    top, bottom = _delta(run, num), _delta(run, den)
    if top is None or bottom is None or bottom <= 0:
        return None
    ratio = top / bottom
    return scale * (1.0 - ratio if complement else ratio)
