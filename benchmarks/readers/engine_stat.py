"""One number of `LLMEngine.get_stats()` as it stood when the window
closed; `path` walks into nested dicts."""


def read(run, path, **_):
    node = run.get("stats1")
    for key in path:
        if not isinstance(node, dict) or key not in node:
            return None
        node = node[key]
    return float(node) if isinstance(node, (int, float)) else None
