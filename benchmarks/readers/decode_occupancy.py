"""Share of decode rows that carried a request: tokens emitted by decode
steps in the window over decode steps times slots (get_stats() deltas;
a prefill emits each request's first token, so those are taken off)."""


def read(run, **_):
    if run.get("kind") != "serve":
        return None
    a, b = run["stats0"], run["stats1"]
    steps = b["decode_steps"] - a["decode_steps"]
    if steps <= 0:
        return None
    toks = ((b["tokens_generated"] - a["tokens_generated"])
            - (b["prefills"] - a["prefills"]))
    return 100.0 * toks / (steps * run["config"]["engine"]["max_slots"])
