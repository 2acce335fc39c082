"""A number the runner itself took, by its key in the run record:
`setup_s` (process start to window open), `compiles_in_window`
(compilations or compile-cache loads inside the window, counted by a
jax.monitoring listener in the process that owns the chip),
`backlog_end` (requests due before the window closed that had no token
by then, wherever in the service they waited)."""


def read(run, key, **_):
    v = run.get(key)
    return None if v is None else float(v)
