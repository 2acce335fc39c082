"""Collective time during which no compute ran on that device, as a
share of the traced window (mean over the devices)."""


def read(run, **_):
    tr = run.get("trace")
    if not tr or not tr.get("window_s"):
        return None
    return 100.0 * tr["collective_exposed_s"] / tr["window_s"]
