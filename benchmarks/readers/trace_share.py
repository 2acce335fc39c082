"""Device time of the operations (`of`: "ops") or programs ("modules")
whose name matches, as a share of the device's busy time."""
import re


def read(run, of, name_re, **_):
    tr = run.get("trace")
    if not tr or not tr.get("busy_s"):
        return None
    if of == "ops":
        hit = sum(s for n, s in tr["ops"].items() if re.search(name_re, n))
    else:
        hit = sum(m["seconds"] for n, m in tr["modules"].items()
                  if re.search(name_re, n))
    return 100.0 * hit / tr["busy_s"]
