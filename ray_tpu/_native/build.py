"""Build-on-first-use for the native components.

Compiles <name>.cc into build/lib<name>-<hash of the source>.so with g++.
The name carries the source's content hash, so a library is stale exactly
when its source changed (file times mean nothing after a copy or a
checkout), and the atomic rename means concurrently-importing worker
processes never see a half-written library. build/ is not tracked by git.
"""
from __future__ import annotations

import hashlib
import os
import subprocess
import tempfile

_HERE = os.path.dirname(os.path.abspath(__file__))
_BUILD_DIR = os.path.join(_HERE, "build")


def build_library(name: str) -> str:
    """Return the path to the library built from <name>.cc, compiling it
    if no build of this exact source exists."""
    src = os.path.join(_HERE, f"{name}.cc")
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    out = os.path.join(_BUILD_DIR, f"lib{name}-{digest}.so")
    if os.path.exists(out):
        return out
    os.makedirs(_BUILD_DIR, exist_ok=True)
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=_BUILD_DIR)
    os.close(fd)
    try:
        subprocess.run(
            ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-o", tmp,
             src, "-lpthread", "-lrt"],
            check=True, capture_output=True, text=True, timeout=120)
        os.replace(tmp, out)   # atomic: racers overwrite with identical .so
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise
    return out
