"""On-hardware kernel tests: run on the REAL TPU backend, interpret=False.

Unlike tests/, this suite does NOT force CPU — it exists precisely to
exercise Mosaic lowering, which no interpret-mode test can see.
Everything here skips unless jax.default_backend() == "tpu".

Run: python -m pytest tests_tpu/ -x -q   (on a TPU host)
chip_smoke.py and bench.py's kernels phase run the same kind of check.
"""
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)


def pytest_configure(config):
    # Cold Mosaic/XLA compiles dominate a first run of this suite; the
    # cache placement rule is the program's own (CPU left alone).
    from ray_tpu.util.jaxenv import enable_compile_cache
    enable_compile_cache()
