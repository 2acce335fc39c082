"""Tier 1 runs `pytest tests/`; the benchmark keeps its own tests beside
its code (`benchmarks/tests`: the manifest's rules, schedule, window,
trace reduction, readers, the reference). This file brings each of them
in as a case of tier 1, so that a PR that breaks the benchmark's
arithmetic or its manifest fails here, on the CPU, before any chip run."""
import glob
import importlib
import os

_HERE = os.path.dirname(os.path.abspath(__file__))
_seen = {}
for _path in sorted(glob.glob(os.path.join(
        os.path.dirname(_HERE), "benchmarks", "tests", "test_*.py"))):
    _stem = os.path.splitext(os.path.basename(_path))[0]
    _mod = importlib.import_module("benchmarks.tests." + _stem)
    for _name, _obj in vars(_mod).items():
        if _name.startswith("_"):
            continue
        if _name.startswith("test_"):
            # two files with one test name would leave one uncollected
            assert _name not in _seen, (_name, _stem, _seen[_name])
            _seen[_name] = _stem
        globals()[_name] = _obj     # tests, and the fixtures they name


# benchmarks/tests/test_manifest.py walks every configuration of the
# manifest through the Llama-shaped section; a configuration of another
# family (latent attention: no num_key_value_heads, head_dim x heads is
# not the hidden size) has a section of its own
# (harness/replica_sarvam.py:model_section, tested beside it). The
# benchmark's file is not this PR's to edit, so the case runs here over
# the Llama-shaped files it was written for.
_refused = globals()[
    "test_a_llama_shaped_file_with_another_head_dim_is_still_refused"]


def test_a_llama_shaped_file_with_another_head_dim_is_still_refused(  # noqa: F811
        manifest, tmp_path):
    import json
    root = os.path.dirname(_HERE)

    def llama_shaped(entry):
        with open(os.path.join(root, entry["file"])) as f:
            return "num_key_value_heads" in json.load(f)
    _refused(dict(manifest, configs=[c for c in manifest["configs"]
                                     if llama_shaped(c)]), tmp_path)
