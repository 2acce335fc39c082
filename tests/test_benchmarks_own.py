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



# Three cases of the benchmark's own tests hold the manifest to what it
# was when they were written: benchmarks/tests/test_reference_sarvam.py
# counts five cells, test_runtime_calls.py lists the four serve cells
# of those five, test_stats_delta.py counts nine metric files of its
# reader. A later cell is entries appended, and those files are not a
# later PR's to edit. Each case runs here as it is over the manifest as
# it was (every other assertion it makes stays held), and what its
# count stood for is asserted over the WHOLE manifest as it is now,
# the later cells and metric files included.
_ROOT = os.path.dirname(_HERE)
_BENCH = os.path.join(_ROOT, "benchmarks")
_ACCEPTED = ["mistral7b_decode_sat", "mistral7b_train_fsdp2_tp2",
             "mistral7b_short_burst", "olmoe7b_decode_sat",
             "sarvam105b_decode_sat"]


def _as_it_was(whole):
    def cut(metric):
        if "workloads" not in metric:
            return metric
        return dict(metric, workloads=[c for c in metric["workloads"]
                                       if c in _ACCEPTED])
    return dict(whole, workloads=whole["workloads"][:len(_ACCEPTED)],
                end_to_end=[cut(m) for m in whole["end_to_end"]],
                per_layer=[cut(m) for m in whole["per_layer"]])


def _runner_of(whole, cell):
    import json
    from benchmarks import run as runmod
    with open(runmod.resolve(whole, cell)["config_path"]) as f:
        return json.load(f)["runner"]


_sarvam_case = globals()[
    "test_the_sarvam_cell_resolves_to_its_own_runner_and_files"]
_calls_case = globals()[
    "test_the_manifest_reports_it_in_the_serve_cells_and_only_there"]


def test_the_sarvam_cell_resolves_to_its_own_runner_and_files(  # noqa: F811
        monkeypatch):
    """The accepted case, then its two counts over every cell there is:
    the accepted cells first and in their order, later ones behind
    them, each resolving to files that exist; one four-chip cell, which
    is all a manifest of this size may have."""
    from benchmarks import run as runmod
    whole = runmod.load_manifest()
    with monkeypatch.context() as m:
        m.setattr(runmod, "load_manifest", lambda: _as_it_was(whole))
        _sarvam_case()
    cells = [w["name"] for w in whole["workloads"]]
    assert cells[:len(_ACCEPTED)] == _ACCEPTED
    assert len(set(cells)) == len(cells) > len(_ACCEPTED)
    assert sum(w["chips"] == 4 for w in whole["workloads"]) == 1 \
        <= max(1, len(cells) // 4)
    for cell in cells:
        found = runmod.resolve(whole, cell)
        assert os.path.exists(found["config_path"]), cell
        if _runner_of(whole, cell).startswith("serve_http"):
            assert os.path.exists(found["traffic_path"]), cell
        for section in ("end_to_end", "per_layer"):
            reported = runmod.cell_metrics(whole, cell, section)
            assert reported, (cell, section)
            for metric in reported:
                assert os.path.exists(os.path.join(
                    _BENCH, "metrics", metric["name"] + ".json")), (
                        cell, metric["name"])


def test_the_manifest_reports_it_in_the_serve_cells_and_only_there(  # noqa: F811,E501
        monkeypatch):
    """The accepted case, then its list over every cell there is:
    `engine_runtime_calls_per_step` on each cell a serve runner runs,
    the later ones too, and on no other."""
    from benchmarks import run as runmod
    whole = runmod.load_manifest()
    with monkeypatch.context() as m:
        m.setattr(runmod, "load_manifest", lambda: _as_it_was(whole))
        _calls_case()
    entry, = [m for m in whole["per_layer"]
              if m["name"] == "engine_runtime_calls_per_step"]
    serve = [w["name"] for w in whole["workloads"]
             if _runner_of(whole, w["name"]).startswith("serve_http")]
    assert entry["workloads"] == serve
    assert set(serve) - set(_ACCEPTED), "a later serve cell is listed too"
    for w in whole["workloads"]:
        names = [m["name"] for m in runmod.cell_metrics(
            whole, w["name"], "per_layer")]
        assert ("engine_runtime_calls_per_step" in names) \
            == (w["name"] in serve), w["name"]


def test_every_metric_file_of_the_reader_names_paths_the_engine_seeds():  # noqa: F811,E501
    """The accepted case's rules over every `stats_delta` file there is
    (it stops at the nine it was written beside): a span or counter
    misspelt in a metric file would read None for ever. Each such file
    is a metric of the manifest."""
    import json
    from benchmarks import run as runmod
    from ray_tpu.serve.llm import engine
    stats_run = globals()["RUN"]
    seeded = set(engine._LOOP_SPANS + engine._REQUEST_SPANS)
    whole = runmod.load_manifest()
    listed = {m["name"] for m in whole["end_to_end"] + whole["per_layer"]}
    found = []
    for name in sorted(os.listdir(os.path.join(_BENCH, "metrics"))):
        with open(os.path.join(_BENCH, "metrics", name)) as f:
            spec = json.load(f)
        if spec["reader"] != "stats_delta":
            continue
        found.append(name[:-5])
        for path in spec["args"]["num"] + spec["args"]["den"]:
            if path[0] == "spans":
                assert path[1] in seeded and path[2] in (0, 1), (name, path)
            else:
                assert len(path) == 1 and path[0].startswith(
                    ("decode_", "prefill_")), (name, path)
        got = runmod.read_metric(_BENCH, name[:-5], stats_run)
        assert got is None or isinstance(got, float), name
    assert len(found) >= 10 and set(found) <= listed
    assert "decode_live_state_share" in found
