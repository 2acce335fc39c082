"""BENCHMARK.json against the contract's lexical rules, every file it
names, and the proof that a new cell, configuration, traffic mix and
metric are files added and entries appended: nothing that exists is
edited."""
import importlib
import json
import os
import re
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    assert len(json.dumps(manifest)) < 64 * 1024
    cells = manifest["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    # the whole check must fit: 2 + 14 runs a cell, at the full 24 cells
    assert (2 + 14 * 24) * (manifest["run_seconds"] + 60) \
        + 24 * 180 + 1200 <= 43200


def test_names_units_and_whys(manifest):
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and 1 <= len(c["why"]) <= 200
        assert all(NAME.match(k) for k in c["reduced"])
        assert not any(k.endswith(("_dim", "_rank", "_size"))
                       for k in c["reduced"])
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))


def test_every_cell_reports_what_it_must(manifest):
    sys.path.insert(0, ROOT)
    from benchmarks.run import cell_metrics
    e2e = {m["name"] for m in manifest["end_to_end"]}
    used = set()
    for w in manifest["workloads"]:
        mine = {m["name"] for m in cell_metrics(manifest, w["name"],
                                                "end_to_end")}
        assert "setup_s" in mine and len(mine) >= 2
        layer = cell_metrics(manifest, w["name"], "per_layer")
        assert layer
        for m in layer:
            assert m["moves"] in mine, (w["name"], m["name"], m["moves"])
        used.add(w["config"])
    assert used == {c["name"] for c in manifest["configs"]}
    assert all(m["moves"] in e2e for m in manifest["per_layer"])


def test_every_named_file_is_there_and_loads(manifest):
    sys.path.insert(0, ROOT)
    from benchmarks.harness import modelcfg
    for c in manifest["configs"]:
        assert c["file"].startswith("benchmarks/")
        cfg = modelcfg.load(os.path.join(ROOT, c["file"]), rehearse=False)
        assert set(cfg["reduced"]) == set(c["reduced"])
        importlib.import_module("benchmarks.runners." + cfg["runner"])
        tiny = modelcfg.load(os.path.join(ROOT, c["file"]), rehearse=True)
        assert tiny["hidden_size"] < cfg["hidden_size"]
    for w in manifest["workloads"]:
        assert os.path.exists(os.path.join(
            BENCH, "traffic", w["traffic"] + ".json"))
    sys.path.insert(0, os.path.join(BENCH, "readers"))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        with open(os.path.join(BENCH, "metrics", m["name"] + ".json")) as f:
            spec = json.load(f)
        assert hasattr(importlib.import_module(spec["reader"]), "read")
    for dirpath, _dirs, files in os.walk(BENCH):
        if "__pycache__" in dirpath:
            continue
        for name in files:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", name), name


def test_a_new_cell_is_files_added_and_entries_appended(manifest, tmp_path):
    """A later PR's cell, configuration, mix and metric in a throw-away
    copy: new files beside the old, new entries at the end, and the
    harness finds them by name with no file of the benchmark edited."""
    sys.path.insert(0, ROOT)
    from benchmarks import run as runmod
    bench = tmp_path / "benchmarks"
    for d in ("configs", "traffic", "metrics", "readers"):
        shutil.copytree(os.path.join(BENCH, d), bench / d)
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}

    base = json.loads((bench / "configs" /
                       "mistral-7b-v0.3-serve-l16.json").read_text())
    base["name"] = "extra-config"
    (bench / "configs" / "extra-config.json").write_text(json.dumps(base))
    mix = json.loads((bench / "traffic" / "short_burst.json").read_text())
    mix["rate_rps"] = 5.0
    (bench / "traffic" / "extra_mix.json").write_text(json.dumps(mix))
    (bench / "readers" / "extra_reader.py").write_text(
        "def read(run, scale=1, **_):\n    return run['x'] * scale\n")
    (bench / "metrics" / "extra_metric.json").write_text(json.dumps(
        {"reader": "extra_reader", "args": {"scale": 3}}))

    grown = json.loads(json.dumps(manifest))
    grown["configs"].append({
        "name": "extra-config", "source": "https://example.org/x",
        "file": "benchmarks/configs/extra-config.json", "reduced": [],
        "why": "throw-away"})
    grown["workloads"].append({
        "name": "extra_cell", "config": "extra-config",
        "traffic": "extra_mix", "chips": 1, "why": "throw-away"})
    grown["per_layer"].append({
        "name": "extra_metric", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "device", "moves": "out_tok_s",
        "workloads": ["extra_cell"]})
    grown["end_to_end"][0]["workloads"] = \
        grown["end_to_end"][0]["workloads"] + ["extra_cell"]

    found = runmod.resolve(grown, "extra_cell", bench_dir=str(bench))
    assert found["config_path"].endswith("extra-config.json")
    assert os.path.exists(found["traffic_path"])
    layer = runmod.cell_metrics(grown, "extra_cell", "per_layer")
    assert [m["name"] for m in layer] == ["extra_metric"]
    assert runmod.read_metric(str(bench), "extra_metric", {"x": 2}) == 6
    # the schedule of the new mix comes from the one generator
    from benchmarks.harness import schedule
    with open(found["traffic_path"]) as f:
        reqs = schedule.build(json.load(f), 1, 10)
    assert reqs and reqs[0].due_s >= 0
    after = {p: p.read_bytes() for p in before}
    assert after == before
