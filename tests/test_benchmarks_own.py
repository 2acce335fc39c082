"""Tier 1 runs `pytest tests/`; the benchmark keeps its own tests beside
its code (`benchmarks/tests`: the manifest's rules, schedule, window,
trace reduction, readers, the reference). This file brings each of them
in as a case of tier 1, so that a PR that breaks the benchmark's
arithmetic or its manifest fails here, on the CPU, before any chip run."""
import glob
import importlib
import os
import sys

import pytest

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
    (it stops at the nine it was written beside), widened by what later
    PRs record: a path is index 0, 1 or 3 (count, wall, CPU) of a span
    the engine seeds, a `decode_*` / `prefill_*` counter, or a number
    that `get_stats()` reports of a fresh engine with one stream open
    on an event loop. A span or counter misspelt in a metric file would
    read None for ever. Each such file is a metric of the manifest."""
    import asyncio
    import json
    import jax
    from benchmarks import run as runmod
    from ray_tpu.models import Llama, LlamaConfig
    from ray_tpu.observability.profiler import GC_SPANS
    from ray_tpu.serve.llm import LLMEngine, LLMEngineConfig, engine
    readers = os.path.join(_BENCH, "readers")
    if readers not in sys.path:
        sys.path.insert(0, readers)
    import stats_delta
    stats_run = globals()["RUN"]
    seeded = set(engine._LOOP_SPANS + engine._REQUEST_SPANS
                 + engine._RUNTIME_SPANS + engine._STEP_SPANS + GC_SPANS)
    model = Llama(LlamaConfig(vocab_size=64, d_model=16, n_layers=1,
                              n_heads=2, n_kv_heads=1, d_ff=32,
                              max_seq_len=64, remat=False))
    eng = LLMEngine(model, model.init_params(jax.random.PRNGKey(0)),
                    LLMEngineConfig(max_slots=2, max_seq_len=64,
                                    prefill_buckets=(16,), kv_page_size=16))

    async def fresh_stats():
        stream = eng.astream_detailed(eng.submit([1, 2, 3],
                                                 max_new_tokens=2))
        try:
            return eng.get_stats()
        finally:
            await stream.aclose()
    try:
        fresh = asyncio.run(fresh_stats())
    finally:
        eng.shutdown()
    assert seeded <= set(fresh["spans"])
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
                assert path[1] in seeded and path[2] in (0, 1, 3), (
                    name, path)
            else:
                assert (len(path) == 1 and path[0].startswith(
                    ("decode_", "prefill_"))) \
                    or stats_delta._at(fresh, path) is not None, (
                        name, path)
        got = runmod.read_metric(_BENCH, name[:-5], stats_run)
        assert got is None or isinstance(got, float), name
    assert len(found) >= 19 and set(found) <= listed
    assert "decode_live_state_share" in found


_NEW_IN_PR_37 = {
    "engine_step_call_ms": 4.0, "engine_fetch_start_ms": 0.5,
    "engine_step_release_ms": 7.0,
    "engine_runtime_blocked_share": 40.0,
    "engine_host_cpu_ms_per_step": 6.0, "engine_thread_cpu_share": 50.0,
    "consumer_loop_cpu_share": 30.0, "replica_gc_pause_share": 1.0,
    "slot_refill_starved_share": 75.0}


def _window(with_rows=True):
    """Two readings of `get_stats()` one 10 s window apart: 1 000 decode
    steps, each a program call of 4 ms (2.4 of them on the CPU), 7 ms
    to release the leaves donated into it and a fetch start of 0.5 ms
    (0.3), 6 ms of CPU a step over the host's
    phases, the two threads at 50 % and 30 %, 100 ms of collections,
    four refills of 100 ms of which 75 starved."""
    def reading(k):
        ms = 1_000_000
        spans = {name: [k, 0, 0, k * 500 * 1_000] for name in (
            "engine.loop", "engine.control", "engine.admit",
            "engine.prefill_dispatch", "engine.chunk_dispatch",
            "engine.decode_prep", "engine.decode_dispatch", "engine.emit",
            "engine.bookkeep", "engine.deliver")}
        spans["engine.decode_dispatch"][3] = k * 1_500 * 1_000
        spans["slot.refill"] = [4 * k // 1000, k * 400 * 1_000, 0, 0]
        out = {"decode_steps": k, "spans": spans}
        if with_rows:
            spans["runtime.step"] = [k, 4 * k * ms, 0, int(2.4 * k * ms)]
            spans["runtime.fetch_start"] = [k, k * ms // 2, 0,
                                            int(0.3 * k * ms)]
            spans["runtime.other"] = [0, 0, 0, 0]
            spans["step.release"] = [k, 7 * k * ms, 0, 2 * k * ms]
            spans["gc.pause"] = [k // 10, k * 100 * 1_000, 0, 0]
            spans["slot.refill.starved"] = [4 * k // 1000,
                                            k * 300 * 1_000, 0, 0]
            out["threads"] = {"engine": 5 * k * ms, "consumers": 3 * k * ms,
                              "wall_ns": 10 * k * ms}
        else:
            for row in spans.values():
                del row[3]              # a tree before the fourth column
        return out
    return {"stats0": reading(1_000), "stats1": reading(2_000)}


@pytest.mark.parametrize("name", sorted(_NEW_IN_PR_37))
def test_a_metric_of_the_engine_threads_time_reads_its_rows(name):
    """Each file new in PR 37: its number from a window of known rows,
    nothing from a parent that has no such row, column or clock, its
    entry in the manifest as the issue gave it."""
    from benchmarks import run as runmod
    assert runmod.read_metric(_BENCH, name, _window()) == pytest.approx(
        _NEW_IN_PR_37[name])
    assert runmod.read_metric(_BENCH, name, _window(False)) is None
    assert runmod.read_metric(_BENCH, name, {}) is None
    whole = runmod.load_manifest()
    entry, = [m for m in whole["per_layer"] if m["name"] == name]
    serve = [w["name"] for w in whole["workloads"]
             if _runner_of(whole, w["name"]).startswith("serve_http")]
    refill, = [m for m in whole["per_layer"]
               if m["name"] == "slot_refill_ms"]
    assert entry["workloads"] == (
        refill["workloads"] if name == "slot_refill_starved_share"
        else serve)
    assert (entry["moves"], entry["better"]) == ("out_tok_s", "lower")
    assert entry["layer"].startswith(
        "service" if name == "consumer_loop_cpu_share"
        else "engine host loop")
    # behind the entries accepted before them (a later PR's entries go
    # behind these in turn)
    first = min(i for i, m in enumerate(whole["per_layer"])
                if m["name"] in _NEW_IN_PR_37)
    assert not {m["name"] for m in whole["per_layer"][:first]} \
        & set(_NEW_IN_PR_37), "new entries go behind the accepted ones"
    assert {m["name"] for m in whole["per_layer"][first:first + 9]} \
        == set(_NEW_IN_PR_37)


_NEW_IN_PR_40 = {"expert_matmul_roofline.lfm2moe": "experts",
                 "paged_kernel_roofline.packed": "paged_kernel",
                 "decode_step_roofline.lfm2moe": "step"}
_LFM2_CELL = "lfm2moe24b_decode_sat"


@pytest.mark.parametrize("name", sorted(_NEW_IN_PR_40))
def test_a_metric_file_new_in_pr_40_names_a_reader_and_arguments_that_exist(
        name):
    """Each file names a reader module with a `read` that takes the
    file's arguments, a decode program the engine has and, where it
    sums a kernel's time, a kernel the program calls by that name; its
    entry lists the one cell, behind every accepted entry."""
    import inspect
    import json
    import re
    from benchmarks import run as runmod
    from ray_tpu.ops.pallas import paged_attention
    from ray_tpu.serve.llm.engine import LLMEngine
    with open(os.path.join(_BENCH, "metrics", name + ".json")) as f:
        spec = json.load(f)
    readers = os.path.join(_BENCH, "readers")
    if readers not in sys.path:
        sys.path.insert(0, readers)
    read = importlib.import_module(spec["reader"]).read
    accepted = inspect.signature(read).parameters
    assert set(spec["args"]) <= set(accepted), (name, spec["args"])
    assert spec["args"]["what"] == _NEW_IN_PR_40[name]
    assert re.search(spec["args"]["module_re"],
                     "jit_" + LLMEngine._decode_paged_step.__name__)
    kernel = spec["args"].get("name_re")
    if _NEW_IN_PR_40[name] == "paged_kernel":
        assert re.search(kernel,
                         paged_attention.paged_decode_attention.__name__)
    elif _NEW_IN_PR_40[name] == "experts":
        from jax.experimental.pallas.ops.tpu.megablox import gmm
        assert re.search(kernel, gmm.__name__)
    else:
        assert kernel is None
    whole = runmod.load_manifest()
    entry, = [m for m in whole["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [_LFM2_CELL]
    assert whole["workloads"][-1]["name"] == _LFM2_CELL
    assert whole["configs"][-1]["name"] == whole["workloads"][-1]["config"]
    names = [m["name"] for m in whole["per_layer"]]
    assert set(names[-3:]) == set(_NEW_IN_PR_40)


def test_the_lfm2moe_cell_is_in_what_every_saturated_serve_cell_reports():
    """Every per-layer list that names the two saturated cells before
    it names this one too, at its end; the expert counters' and the
    slot state's lists as well; and no list of another model's cost
    arithmetic does."""
    from benchmarks import run as runmod
    whole = runmod.load_manifest()
    for m in whole["end_to_end"] + whole["per_layer"]:
        w = m.get("workloads", [])
        if {"sarvam105b_decode_sat", "olmohybrid7b_decode_sat"} <= set(w):
            assert w[-1] == _LFM2_CELL, m["name"]
    by_name = {m["name"]: m for m in whole["per_layer"]}
    for name in ("moe_dev_share", "moe_expert_load_max_over_mean",
                 "moe_pad_row_share", "decode_live_state_share"):
        assert by_name[name]["workloads"][-1] == _LFM2_CELL, name
    for name in ("paged_kernel_roofline", "decode_step_roofline",
                 "expert_matmul_roofline", "decode_step_roofline.moe",
                 "expert_matmul_roofline.share", "latent_kernel_roofline",
                 "gdn_kernel_roofline", "paged_kernel_roofline.hybrid",
                 "moe_local_assignment_share"):
        assert _LFM2_CELL not in by_name[name]["workloads"], name
