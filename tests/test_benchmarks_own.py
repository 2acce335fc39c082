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
        # (a latent-attention file may publish num_key_value_heads too:
        # what it caches is its kv_lora_rank)
        with open(os.path.join(root, entry["file"])) as f:
            cfg = json.load(f)
        # (nor is a hybrid whose full layers' heads are wider than
        # hidden_size / heads: its section is replica_solar's)
        # (nor one whose layers are a pattern of state-space, attention
        # and expert sub-layers: replica_nemotron's)
        return ("num_key_value_heads" in cfg and "kv_lora_rank" not in cfg
                and "linear_attn_config" not in cfg
                and "hybrid_override_pattern" not in cfg)
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
    from ray_tpu.observability.profiler import (GC_SPANS, LOCK_SPANS,
                                                PROCESS_SPANS)
    # counters a model with residual streams leaves behind its
    # expert layers' (the engine seeds them from `model.step_stats`)
    from ray_tpu.ops.hyper_connections import HC_STATS
    from ray_tpu.serve.llm import LLMEngine, LLMEngineConfig, engine
    readers = os.path.join(_BENCH, "readers")
    if readers not in sys.path:
        sys.path.insert(0, readers)
    import stats_delta
    stats_run = globals()["RUN"]
    seeded = set(engine._LOOP_SPANS + engine._REQUEST_SPANS
                 + engine._RUNTIME_SPANS + engine._STEP_SPANS + GC_SPANS
                 + LOCK_SPANS + PROCESS_SPANS)
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
                assert (len(path) == 1 and (path[0].startswith(
                    ("decode_", "prefill_")) or path[0] in HC_STATS)) \
                    or stats_delta._at(fresh, path) is not None, (
                        name, path)
        got = runmod.read_metric(_BENCH, name[:-5], stats_run)
        assert got is None or isinstance(got, float), name
        if name[:-5] in _NEW_IN_PR_54:
            assert isinstance(runmod.read_metric(
                _BENCH, name[:-5], _lock_and_loop_window()), float), name
    assert len(found) >= 21 + len(_NEW_IN_PR_54) and set(found) <= listed
    assert set(_NEW_IN_PR_54) <= set(found)
    assert {"decode_live_state_share", "hc_unconverged_share",
            "hc_clamped_share"} <= set(found)


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


# PR 54's, at the end of `per_layer`: name -> (what `_lock_and_loop_
# window` reads, unit, better, source, the layer's first words)
# PR 56's cell and its seven metric files, behind everything (the cases
# further down hold them; the older cases count them off the tail)
_NEMOTRON_CELL = "nemotron120b_decode_sat"
_NEW_IN_PR_56 = {"ssm_kernel_roofline": "ssm_kernel",
                 "ssm_kernel_dev_share": None,
                 "ssm_scan_dev_share": "ssm_scan",
                 "expert_matmul_roofline.nemotron": "experts",
                 "paged_kernel_roofline.nemotron": "paged_kernel",
                 "decode_step_roofline.nemotron": "step",
                 "moe_dev_share.nemotron": None}

_NEW_IN_PR_54 = {
    "engine_lock_reacquire_us": (
        40.0, "us", "lower", "program_span", "engine host loop"),
    "engine_lock_long_wait_share": (
        20.0, "%", "lower", "program_span", "engine host loop"),
    "engine_release_still_share": (
        100.0 * 5 / 7, "%", "lower", "program_span", "engine host loop"),
    "engine_leaves_released_per_call": (
        35.0, "leaves", "lower", "program_counter", "engine host loop"),
    "stream_replies_per_token": (
        1.2, "replies", "lower", "program_counter", "service"),
    "consumer_cpu_us_per_reply": (
        100.0, "us", "lower", "program_counter", "service"),
    "consumer_telemetry_share": (
        10.0, "%", "lower", "program_span", "service"),
    "consumer_reply_share": (20.0, "%", "lower", "program_span", "service"),
    "consumer_named_share": (51.0, "%", "higher", "program_span", "service"),
    "deliver_items_per_batch": (
        51.0, "items", "higher", "program_counter", "service")}


def _lock_and_loop_window(with_rows=True):
    """Two readings of `get_stats()` 1 000 step calls apart: a probe
    every 8th call that waited 40 us, one in five over 1 ms; 7 ms to
    release 35 leaves, 2 of them on the CPU; 50 tokens a step in 60
    `stream_next` replies and one hand-over of 51 items; the consumers'
    loop 6 ms of CPU a step, of which the reply 1.2, the telemetry and
    `stream_next` 0.6 each, resolve and the chunks' puts 0.3 each, the
    hand-over 0.06. A parent's window has the release's row, the
    hand-over's counters and the clocks, and nothing else of these."""
    def reading(k):
        us = 1_000
        out = {"tokens_generated": 50 * k, "deliver_items": 51 * k,
               "deliver_batches": k,
               "spans": {"step.release": [k, 7_000 * us * k, 0,
                                          2_000 * us * k]},
               "threads": {"consumers": 6_000 * us * k,
                           "wall_ns": 10_000 * us * k}}
        if with_rows:
            out["step_leaves_released"] = 35 * k
            out["spans"].update({
                "lock.reacquire": [k // 8, k // 8 * 40 * us, 0, 0],
                "lock.reacquire.lost": [k // 16, k // 16 * 70 * us, 0, 0],
                "lock.reacquire.long": [k // 40, k // 40 * 3_000 * us,
                                        0, 0],
                "actor.call.resolve": [60 * k, 300 * us * k, 0, 0],
                "actor.call.reply": [60 * k, 1_200 * us * k, 0, 0],
                "actor.call.telemetry": [60 * k, 600 * us * k, 0, 0],
                "replica.stream_next": [60 * k, 600 * us * k, 0, 0],
                "replica.stream_put": [52 * k, 300 * us * k, 0, 0],
                "consumer.deliver": [k, 60 * us * k, 0, 0]})
        return out
    return {"stats0": reading(1_000), "stats1": reading(2_000)}


@pytest.mark.parametrize("name", sorted(_NEW_IN_PR_54))
def test_a_metric_of_the_lock_or_the_actor_loop_reads_its_rows(name):
    """Each file new in PR 54: its number from a window of known rows,
    nothing from a parent that has no such row or counter (but for the
    two that read what the parent already reports), its entry at the
    end of the manifest as the issue gave it."""
    from benchmarks import run as runmod
    reads, unit, better, source, layer = _NEW_IN_PR_54[name]
    assert runmod.read_metric(_BENCH, name, _lock_and_loop_window()) \
        == pytest.approx(reads)
    on_parent = runmod.read_metric(_BENCH, name,
                                   _lock_and_loop_window(False))
    if name in ("engine_release_still_share", "deliver_items_per_batch"):
        assert on_parent == pytest.approx(reads)
    else:
        assert on_parent is None
    assert runmod.read_metric(_BENCH, name, {}) is None
    whole = runmod.load_manifest()
    entry, = [m for m in whole["per_layer"] if m["name"] == name]
    serve = [w["name"] for w in whole["workloads"]
             if _runner_of(whole, w["name"]).startswith("serve_http")]
    assert len(serve) == 9 and entry["workloads"] == serve
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert (entry["unit"], entry["better"], entry["source"],
            entry["moves"]) == (unit, better, source, "out_tok_s")
    assert entry["layer"].startswith(layer)
    accepted = {m["layer"] for m in whole["per_layer"]
                if m["name"] not in _NEW_IN_PR_54
                and m["name"] not in _NEW_IN_PR_56}
    assert entry["layer"] in accepted       # letter for letter
    tail = whole["per_layer"][:-len(_NEW_IN_PR_56)]
    assert {m["name"] for m in tail[-len(_NEW_IN_PR_54):]} \
        == set(_NEW_IN_PR_54)


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
    # the seventh cell and the sixth configuration; PR 48's and PR 52's
    # go behind
    assert whole["workloads"][6]["name"] == _LFM2_CELL
    assert whole["configs"][5]["name"] == whole["workloads"][6]["config"]
    names = [m["name"] for m in whole["per_layer"]]
    at = len(names) - len(_NEW_IN_PR_56) - len(_NEW_IN_PR_54) \
        - len(_NEW_IN_PR_53) - len(_NEW_IN_PR_52) - len(_NEW_IN_PR_48) - 3
    assert set(names[at:at + 3]) == set(_NEW_IN_PR_40)


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
            assert w[w.index("olmohybrid7b_decode_sat") + 1] \
                == _LFM2_CELL, m["name"]
    by_name = {m["name"]: m for m in whole["per_layer"]}
    for name in ("moe_dev_share", "moe_expert_load_max_over_mean",
                 "moe_pad_row_share", "decode_live_state_share"):
        assert _LFM2_CELL in by_name[name]["workloads"][-4:], name
    for name in ("paged_kernel_roofline", "decode_step_roofline",
                 "expert_matmul_roofline", "decode_step_roofline.moe",
                 "expert_matmul_roofline.share", "latent_kernel_roofline",
                 "gdn_kernel_roofline", "paged_kernel_roofline.hybrid",
                 "moe_local_assignment_share"):
        assert _LFM2_CELL not in by_name[name]["workloads"], name


_NEW_IN_PR_48 = {"hc_kernel_dev_share": None,
                 "hc_kernel_roofline": "hc_kernels",
                 "hc_unconverged_share": None, "hc_clamped_share": None,
                 "latent_kernel_roofline.xing": "latent_kernel",
                 "expert_matmul_roofline.xing": "experts",
                 "decode_step_roofline.xing": "step"}
_XING_CELL = "xing29b_decode_sat"


def _xing_window(counters=True, kernels=True):
    """What a traced run of the cell hands a reader: two readings of
    `get_stats()` 1 000 decode steps apart (129 rows through 12
    sub-layers a step, 3 rows in a thousand unconverged, none clamped),
    a reduced trace of 100 decode runs, the model section, the peaks."""
    import json
    from benchmarks.harness import replica_xing
    from benchmarks.harness.peaks import PEAKS
    with open(os.path.join(_BENCH, "configs",
                           "xing4.0-29b-a4b-serve-l6.json")) as f:
        cfg = json.load(f)

    def reading(k):
        out = {"decode_steps": k, "prefill_calls": k // 50,
               "decode_pages_live": k * 128 * 12,
               "moe_assignments": k * 128 * 4 * 5, "moe_rows": k * 128 * 5,
               "moe_experts_touched": k * 64 * 5,
               "moe_expert_load_max": k * 12 * 5, "moe_pad_rows": k * 5,
               "moe_routed_assignments": k * 128 * 4 * 5}
        if counters:
            out.update(hc_rows=k * 128 * 12, hc_clamped_rows=0,
                       hc_unconverged_rows=k * 128 * 12 * 3 // 1000)
        return out
    ops = {"gmm": 1.2, "latent_decode_attention": 0.2, "fusion": 0.5}
    if kernels:
        ops.update(hc_mix_in=0.04, hc_mix_out=0.06)
    return {"stats0": reading(1000), "stats1": reading(2000),
            "trace": {"busy_s": 2.0, "window_s": 2.1, "ops": ops,
                      "modules": {"jit__decode_paged_step":
                                  {"count": 100, "seconds": 1.9}}},
            "peaks": PEAKS["TPU v5e"], "config": cfg,
            "model": replica_xing.model_section(cfg),
            "trace_contexts": [900] * 128}


@pytest.mark.parametrize("name", sorted(_NEW_IN_PR_48))
def test_a_metric_file_new_in_pr_48_reads_its_window_and_nothing_else(name):
    """Each file names a reader with a `read` that takes the file's
    arguments, the decode program the engine has and, where it sums a
    kernel's time, a kernel the program calls by that name; from a
    window of known counters and kernel times it reads a share under
    100 %; from a program without the counters, a trace without the
    kernels, another family's model section or an empty run it reads
    None and does not raise (what the parent's traced runs hand it);
    its entry lists the one cell, behind every accepted entry."""
    import inspect
    import json
    import re
    from benchmarks import run as runmod
    from ray_tpu.ops.pallas import hyper_connections as kernels
    from ray_tpu.ops.pallas.latent_attention import latent_decode_attention
    from ray_tpu.serve.llm.engine import LLMEngine
    with open(os.path.join(_BENCH, "metrics", name + ".json")) as f:
        spec = json.load(f)
    readers = os.path.join(_BENCH, "readers")
    if readers not in sys.path:
        sys.path.insert(0, readers)
    read = importlib.import_module(spec["reader"]).read
    assert set(spec["args"]) <= set(inspect.signature(read).parameters)
    what = _NEW_IN_PR_48[name]
    kernel = spec["args"].get("name_re")
    if what is not None:
        assert spec["args"]["what"] == what
    if what == "kda_scan":
        from benchmarks.harness import replica_solar
        from ray_tpu.ops import gated_deltanet
        # the loop the replica looks for is a `lax.scan` over chunks
        # inside the engine's prefill program
        assert "jax.lax.scan(body" in inspect.getsource(
            gated_deltanet._chunk_scan_channel)
        assert re.search(
            inspect.signature(replica_solar.chunk_scan_seconds)
            .parameters["prefill_re"].default,
            "jit_" + LLMEngine._prefill_paged_step.__name__)
    elif what is not None:
        assert re.search(spec["args"]["module_re"],
                         "jit_" + LLMEngine._decode_paged_step.__name__)
    if name.startswith("hc_kernel"):
        text = inspect.getsource(kernels)
        assert re.search(kernel, "hc_mix_in") and 'name="hc_mix_in"' in text
        assert re.search(kernel, "hc_mix_out") \
            and 'name="hc_mix_out"' in text
    elif what == "latent_kernel":
        assert re.search(kernel, latent_decode_attention.__name__)
    elif what == "experts":
        from jax.experimental.pallas.ops.tpu.megablox import gmm
        assert re.search(kernel, gmm.__name__)
    got = runmod.read_metric(_BENCH, name, _xing_window())
    assert isinstance(got, float) and 0.0 <= got < 100.0, (name, got)
    if name == "hc_unconverged_share":
        assert got == pytest.approx(0.3, abs=0.01)
    if name == "hc_kernel_dev_share":
        assert got == pytest.approx(5.0)
    # rule (beta): a parent's program has no such counter, kernel or
    # model section, and its traced run still gives its result
    lacking = _xing_window(counters=False, kernels=False)
    if name.startswith("hc_"):
        if name == "hc_kernel_dev_share":
            assert runmod.read_metric(_BENCH, name, lacking) == 0.0
        else:
            assert runmod.read_metric(_BENCH, name, lacking) is None
    other = dict(_xing_window(), model={"hidden_size": 4096,
                                        "kv_lora_rank": 512})
    if what is not None:
        assert runmod.read_metric(_BENCH, name, other) is None
    assert runmod.read_metric(_BENCH, name, {}) is None
    whole = runmod.load_manifest()
    entry, = [m for m in whole["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [_XING_CELL]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert (entry["unit"], entry["moves"]) == ("%", "out_tok_s")
    assert entry["source"] == ("program_counter" if what is None
                               and not name.startswith("hc_kernel")
                               else "device_trace")
    # the eighth cell and the seventh configuration; PR 52's go behind
    assert whole["workloads"][7]["name"] == _XING_CELL
    assert whole["configs"][6]["name"] == whole["workloads"][7]["config"]
    names = [m["name"] for m in whole["per_layer"]]
    at = len(names) - len(_NEW_IN_PR_56) - len(_NEW_IN_PR_54) \
        - len(_NEW_IN_PR_53) - len(_NEW_IN_PR_52) - 7
    assert set(names[at:at + 7]) == set(_NEW_IN_PR_48)


@pytest.mark.parametrize("name, reads", [
    ("moe_expert_load_max_over_mean", 64 * 12 / (128 * 4)),
    ("moe_pad_row_share", 100 / 129),
    ("moe_local_assignment_share", 100.0),
    ("moe_dev_share", 60.0), ("latent_kernel_dev_share", 10.0)])
def test_an_accepted_expert_metric_reads_the_xing_window(name, reads):
    """The accepted readers of the lists the cell was appended to find
    their keys in this model's section (the published file says
    n_routed_experts where `moe_counter` reads num_experts: the first
    check of PR 48 was refused for a traced line without
    `moe_expert_load_max_over_mean`) and in its counters."""
    from benchmarks import run as runmod
    assert runmod.read_metric(_BENCH, name, _xing_window()) \
        == pytest.approx(reads)


def test_the_xing_cell_is_in_what_every_saturated_serve_cell_reports():
    """The eighth cell of (since PR 56) ten, one of them on four chips.
    Every list that names the sarvam cell and is not read by that
    model's own cost arithmetic names this one too, behind every cell
    accepted before it (a later PR's cell goes behind this one in
    turn); `out_tok_s` as well; no list of another model's cost
    arithmetic or state does."""
    from benchmarks import run as runmod
    whole = runmod.load_manifest()
    assert len(whole["workloads"]) == 10 and len(whole["configs"]) == 9
    assert [w["name"] for w in whole["workloads"] if w["chips"] == 4] \
        == ["mistral7b_train_fsdp2_tp2"]
    own = {"latent_kernel_roofline", "expert_matmul_roofline.share",
           "decode_step_roofline.latent_moe"}

    def last_of_its_day(w):
        return [c for c in w
                if c not in (_SOLAR_CELL, _NEMOTRON_CELL)][-1]
    for m in whole["end_to_end"] + whole["per_layer"]:
        w = m.get("workloads", [])
        if "sarvam105b_decode_sat" in w and m["name"] not in own:
            assert last_of_its_day(w) == _XING_CELL, m["name"]
        elif m["name"] not in _NEW_IN_PR_48:
            assert _XING_CELL not in w, m["name"]
    out, = [m for m in whole["end_to_end"] if m["name"] == "out_tok_s"]
    assert last_of_its_day(out["workloads"]) == _XING_CELL
    cell = whole["workloads"][7]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "xing4.0-29b-a4b-serve-l6", "decode_sat_xing", 1)
    config = whole["configs"][6]
    assert config["reduced"] == ["num_hidden_layers",
                                 "first_k_dense_replace"]
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200


_NEW_IN_PR_52 = {"kda_kernel_roofline": "kda_kernel",
                 "kda_kernel_dev_share": None,
                 "kda_scan_dev_share": "kda_scan",
                 "moe_dev_share.solar": None,
                 "expert_matmul_roofline.solar": "experts",
                 "paged_kernel_roofline.solar": "paged_kernel",
                 "decode_step_roofline.solar": "step"}
_SOLAR_CELL = "solar250b_decode_sat"
# PR 53's, behind them: a share of the engine's own counters
_NEW_IN_PR_53 = ("prefill_live_chunk_share",)


def _solar_window(counters=True, kernels=True):
    """What a traced run of the cell hands a reader: two readings of
    `get_stats()` 1 000 decode steps apart (128 rows decoding of 129,
    three delta-rule layers' state rows, 14 live pages a row in the one
    full layer, 3.2 rows an expert held in 4 layers), a reduced trace of
    100 decode runs of 16 ms, the model section, the peaks."""
    import json
    from benchmarks.harness import replica_solar
    from benchmarks.harness.peaks import PEAKS
    with open(os.path.join(_BENCH, "configs",
                           "solar-open2-250b-serve-ep8-l4.json")) as f:
        cfg = json.load(f)

    def reading(k):
        out = {"decode_steps": k, "prefill_calls": k // 50,
               "decode_pages_live": k * 128 * 14,
               "decode_pages_window": k * 129 * 32,
               "moe_assignments": k * 128 * 4, "moe_rows": k * 128 * 4,
               "moe_experts_touched": k * 38 * 4,
               "moe_expert_load_max": k * 9 * 4, "moe_pad_rows": k * 4,
               "moe_routed_assignments": k * 128 * 8 * 4}
        if counters:
            out.update(decode_state_rows_live=k * 128 * 3,
                       decode_state_rows_window=k * 129 * 3,
                       # a 1 024-wide call of one row of 700 tokens in
                       # three delta-rule layers, chunks of 64
                       prefill_chunks_live=(k // 50) * 11 * 3,
                       prefill_chunks_window=(k // 50) * 16 * 3)
        return out
    ops = {"gmm": 0.7, "paged_decode_attention": 0.07, "fusion": 0.4,
           "sort": 0.01}
    scan = {}
    if kernels:
        # the chunk scan's loop and the grouped matmul's binary search
        # are both a `while`: the replica tells them apart
        # (`replica_solar.chunk_scan_seconds`)
        ops.update({"kda_decode_step": 0.5, "while": 0.11})
        scan = {"kda_scan_s": 0.1}
    return {"stats0": reading(1000), "stats1": reading(2000),
            "trace": {"busy_s": 2.0, "window_s": 2.02, "ops": ops, **scan,
                      "modules": {"jit__decode_paged_step":
                                  {"count": 100, "seconds": 1.6}}},
            "peaks": PEAKS["TPU v5e"], "config": cfg,
            "model": replica_solar.model_section(cfg),
            "trace_contexts": [900] * 128}


@pytest.mark.parametrize("name", sorted(_NEW_IN_PR_52))
def test_a_metric_file_new_in_pr_52_reads_its_window_and_nothing_else(name):
    """As PR 48's case: each file names a reader with a `read` that
    takes the file's arguments, the decode program the engine has and,
    where it sums a kernel's time, a kernel the program calls by that
    name; from a window of known counters and kernel times it reads a
    share under 100 %; from a program without the counters, a trace
    without the kernels, another family's model section or an empty run
    it reads None (a plain share of the trace: 0) and does not raise
    (what the parent's traced runs hand it); its entry lists the one
    cell, behind every accepted entry."""
    import inspect
    import json
    import re
    from benchmarks import run as runmod
    from ray_tpu.ops.pallas import gdn_decode, paged_attention
    from ray_tpu.serve.llm.engine import LLMEngine
    with open(os.path.join(_BENCH, "metrics", name + ".json")) as f:
        spec = json.load(f)
    readers = os.path.join(_BENCH, "readers")
    if readers not in sys.path:
        sys.path.insert(0, readers)
    read = importlib.import_module(spec["reader"]).read
    assert set(spec["args"]) <= set(inspect.signature(read).parameters)
    what = _NEW_IN_PR_52[name]
    kernel = spec["args"].get("name_re")
    if what is not None:
        assert spec["args"]["what"] == what
    if what == "kda_scan":
        from benchmarks.harness import replica_solar
        from ray_tpu.ops import gated_deltanet
        # the loop the replica looks for is a `lax.scan` over chunks
        # inside the engine's prefill program
        assert "jax.lax.scan(body" in inspect.getsource(
            gated_deltanet._chunk_scan_channel)
        assert re.search(
            inspect.signature(replica_solar.chunk_scan_seconds)
            .parameters["prefill_re"].default,
            "jit_" + LLMEngine._prefill_paged_step.__name__)
    elif what is not None:
        assert re.search(spec["args"]["module_re"],
                         "jit_" + LLMEngine._decode_paged_step.__name__)
    if name.startswith("kda_kernel"):
        assert re.search(kernel, gdn_decode.kda_decode_step.__name__)
        # the one kernel runs under the name its caller hands it
        assert '"kda_decode_step"' in inspect.getsource(
            gdn_decode.kda_decode_step.__wrapped__)
        # and not the sibling's kernel, which its own metrics read
        assert not re.search(kernel, gdn_decode.gdn_decode_step.__name__)
    elif what == "paged_kernel":
        assert re.search(kernel,
                         paged_attention.paged_decode_attention.__name__)
    elif what == "experts":
        from jax.experimental.pallas.ops.tpu.megablox import gmm
        assert re.search(kernel, gmm.__name__)
    got = runmod.read_metric(_BENCH, name, _solar_window())
    assert isinstance(got, float) and 0.0 < got < 100.0, (name, got)
    if name == "kda_kernel_dev_share":
        assert got == pytest.approx(25.0)
    if name == "kda_scan_dev_share":
        assert got == pytest.approx(5.0)
    if name == "moe_dev_share.solar":
        assert got == pytest.approx(35.0)
        from jax.experimental.pallas.ops.tpu.megablox import gmm
        assert re.search(kernel, gmm.__name__)
    if name == "kda_kernel_roofline":
        # 128 rows x 3 layers x 2 x 4 MiB over 819 GB/s, in 5 ms a run
        assert got == pytest.approx(
            100 * 128 * 3 * 2 * 4 * 2 ** 20 / 819e9 / 5e-3, rel=1e-3)
    # rule (beta): a parent's program has no such counter or kernel,
    # and its traced run still gives its result
    lacking = _solar_window(counters=False, kernels=False)
    if name.startswith("kda_"):
        assert runmod.read_metric(_BENCH, name, lacking) in (None, 0.0)
        assert (runmod.read_metric(_BENCH, name, lacking) is None) \
            == (what is not None)
    other = dict(_solar_window(), model={"hidden_size": 4096,
                                         "conv_L_cache": 3})
    if what is not None:
        assert runmod.read_metric(_BENCH, name, other) is None
    assert runmod.read_metric(_BENCH, name, {}) is None
    whole = runmod.load_manifest()
    entry, = [m for m in whole["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [_SOLAR_CELL]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert (entry["unit"], entry["moves"], entry["source"]) \
        == ("%", "out_tok_s", "device_trace")
    assert whole["workloads"][8]["name"] == _SOLAR_CELL
    assert whole["configs"][7]["name"] == whole["workloads"][8]["config"]
    names = [m["name"] for m in whole["per_layer"]]
    at = names.index("decode_step_roofline.xing") + 1
    assert set(names[at:at + 7]) == set(_NEW_IN_PR_52)


def test_the_chunk_scans_loops_are_told_from_the_searches(monkeypatch):
    """Both are a `while` on the trace. In a run of a prefill program the
    longest `while` operations, one a delta-rule layer, are the chunk
    scan's; the searches inside the grouped matmuls there, and every
    `while` of a decode program, are not counted."""
    from types import SimpleNamespace as NS
    import jax.profiler
    from benchmarks.harness import replica_solar, trace_reduce

    def ev(name, start_us, dur_us):
        return NS(name=name, start_ns=start_us * 1e3, duration_ns=dur_us * 1e3)
    ops = [ev("%while.79 = (f32[2,64]) while(%tuple.1)", 100, 1000),
           ev("%while.80 = (f32[2,64]) while(%tuple.2)", 1200, 2000),
           ev("%while.81 = (f32[2,64]) while(%tuple.3)", 3300, 1500)]
    ops += [ev(f"%while.{82 + i} = (s32[40]) while(%t)", 5000 + 20 * i, 10)
            for i in range(4)]
    ops += [ev("%fusion.5 = f32[8] fusion(%p)", 6000, 3000),
            ev("%while.3 = (s32[40]) while(%t)", 10100, 10),
            ev("%while.4 = (s32[40]) while(%t)", 10200, 12)]
    plane = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            ev("jit__prefill_paged_step(123)", 0, 10000),
            ev("jit__decode_paged_step(456)", 10000, 2000)]),
        NS(name="XLA Ops", events=ops)])
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: d)
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: NS(
                            planes=[plane, NS(name="/host:CPU", lines=[])])))
    assert replica_solar.chunk_scan_seconds("x", 3) \
        == pytest.approx(4.5e-3)
    # a program without such layers, or a trace without a device
    assert replica_solar.chunk_scan_seconds("x", 0) == 0.0
    plane.name = "/host:other"
    assert replica_solar.chunk_scan_seconds("x", 3) == 0.0


@pytest.mark.parametrize("name, reads", [
    ("moe_expert_load_max_over_mean", 40 * 9 / 128),
    ("moe_pad_row_share", 100 / 129),
    ("moe_local_assignment_share", 12.5),
    ("decode_live_state_share", 100 * 128 / 129),
    ("decode_live_page_share", 100 * 128 * 14 / (129 * 32)),
    ("attention_kernel_dev_share", 3.5)])
def test_an_accepted_metric_reads_the_solar_window(name, reads):
    """Rule (gamma): the accepted readers of the lists the cell was
    appended to find their keys in this model's section (the published
    file says n_routed_experts where `moe_counter` reads num_experts)
    and in its counters. `moe_dev_share` is not among them: it counts
    `while` by name, and in this cell the chunk scan's loop over chunks
    is one (`moe_dev_share.solar` reads the layer by its scope)."""
    from benchmarks import run as runmod
    assert runmod.read_metric(_BENCH, name, _solar_window()) \
        == pytest.approx(reads)


def test_the_solar_cell_is_in_what_a_saturated_serve_cell_with_experts_and_state_reports():  # noqa: E501
    """The ninth cell of (since PR 56) ten, one of them on four chips.
    Every list that names the
    LFM2-MoE cell (a hybrid with experts and slot state) and is not read
    by that model's own cost arithmetic names this one too, at its end,
    `moe_local_assignment_share` (a share) and `out_tok_s` as well; no
    list of another model's cost arithmetic does; and the traced line's
    metrics are `cell_metrics`'."""
    from benchmarks import run as runmod
    whole = runmod.load_manifest()
    own = {"expert_matmul_roofline.lfm2moe", "paged_kernel_roofline.packed",
           "decode_step_roofline.lfm2moe",
           # matches `while` by name: here the chunk scan's loop too
           "moe_dev_share"}
    for m in whole["end_to_end"] + whole["per_layer"]:
        w = m.get("workloads", [])
        if ("lfm2moe24b_decode_sat" in w and m["name"] not in own) \
                or m["name"] in _NEW_IN_PR_52 \
                or m["name"] in _NEW_IN_PR_53 \
                or m["name"] == "moe_local_assignment_share":
            # (PR 56's cell goes behind it in turn)
            assert [c for c in w if c != _NEMOTRON_CELL][-1] \
                == _SOLAR_CELL, m["name"]
        else:
            assert _SOLAR_CELL not in w, m["name"]
    cell, config = whole["workloads"][8], whole["configs"][7]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (_SOLAR_CELL, "solar-open2-250b-serve-ep8-l4",
            "decode_sat_solar", 1)
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    assert "8x a deployment chip's rows" in cell["why"] \
        and "4.8 rows an expert" in cell["why"] and "192 slots" in cell["why"]
    # every per-layer metric the cell is listed for reads the synthetic
    # window (what a traced line is held against)
    run = _solar_window()
    run.update(streams=[], t0=0.0, t1=1.0, late_ms=[1.0],
               compiles_in_window=0, backlog_end=0)
    silent = []
    for m in runmod.cell_metrics(whole, _SOLAR_CELL, "per_layer"):
        with open(os.path.join(_BENCH, "metrics",
                               m["name"] + ".json")) as f:
            reader = __import__("json").load(f)["reader"]
        if reader in ("solar_roofline", "trace_share", "moe_counter",
                      "moe_local_share"):
            if runmod.read_metric(_BENCH, m["name"], run) is None:
                silent.append(m["name"])
    assert silent == []


def test_prefill_live_chunk_share_reads_the_engines_counters():
    """PR 53's metric: chunks of the delta-rule layers' chunkwise form
    that hold a prompt token over those the padded calls span, from the
    window's two readings of `get_stats()`; listed for the solar cell
    alone, at the end of the list, and silent (no number, no error)
    against a program that does not count them, as the parent commit's
    does not."""
    from benchmarks import run as runmod
    whole = runmod.load_manifest()
    # PR 54's go behind it
    entry = whole["per_layer"][-1 - len(_NEW_IN_PR_54)
                               - len(_NEW_IN_PR_56)]
    # (PR 56's cell counts its state-space layers' chunks by the same
    # counters: appended)
    assert entry == {"name": "prefill_live_chunk_share", "unit": "%",
                     "better": "higher", "source": "program_counter",
                     "layer": "kernels (ops/pallas)", "moves": "out_tok_s",
                     "workloads": [_SOLAR_CELL, _NEMOTRON_CELL]}
    got = runmod.read_metric(_BENCH, entry["name"], _solar_window())
    assert got == pytest.approx(100 * 11 / 16)
    assert runmod.read_metric(_BENCH, entry["name"],
                              _solar_window(counters=False)) is None


def test_the_solar_cell_rehearses_through_run_py(tmp_path):
    """`run.py --rehearse` of the cell on the CPU at toy widths: the
    family's runner, replica, reference and traffic files are found by
    name, the engine serves the mix, the check against
    `reference_solar` passes, and the line holds the cell's metrics
    without a device number."""
    import json
    import subprocess
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(_BENCH, "run.py"), "--workload",
         _SOLAR_CELL, "--rehearse", "--seed", "5200000011", "--seconds",
         "3", "--trace", "0", "--out", str(tmp_path)],
        cwd=_ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"] and not line["failed"]
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"out_tok_s", "setup_s"}
    assert all(v["value"] is None for v in line["metrics"].values())
    ref = line["checks"]["reference"]
    assert ref["ok"] and ref["not_followed"] == 0 and ref["layers"] == 4
    assert ref["prefill_bucket"] > ref["prompt_len"]


# ---- PR 56: the Nemotron-3-Super cell ------------------------------------

def _nemotron_window(counters=True, kernels=True):
    """What a traced run of the cell hands a reader: two readings of
    `get_stats()` 1 000 decode steps apart (180 rows decoding of 193,
    five Mamba-2 layers' state rows, 14 live pages a row in the one
    attention layer, 7.7 rows an expert held in 5 expert layers), a
    reduced trace of 160 decode runs of 22.5 ms, the model section, the
    peaks."""
    import json
    from benchmarks.harness import replica_nemotron
    from benchmarks.harness.peaks import PEAKS
    with open(os.path.join(
            _BENCH, "configs",
            "nemotron-3-super-120b-serve-ep8-l11.json")) as f:
        cfg = json.load(f)

    def reading(k):
        out = {"decode_steps": k, "prefill_calls": k // 50,
               "decode_pages_live": k * 180 * 14,
               "decode_pages_window": k * 193 * 32,
               "moe_assignments": k * 495 * 5, "moe_rows": k * 180 * 5,
               "moe_experts_touched": k * 64 * 5,
               "moe_expert_load_max": k * 16 * 5, "moe_pad_rows": k * 13 * 5,
               "moe_routed_assignments": k * 180 * 22 * 5}
        if counters:
            out.update(decode_state_rows_live=k * 180 * 5,
                       decode_state_rows_window=k * 193 * 5,
                       # a 1 024-wide call of one row of 700 tokens in
                       # five Mamba-2 layers, chunks of 128
                       prefill_chunks_live=(k // 50) * 6 * 5,
                       prefill_chunks_window=(k // 50) * 8 * 5)
        return out
    ops = {"gmm": 0.8, "paged_decode_attention": 0.12, "fusion": 1.0,
           "sort": 0.02}
    scan = {}
    if kernels:
        ops["ssm_decode_step"] = 1.6
        scan = {"ssm_scan_s": 0.1}      # `replica_nemotron.scope_seconds`
    return {"stats0": reading(1000), "stats1": reading(2000),
            "trace": {"busy_s": 4.0, "window_s": 4.02, "ops": ops, **scan,
                      "modules": {"jit__decode_paged_step":
                                  {"count": 160, "seconds": 3.6}}},
            "peaks": PEAKS["TPU v5e"], "config": cfg,
            "model": replica_nemotron.model_section(cfg),
            "trace_contexts": [900] * 180}


@pytest.mark.parametrize("name", sorted(_NEW_IN_PR_56))
def test_a_metric_file_new_in_pr_56_reads_its_window_and_nothing_else(name):
    """As PR 52's case: each file names a reader with a `read` that
    takes the file's arguments, the decode program the engine has and,
    where it sums a kernel's time, a kernel the program calls by that
    name; from a window of known counters and kernel times it reads a
    share under 100 %; from a program without the counters, a trace
    without the kernels, another family's model section or an empty run
    it reads nothing and raises nothing (rule (beta))."""
    import importlib
    import inspect
    import json
    import re
    from benchmarks import run as runmod
    from ray_tpu.ops.pallas import gdn_decode, paged_attention
    from ray_tpu.serve.llm.engine import LLMEngine
    with open(os.path.join(_BENCH, "metrics", name + ".json")) as f:
        spec = json.load(f)
    readers = os.path.join(_BENCH, "readers")
    if readers not in sys.path:
        sys.path.insert(0, readers)
    reader = importlib.import_module(spec["reader"])
    args, what = spec["args"], _NEW_IN_PR_56[name]
    assert set(args) <= set(inspect.signature(reader.read).parameters)
    assert args.get("what") == what
    if "module_re" in args:
        assert re.search(args["module_re"],
                         LLMEngine._decode_paged_step.__name__)
    kernel = args.get("name_re")
    if name.startswith("ssm_kernel"):
        assert re.search(kernel, gdn_decode.ssm_decode_step.__name__)
        assert '"ssm_decode_step"' in inspect.getsource(
            gdn_decode.ssm_decode_step.__wrapped__)
        # and neither sibling's arm, which their own metrics read
        for other in (gdn_decode.gdn_decode_step,
                      gdn_decode.kda_decode_step):
            assert not re.search(kernel, other.__name__)
    elif what == "paged_kernel":
        assert re.search(kernel,
                         paged_attention.paged_decode_attention.__name__)
    elif what == "experts" or name == "moe_dev_share.nemotron":
        from jax.experimental.pallas.ops.tpu.megablox import gmm
        assert re.search(kernel, gmm.__name__)
    elif what == "ssm_scan":
        # read by the scope's name, which the program lowers
        from ray_tpu.models import hybrid
        assert '"ssm.scan"' in inspect.getsource(hybrid.Mamba2)
    got = runmod.read_metric(_BENCH, name, _nemotron_window())
    assert isinstance(got, float) and 0.0 < got < 100.0, (name, got)
    if name == "ssm_kernel_dev_share":
        assert got == pytest.approx(40.0)
    if name == "ssm_scan_dev_share":
        assert got == pytest.approx(2.5)
    if name == "moe_dev_share.nemotron":
        assert got == pytest.approx(20.0)
    if name == "ssm_kernel_roofline":
        # 180 rows x 5 layers x 2 x 4 MiB over 819 GB/s, in 10 ms a run
        assert got == pytest.approx(
            100 * 180 * 5 * 2 * 4 * 2 ** 20 / 819e9 / 10e-3, rel=1e-3)
    if name == "paged_kernel_roofline.nemotron":
        # the PUBLISHED 1 024 B a token, not the 4 096 B the pool lays out
        assert got == pytest.approx(
            100 * 180 * 14 * 64 * 1024 / 819e9 / 0.75e-3, rel=1e-3)
    lacking = _nemotron_window(counters=False, kernels=False)
    if name.startswith("ssm_"):
        assert runmod.read_metric(_BENCH, name, lacking) in (None, 0.0)
        assert (runmod.read_metric(_BENCH, name, lacking) is None) \
            == (what is not None)
    other = dict(_nemotron_window(), model={"hidden_size": 4096,
                                            "kda_rank": 128})
    if what is not None:
        assert runmod.read_metric(_BENCH, name, other) is None
    assert runmod.read_metric(_BENCH, name, {}) is None
    # and on another family's window (the parent's side of a traced run
    # of an accepted cell): nothing
    if what is not None:
        assert runmod.read_metric(_BENCH, name, _solar_window()) is None
    whole = runmod.load_manifest()
    entry, = [m for m in whole["per_layer"] if m["name"] == name]
    assert entry["workloads"] == [_NEMOTRON_CELL]
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert (entry["unit"], entry["moves"], entry["source"]) \
        == ("%", "out_tok_s", "device_trace")
    assert whole["workloads"][9]["name"] == _NEMOTRON_CELL
    assert whole["configs"][8]["name"] == whole["workloads"][9]["config"]
    assert {m["name"] for m in whole["per_layer"][-7:]} \
        == set(_NEW_IN_PR_56)
    accepted = {m["layer"] for m in whole["per_layer"][:-7]}
    assert entry["layer"] in accepted       # letter for letter


def test_a_scope_is_read_from_the_programs_the_profile_keeps(tmp_path,
                                                            monkeypatch):
    """`ssm_scan_dev_share` reads the chunkwise scan by its named scope.
    A trace's events do not carry it, but the profile keeps every
    program's HloProto (`/host:metadata`), each instruction with its
    `op_name`: `scope_ops` reads those out of a REAL profile's bytes,
    and `scope_seconds` sums the events of a program's runs whose
    instruction lies under the scope, the union of their intervals (a
    loop and its body's operations count once), a mean over the
    devices; None where the profile keeps no program."""
    import glob
    from types import SimpleNamespace as NS
    import jax
    import jax.numpy as jnp
    from benchmarks.harness import replica_nemotron, trace_reduce

    def f(x, w):
        with jax.named_scope("ssm.scan"):
            def body(c, _):
                return jnp.tanh(c @ w), None
            x, _ = jax.lax.scan(body, x, None, length=4)
        with jax.named_scope("ssm.norm"):
            return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True))
    g = jax.jit(f)
    x, w = jnp.ones((16, 32)), jnp.full((32, 32), 0.01)
    g(x, w).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    g(x, w).block_until_ready()
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    with open(path, "rb") as fh:
        raw = fh.read()
    found = replica_nemotron.scope_ops(raw, "ssm.scan")
    program, = [k for k in found if k.startswith("jit_f(")]
    scan, norm = found[program], replica_nemotron.scope_ops(
        raw, "ssm.norm")[program]
    assert any(n.startswith("while") for n in scan) and scan and norm
    assert not scan & norm
    assert replica_nemotron.scope_ops(raw, "no.such")[program] == set()
    assert replica_nemotron.scope_ops(b"", "ssm.scan") == {}
    # the intervals: two runs of the program, a loop around its body's
    # operations, another program's operation of the same name
    loop = next(n for n in scan if n.startswith("while"))
    inside = next(n for n in scan if not n.startswith("while"))

    def ev(name, start_us, dur_us):
        return NS(name=name, start_ns=start_us * 1000,
                  duration_ns=dur_us * 1000)
    ops = [ev(f"%{loop} = (f32[2]) while(%t)", 100, 2000),
           ev(f"%{inside} = f32[2] fusion(%p)", 150, 500),
           ev(f"%{next(iter(norm))} = f32[2] fusion(%p)", 2200, 300),
           ev(f"%{loop} = (f32[2]) while(%t)", 5100, 1000),
           ev(f"%{loop} = (s32[4]) while(%t)", 9000, 700)]
    plane = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[ev(program, 0, 3000),
                                       ev(program, 5000, 2000),
                                       ev("jit_other(77)", 8000, 2000)]),
        NS(name="XLA Ops", events=ops)])
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: path)
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda p: NS(
                            planes=[plane, NS(name="/host:CPU", lines=[])])))
    assert replica_nemotron.scope_seconds("x", "ssm.scan") \
        == pytest.approx(3.0e-3)
    assert replica_nemotron.scope_seconds("x", "ssm.norm") \
        == pytest.approx(0.3e-3)
    assert replica_nemotron.scope_seconds("x", "no.such") == 0.0
    plane.name = "/host:other"
    assert replica_nemotron.scope_seconds("x", "ssm.scan") is None
    empty = tmp_path / "empty.pb"
    empty.write_bytes(b"")
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: str(empty))
    assert replica_nemotron.scope_seconds("x", "ssm.scan") is None


@pytest.mark.parametrize("name, reads", [
    ("moe_expert_load_max_over_mean", 64 * 16 / 495),
    ("moe_pad_row_share", 100 * 13 / 193),
    ("moe_local_assignment_share", 12.5),
    ("decode_live_state_share", 100 * 180 / 193),
    ("decode_live_page_share", 100 * 180 * 14 / (193 * 32)),
    ("prefill_live_chunk_share", 75.0),
    ("attention_kernel_dev_share", 3.0)])
def test_an_accepted_metric_reads_the_nemotron_window(name, reads):
    """Rule (gamma): the accepted readers of the lists the cell was
    appended to find their keys in this model's section (num_experts for
    `moe_counter`) and in its counters. `moe_dev_share` is not among
    them: it counts `while` by name, and in this cell the chunkwise
    scan's loop over chunks is one (`moe_dev_share.nemotron` reads the
    grouped matmuls by name)."""
    from benchmarks import run as runmod
    assert runmod.read_metric(_BENCH, name, _nemotron_window()) \
        == pytest.approx(reads)


def test_the_nemotron_cell_is_in_what_a_saturated_serve_cell_with_experts_and_state_reports():  # noqa: E501
    """Ten cells, one of them on four chips. Every list that names the
    Solar-Open2 cell and is not read by that model's own cost arithmetic
    or kernels names this one too, at its end, `out_tok_s` as well; no
    list of another model's does; and every per-layer metric the cell
    is listed for reads the synthetic window (what a traced line is
    held against)."""
    from benchmarks import run as runmod
    whole = runmod.load_manifest()
    assert len(whole["workloads"]) == 10 and len(whole["configs"]) == 9
    assert [w["name"] for w in whole["workloads"] if w["chips"] == 4] \
        == ["mistral7b_train_fsdp2_tp2"]
    for m in whole["end_to_end"] + whole["per_layer"]:
        w = m.get("workloads", [])
        if (_SOLAR_CELL in w and m["name"] not in _NEW_IN_PR_52) \
                or m["name"] in _NEW_IN_PR_56:
            assert w[-1] == _NEMOTRON_CELL, m["name"]
        else:
            assert _NEMOTRON_CELL not in w, m["name"]
    cell, config = whole["workloads"][-1], whole["configs"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (_NEMOTRON_CELL, "nemotron-3-super-120b-serve-ep8-l11",
            "decode_sat_nemotron", 1)
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    assert "8x a deployment chip's rows" in cell["why"] \
        and "8.25 rows an expert" in cell["why"] \
        and "192 slots" in cell["why"]
    run = _nemotron_window()
    run.update(streams=[], t0=0.0, t1=1.0, late_ms=[1.0],
               compiles_in_window=0, backlog_end=0)
    silent = []
    for m in runmod.cell_metrics(whole, _NEMOTRON_CELL, "per_layer"):
        with open(os.path.join(_BENCH, "metrics",
                               m["name"] + ".json")) as f:
            reader = __import__("json").load(f)["reader"]
        if reader in ("nemotron_roofline", "trace_share", "moe_counter",
                      "moe_local_share"):
            if runmod.read_metric(_BENCH, m["name"], run) is None:
                silent.append(m["name"])
    assert silent == []


def test_the_nemotron_cell_rehearses_through_run_py(tmp_path):
    """`run.py --rehearse` of the cell on the CPU at toy widths: the
    family's runner, replica, reference and traffic files are found by
    name, the engine serves the mix, the check against
    `reference_nemotron` passes (the eleven published layers, the first
    Mamba-2 layer's recurrence tapped), and the traced line holds every
    metric of the cell that needs no device trace, without a number."""
    import json
    import subprocess
    from benchmarks import run as runmod
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    done = subprocess.run(
        [sys.executable, os.path.join(_BENCH, "run.py"), "--workload",
         _NEMOTRON_CELL, "--rehearse", "--seed", "5600000011", "--seconds",
         "3", "--trace", "1", "--out", str(tmp_path)],
        cwd=_ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] and line["rehearsal"] and not line["failed"]
    assert line["attempted"] > 0
    assert all(v["value"] is None for v in line["metrics"].values())
    ref = line["checks"]["reference"]
    assert ref["ok"] and ref["not_followed"] == 0 and ref["layers"] == 11
    assert ref["prefill_bucket"] > ref["prompt_len"]
    assert ref["recurrence_err_rel"] < ref["recurrence_tol_rel"]
    # rule (gamma), as far as a CPU can hold it: what the line lacks of
    # the cell's per-layer metrics are those a device trace alone gives
    whole = runmod.load_manifest()
    listed = {m["name"]: m["source"] for m in
              runmod.cell_metrics(whole, _NEMOTRON_CELL, "per_layer")}
    lacking = set(listed) - set(line["metrics"])
    assert {listed[n] for n in lacking} <= {"device_trace"}, lacking
    assert set(_NEW_IN_PR_56) <= lacking
