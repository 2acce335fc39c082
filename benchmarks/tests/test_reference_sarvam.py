"""The sarvam-105b reference by itself (its independence, its routing,
the share and the controls), the costs and readers the sarvam cell adds,
and that the cell's runner, files and metrics resolve by name."""
import ast
import importlib
import json
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
SARVAM_CELL = "sarvam105b_decode_sat"
PUBLISHED = {"num_hidden_layers": 32, "num_experts": 128,
             "vocab_size": 262144}


def _section(rehearse: bool = False) -> dict:
    from benchmarks.harness import modelcfg, replica_sarvam
    return replica_sarvam.model_section(modelcfg.load(os.path.join(
        BENCH, "configs", "sarvam-105b-serve-ep4-l6.json"), rehearse))


def test_sarvam_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "harness", "reference_sarvam.py")) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add((node.module or ".").split(".")[0])
            assert node.level == 0, "no relative import either"
    assert names <= {"__future__", "jax", "math"}, names


def test_sarvam_reference_routes_by_biased_sigmoid_and_follows_near_ties():
    import jax.numpy as jnp
    from benchmarks.harness import reference_sarvam as ref
    m = dict(_section(True), num_experts_per_tok=2, router_width=4,
             num_experts=2, expert_first=2, routed_scaling_factor=2.5)
    h = jnp.eye(4, dtype=jnp.float32)[:2]       # logits = rows 0, 1 of W_r
    moe = {"router_kernel": jnp.asarray(
        [[2.0, 0.0, -1.0, 1.0], [0.0, 0.1, 0.0, 0.12],
         [0.0] * 4, [0.0] * 4], jnp.float32),
        "router_bias": jnp.asarray([-1.0, 0.0, 1.0, 0.0], jnp.float32)}
    s = 1.0 / (1.0 + np.exp(-np.asarray([2.0, 0.0, -1.0, 1.0])))
    weights, info = ref.routing(h, moe, m)
    assert np.asarray(info["chosen"][0]).tolist() == [False, False, True,
                                                      True]
    np.testing.assert_allclose(
        weights[0], [0, 0, *(2.5 * s[2:] / s[2:].sum())], rtol=1e-6)
    # position 1: biased scores [-.5, .525, 1.5, .530]: experts 2 and 3;
    # a system that took 1 for 3 is followed inside the margin only
    assert np.asarray(info["chosen"][1]).tolist() == [False, False, True,
                                                      True]
    theirs = jnp.asarray([[2, 3], [2, 1]])
    _w, near = ref.routing(h, moe, m, follow=theirs, tie_margin=0.05)
    assert np.asarray(near["chosen"][1]).tolist() == [False, True, True,
                                                      False]
    assert not near["not_followed"].any() and not near["own"][1]
    _w, far = ref.routing(h, moe, m, follow=theirs, tie_margin=0.001)
    assert bool(far["not_followed"][1]) and not far["not_followed"][0]
    # the controls compute another model
    plain, _ = ref.routing(h, moe, dict(m, controls={"select_without_bias"}))
    assert float(plain[0, 0]) > 0                 # expert 0 by score alone
    unscaled, _ = ref.routing(h, moe, dict(m, controls={"no_scaling"}))
    np.testing.assert_allclose(unscaled * 2.5, weights, rtol=1e-6)
    held, _ = ref.routing(h, moe, dict(m, controls={"norm_over_held"}))
    np.testing.assert_allclose(held[:, 2:].sum(-1), 2.5, rtol=1e-6)
    assert ref.softmax_scale(_section()) == pytest.approx(0.13523, abs=5e-6)
    assert ref.softmax_scale(dict(_section(), controls={
        "scale_without_yarn"})) == pytest.approx(192 ** -0.5)


def test_sarvam_costs_count_the_published_model_and_the_cut():
    from benchmarks.harness import costs_sarvam
    m = _section()
    assert costs_sarvam.attention_params(m) == 94633984       # 94.63 M
    assert costs_sarvam.total_params(m) == 5461041920         # 10.17 GiB
    whole = costs_sarvam.total_params(dict(m, **PUBLISHED))
    assert 105e9 < whole < 107e9                              # 105 B-class
    assert costs_sarvam.latent_bytes_per_token(m) == 6 * 1152
    # 121 FLOP a byte: under the chip's ridge of 240, so memory bound
    attn = costs_sarvam.latent_attention(m, 1000.0)
    assert attn["flops"] / attn["bytes"] == pytest.approx(
        2 * 64 * (576 + 512) / 1152)
    # the experts held are most of a decode step's bytes
    step = costs_sarvam.decode_step(m, [860] * 128, 160.0, 128 * 8 * 5 / 4)
    experts = costs_sarvam.expert_matmuls(m, 128 * 8 * 5 / 4, 160.0)
    assert 0.7 < experts["bytes"] / step["bytes"] < 0.8
    assert costs_sarvam.decode_step(m, [860] * 128, 80.0, 1280.0)["bytes"] \
        < step["bytes"]


def _run(**kw):
    run = {"model": _section(),
           "config": {"engine": {"kv_page_size": 64}},
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
           "stats0": {"decode_steps": 0, "prefill_calls": 0,
                      "decode_pages_live": 0, "moe_experts_touched": 0,
                      "moe_assignments": 0, "moe_routed_assignments": 0},
           "stats1": {"decode_steps": 10, "prefill_calls": 0,
                      "decode_pages_live": 10 * 128 * 14,
                      "moe_experts_touched": 10 * 160,
                      "moe_assignments": 10 * 1280,
                      "moe_routed_assignments": 10 * 5120},
           "trace_contexts": [860] * 128,
           "trace": {"busy_s": 1.0,
                     "modules": {"jit__decode_paged_impl":
                                 {"count": 5, "seconds": 0.1}},
                     "ops": {"latent_decode_attention.2": 0.01,
                             "gmm.3": 0.06, "fusion": 0.3}}}
    run.update(kw)
    return run


def test_sarvam_readers_read_and_read_none_without_the_counters():
    from benchmarks import run as runmod
    from benchmarks.harness import costs_sarvam
    run = _run()
    m, peaks = run["model"], run["peaks"]
    assert runmod.read_metric(BENCH, "latent_kernel_dev_share", run) \
        == pytest.approx(1.0)
    assert runmod.read_metric(BENCH, "moe_local_assignment_share", run) \
        == pytest.approx(25.0)
    least = costs_sarvam.least_seconds(
        costs_sarvam.latent_attention(m, 128 * 14 * 64), peaks)
    assert least["bound"] == "memory"
    assert runmod.read_metric(BENCH, "latent_kernel_roofline", run) \
        == pytest.approx(100 * least["seconds"] / (0.01 / 5))
    least = costs_sarvam.least_seconds(
        costs_sarvam.expert_matmuls(m, 1280, 160), peaks)["seconds"]
    assert runmod.read_metric(BENCH, "expert_matmul_roofline.share", run) \
        == pytest.approx(100 * least / (0.06 / 5))
    least = costs_sarvam.least_seconds(costs_sarvam.decode_step(
        m, [860] * 128, 160.0, 1280.0), peaks)["seconds"]
    got = runmod.read_metric(BENCH, "decode_step_roofline.latent_moe", run)
    assert got == pytest.approx(100 * least / 0.02) and got < 100
    # a program without the counters, the kernel or the family: nothing
    old = _run(stats0={"decode_steps": 0, "prefill_calls": 0},
               stats1={"decode_steps": 10, "prefill_calls": 0})
    del old["trace"]["ops"]["latent_decode_attention.2"]
    assert runmod.read_metric(BENCH, "latent_kernel_dev_share", old) == 0.0
    dense = _run(model={"hidden_size": 4096, "num_experts": 64})
    for name in ("latent_kernel_roofline", "expert_matmul_roofline.share",
                 "decode_step_roofline.latent_moe",
                 "moe_local_assignment_share"):
        assert runmod.read_metric(BENCH, name, old) is None, name
        if name != "moe_local_assignment_share":
            assert runmod.read_metric(BENCH, name, dense) is None, name


def test_the_sarvam_cell_resolves_to_its_own_runner_and_files():
    from benchmarks import run as runmod
    from benchmarks.harness import modelcfg
    manifest = runmod.load_manifest()
    assert len(manifest["workloads"]) == 5
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    found = runmod.resolve(manifest, SARVAM_CELL)
    cfg = modelcfg.load(found["config_path"], False)
    assert found["config_entry"]["reduced"] == list(cfg["reduced"]) == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    for key, published in PUBLISHED.items():
        assert cfg["reduced"][key]["published"] == published
        assert cfg["reduced"][key]["here"] == cfg[key]
    runner = importlib.import_module("benchmarks.runners." + cfg["runner"])
    from benchmarks.runners import serve_http
    assert runner.serve_http is serve_http      # the one run(), not a copy
    assert set(runner.sarvam_family()) == set(serve_http.llama_family())
    assert runner.sarvam_family()["probe"].__name__ == "sarvam_preset"
    with open(runner.__file__) as f:
        assert len(f.read().splitlines()) < 30
    with open(found["traffic_path"]) as f:
        traffic = json.load(f)
    assert (traffic["prompt_len"]["median"], traffic["prompt_len"]["max"],
            traffic["output_len"]["median"], traffic["output_len"]["max"],
            traffic["block"], traffic["ramp_s"]) == (512, 2048, 512, 1024,
                                                     32, 20.0)
    assert traffic["check"] == {"prompt_len": 512, "new_tokens": 8}
    # the rate is 1.15 x the swept knee, and the file states its ceiling
    from benchmarks.harness import schedule
    answers = schedule.length_multiset(traffic["output_len"], 32)
    assert traffic["rate_over_knee"] == pytest.approx(
        traffic["rate_rps"] / traffic["knee_rps"], abs=0.01) == 1.15
    assert traffic["offered_tok_s"] == pytest.approx(
        traffic["rate_rps"] * sum(answers) / 32, abs=0.1)
    tiny = modelcfg.load(found["config_path"], True)
    assert tiny["hidden_size"] == 64 and tiny["num_experts"] == 2
    assert _section(True)["router_width"] == 8
    for section in ("end_to_end", "per_layer"):
        for metric in runmod.cell_metrics(manifest, SARVAM_CELL, section):
            assert os.path.exists(os.path.join(
                BENCH, "metrics", metric["name"] + ".json")), metric["name"]
    layer = {x["name"] for x in runmod.cell_metrics(manifest, SARVAM_CELL,
                                                    "per_layer")}
    assert {"latent_kernel_roofline", "latent_kernel_dev_share",
            "expert_matmul_roofline.share",
            "decode_step_roofline.latent_moe",
            "moe_local_assignment_share"} <= layer
    # K-and-V arithmetic is not for a pool of latents
    assert not {"paged_kernel_roofline", "decode_step_roofline",
                "decode_step_roofline.moe", "expert_matmul_roofline"} & layer


def test_a_file_the_sarvam_family_cannot_take_is_refused_at_once():
    from benchmarks.harness import modelcfg, replica_sarvam
    path = os.path.join(BENCH, "configs", "sarvam-105b-serve-ep4-l6.json")
    cfg = modelcfg.load(path, False)
    with pytest.raises(SystemExit, match="caches kv_lora_rank"):
        replica_sarvam.model_section(dict(cfg, head_dim=512))
    with pytest.raises(SystemExit, match="router's width"):
        replica_sarvam.model_section(dict(cfg, num_experts=16))
    with pytest.raises(SystemExit, match="lacks"):
        replica_sarvam.model_section(
            {k: v for k, v in cfg.items() if k != "kv_lora_rank"})
    mistral = modelcfg.load(os.path.join(
        BENCH, "configs", "mistral-7b-v0.3-serve-l16.json"), False)
    with pytest.raises(SystemExit, match="lacks"):
        replica_sarvam.model_section(mistral)
