"""The OLMoE reference by itself (its independence, its routing and the
near-tie rule), the costs and readers the OLMoE cell adds, and that the
cell's runner, files and metrics resolve by name."""
import ast
import importlib
import json
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
CELL = "olmoe7b_decode_sat"


def _m(**kw):
    return dict({"hidden_size": 32, "num_hidden_layers": 1,
                 "num_attention_heads": 2, "num_key_value_heads": 2,
                 "head_dim": 16, "intermediate_size": 16, "vocab_size": 64,
                 "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
                 "num_experts": 4, "num_experts_per_tok": 2,
                 "norm_topk_prob": False}, **kw)


def test_olmoe_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "harness", "reference_olmoe.py")) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add((node.module or ".").split(".")[0])
            assert node.level == 0, "no relative import either"
    assert names <= {"__future__", "jax"}, names


def test_olmoe_reference_routes_by_softmax_over_all_then_top_k():
    import jax.numpy as jnp
    from benchmarks.harness import reference_olmoe as ref
    h = jnp.eye(4, dtype=jnp.float32)[:1] * 1.0          # picks row 0
    kernel = jnp.asarray([[1.0, 2.0, 0.0, -1.0]] + [[0.0] * 4] * 3)
    w, info = ref.routing(h, kernel, _m())
    np.testing.assert_allclose(
        np.asarray(w[0]), [0.2368828, 0.6439143, 0.0, 0.0], rtol=1e-6)
    assert np.asarray(info["chosen"][0]).tolist() == [True, True, False,
                                                      False]
    # margin between the 2nd (0.2369) and 3rd (0.0871) probability
    np.testing.assert_allclose(float(info["margin_rel"][0]),
                               (0.2368828 - 0.0871443) / 0.2368828,
                               rtol=1e-5)
    wn, _ = ref.routing(h, kernel, _m(norm_topk_prob=True))
    np.testing.assert_allclose(np.asarray(wn[0]).sum(), 1.0, rtol=1e-6)


@pytest.mark.parametrize("gap,margin,followed", [
    (0.01, 0.05, True),        # a near-tie: the system's choice is taken
    (0.50, 0.05, False),       # not a tie: the reference keeps its own
])
def test_olmoe_reference_follows_only_near_ties(gap, margin, followed):
    import jax.numpy as jnp
    from benchmarks.harness import reference_olmoe as ref
    h = jnp.eye(4, dtype=jnp.float32)[:1]
    kernel = jnp.asarray([[2.0, 1.0, 1.0 - gap, -3.0]] + [[0.0] * 4] * 3)
    theirs = jnp.asarray([[0, 2]])          # 2 in place of 1
    w, info = ref.routing(h, kernel, _m(), follow=theirs,
                          tie_margin=margin)
    assert bool(info["own"][0]) is False
    assert bool(info["not_followed"][0]) is (not followed)
    assert (np.asarray(w[0]) > 0).tolist() == (
        [True, False, True, False] if followed
        else [True, True, False, False])


def test_olmoe_costs_count_the_published_model():
    from benchmarks.harness import costs_moe
    with open(os.path.join(BENCH, "configs",
                           "olmoe-1b-7b-0125-serve-l8.json")) as f:
        cfg = json.load(f)
    full = dict(cfg, num_hidden_layers=16)
    assert abs(costs_moe.total_params(full) / 1e9 - 6.92) < 0.01
    assert abs(costs_moe.total_params(cfg) / 1e9 - 3.56) < 0.01
    # all 64 experts touched by 64 rows x 8: weights dominate the bytes
    step = costs_moe.decode_step(cfg, [400] * 64, 64.0)
    experts = costs_moe.expert_matmuls(cfg, 64 * 8 * 8, 64 * 8)
    assert 0.7 < experts["bytes"] / step["bytes"] < 0.8
    # fewer experts touched, fewer bytes; never "all 64" by assumption
    assert costs_moe.decode_step(cfg, [400] * 64, 32.0)["bytes"] \
        < step["bytes"]


def _run(**kw):
    m = _m(num_hidden_layers=2)
    run = {"model": m,
           "peaks": {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9},
           "stats0": {"decode_steps": 0, "prefill_calls": 0,
                      "moe_experts_touched": 0, "moe_assignments": 0,
                      "moe_expert_load_max": 0, "moe_rows": 0,
                      "moe_pad_rows": 0},
           "stats1": {"decode_steps": 10, "prefill_calls": 0,
                      "moe_experts_touched": 80, "moe_assignments": 320,
                      "moe_expert_load_max": 120, "moe_rows": 160,
                      "moe_pad_rows": 40},
           "trace_contexts": [10] * 8,
           "trace": {"busy_s": 1.0,
                     "modules": {"jit__decode_paged_impl":
                                 {"count": 5, "seconds": 5e-3}},
                     "ops": {"gmm.3": 0.2, "sort": 0.1, "fusion": 0.3}}}
    run.update(kw)
    return run


def test_olmoe_readers_read_and_read_none_without_the_counters():
    from benchmarks import run as runmod
    from benchmarks.harness import costs, costs_moe
    run = _run()
    m = run["model"]
    assert runmod.read_metric(BENCH, "moe_dev_share", run) \
        == pytest.approx(30.0)
    assert runmod.read_metric(BENCH, "moe_pad_row_share", run) \
        == pytest.approx(20.0)
    assert runmod.read_metric(BENCH, "moe_expert_load_max_over_mean", run) \
        == pytest.approx(4 * 120 / 320)
    least = costs.least_seconds(costs_moe.expert_matmuls(m, 32, 8),
                                run["peaks"])["seconds"]
    assert runmod.read_metric(BENCH, "expert_matmul_roofline", run) \
        == pytest.approx(100 * least / (0.2 / 5))
    step = costs.least_seconds(costs_moe.decode_step(m, [10] * 8, 4.0),
                               run["peaks"])["seconds"]
    assert runmod.read_metric(BENCH, "decode_step_roofline.moe", run) \
        == pytest.approx(100 * step / 1e-3)
    # a program without the counters or the scopes: nothing, no error
    old = _run(stats0={"decode_steps": 0, "prefill_calls": 0},
               stats1={"decode_steps": 10, "prefill_calls": 0})
    del old["trace"]["ops"]["gmm.3"], old["trace"]["ops"]["sort"]
    assert runmod.read_metric(BENCH, "moe_dev_share", old) == 0.0
    for name in ("moe_pad_row_share",
                 "moe_expert_load_max_over_mean", "expert_matmul_roofline",
                 "decode_step_roofline.moe"):
        assert runmod.read_metric(BENCH, name, old) is None, name


def test_the_olmoe_cell_resolves_to_its_own_runner_and_files():
    from benchmarks import run as runmod
    from benchmarks.harness import modelcfg
    manifest = runmod.load_manifest()
    found = runmod.resolve(manifest, CELL)
    cfg = modelcfg.load(found["config_path"], False)
    assert found["config_entry"]["reduced"] == ["num_hidden_layers"]
    assert list(cfg["reduced"]) == ["num_hidden_layers"]
    runner = importlib.import_module("benchmarks.runners." + cfg["runner"])
    assert runner.__name__.endswith("serve_http_moe") and callable(
        runner.run)
    from benchmarks.runners import serve_http
    assert runner.warm_spec is serve_http.warm_spec     # imported, not copied
    assert os.path.exists(found["traffic_path"])
    rehearsal = modelcfg.load(found["config_path"], True)
    assert rehearsal["num_experts"] == 8 and rehearsal["hidden_size"] == 64
    for section in ("end_to_end", "per_layer"):
        for metric in runmod.cell_metrics(manifest, CELL, section):
            assert os.path.exists(os.path.join(
                BENCH, "metrics", metric["name"] + ".json")), metric["name"]
    layer = {x["name"] for x in runmod.cell_metrics(manifest, CELL,
                                                    "per_layer")}
    assert {"moe_dev_share", "expert_matmul_roofline",
            "decode_step_roofline.moe", "moe_expert_load_max_over_mean",
            "moe_pad_row_share"} <= layer
    assert "decode_step_roofline" not in layer    # counts a dense MLP
