"""The Olmo-Hybrid-7B reference by itself (its independence, its
agreement with the program's model code at a small size, its controls),
the costs and readers the hybrid cell adds, and that the cell's runner,
files and metrics resolve by name."""
import ast
import importlib
import json
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
HYBRID_CELL = "olmohybrid7b_decode_sat"
CONFIG = os.path.join(BENCH, "configs", "olmo-hybrid-7b-serve-l16.json")
# config.json of allenai/Olmo-Hybrid-7B as the catalog has it
PUBLISHED = {
    "vocab_size": 100352, "hidden_size": 3840, "intermediate_size": 11008,
    "num_hidden_layers": 32, "num_attention_heads": 30,
    "num_key_value_heads": 30, "max_position_embeddings": 65536,
    "rms_norm_eps": 1e-06, "linear_num_key_heads": 30,
    "linear_num_value_heads": 30, "linear_key_head_dim": 96,
    "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "tie_word_embeddings": False,
    "attention_bias": False, "hidden_act": "silu",
    "model_type": "olmo_hybrid", "rope_parameters": {"rope_theta": None},
    "layer_types": ["linear_attention"] * 3 + ["full_attention"]}
CONTROLS = ("bf16_state", "beta_without_2", "no_decay",
            "state_to_bucket_end", "no_qk_l2norm", "no_qk_rmsnorm")


def _section(rehearse: bool = False) -> dict:
    from benchmarks.harness import modelcfg, replica_olmohybrid
    return replica_olmohybrid.model_section(modelcfg.load(CONFIG, rehearse))


def test_olmohybrid_reference_imports_nothing_of_the_program():
    path = os.path.join(BENCH, "harness", "reference_olmohybrid.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add((node.module or ".").split(".")[0])
            assert node.level == 0, "no relative import either"
    assert names <= {"__future__", "jax", "functools", "typing"}, names


@pytest.fixture(scope="module")
def toy():
    """The rehearsal's widths in float32, 1-d weights off their ones and
    zeros, 37 tokens, the program's logits and the reference's."""
    import jax
    import jax.numpy as jnp
    from benchmarks.harness import reference_olmohybrid, replica_olmohybrid
    from benchmarks.harness import modelcfg
    from ray_tpu.models import Hybrid
    cfg = modelcfg.load(CONFIG, True)
    model = Hybrid(replica_olmohybrid.hybrid_config(
        cfg, param_dtype=jnp.float32, dtype=jnp.float32))
    params = model.init_params(jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(
            jax.random.PRNGKey(a.size), a.shape, a.dtype)
        if a.ndim == 1 else a, params)
    tokens = np.random.default_rng(0).integers(1, cfg["vocab_size"], 37)
    with jax.default_matmul_precision("highest"):
        got, _ = model.apply({"params": params}, jnp.asarray(tokens)[None])
    m = _section(True)
    ref = reference_olmohybrid.forward_logits(params, jnp.asarray(tokens), m)
    return {"params": params, "tokens": tokens, "m": m,
            "got": np.asarray(got[0]), "ref": np.asarray(ref)}


def test_olmohybrid_reference_against_the_model_code(toy):
    """The chunkwise form of the program against the token-by-token
    scan of the reference, through whole blocks."""
    assert toy["ref"].shape == (37, 512)
    assert np.abs(toy["got"] - toy["ref"]).max() < 2e-4 * toy["ref"].std()
    import jax.numpy as jnp
    from benchmarks.harness import reference_olmohybrid
    last = reference_olmohybrid.forward_logits(
        toy["params"], jnp.asarray(toy["tokens"]), toy["m"], last=3)
    np.testing.assert_allclose(last, toy["ref"][-3:], atol=1e-5)


@pytest.mark.parametrize("name", CONTROLS)
def test_olmohybrid_controls_compute_another_model(toy, name):
    import jax.numpy as jnp
    from benchmarks.harness import reference_olmohybrid
    wrong = np.asarray(reference_olmohybrid.forward_logits(
        toy["params"], jnp.asarray(toy["tokens"]),
        dict(toy["m"], controls=frozenset([name]), bucket=32,
             prompt_len=30)))
    assert wrong.shape == toy["ref"].shape
    err = np.abs(wrong - toy["ref"]).max(-1) / toy["ref"].std()
    assert err.max() > 0.05, err.max()
    if name == "state_to_bucket_end":
        # the prompt's own positions come before the padding
        assert err[:30].max() < 1e-4 < err[30:].min()


def test_olmohybrid_costs_count_the_published_model_and_the_cut():
    import jax
    import jax.numpy as jnp
    from benchmarks.harness import (costs, costs_olmohybrid, modelcfg,
                                    replica_olmohybrid)
    from ray_tpu.models import Hybrid
    m = _section()
    assert (costs_olmohybrid.linear_layers(m),
            costs_olmohybrid.full_layers(m)) == (12, 4)
    mlp = costs_olmohybrid.mlp_params(m)
    assert mlp == 126812160
    assert costs_olmohybrid.linear_mixer_params(m) + mlp \
        == pytest.approx(215.6e6, rel=1e-3)
    assert costs_olmohybrid.full_mixer_params(m) + mlp \
        == pytest.approx(185.8e6, rel=1e-3)
    # the count is the program's own parameter tree's
    model = Hybrid(replica_olmohybrid.hybrid_config(
        modelcfg.load(CONFIG, False), param_dtype=jnp.bfloat16))
    shapes = jax.eval_shape(lambda k: model.init(
        k, jnp.zeros((1, 8), jnp.int32))["params"], jax.random.PRNGKey(0))
    held = sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(shapes))
    assert costs_olmohybrid.total_params(m) == held == 4100788944
    whole = costs_olmohybrid.total_params(dict(
        m, num_hidden_layers=32, layer_types=m["layer_types"] * 2))
    assert 7.3e9 < whole < 7.6e9                              # 7B-class
    # 12 x (30 x 192 x 96 float32 + 3 x 11 520 bf16) a slot; K and V of
    # 30 heads in 4 layers a token, a quarter of what `costs` reckons
    assert costs_olmohybrid.state_bytes_per_slot(m) == 27371520
    assert costs_olmohybrid.kv_bytes_per_token(m) == 61440 \
        == costs.kv_bytes_per_token(m) // 4
    # the step kernel: 7 operations for 8 bytes, memory bound
    gdn = costs_olmohybrid.gdn_step(m, 56 * 12)
    assert gdn["bytes"] == 56 * 12 * 30 * 192 * 96 * 8
    assert gdn["flops"] / gdn["bytes"] == pytest.approx(7 / 8)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert costs_olmohybrid.least_seconds(gdn, peaks)["bound"] == "memory"
    paged = costs_olmohybrid.paged_attention(m, 100.0, 64)
    assert paged["bytes"] == 100 * 64 * 61440
    step = costs_olmohybrid.decode_step(m, [800] * 56)
    assert step["bytes"] == (costs_olmohybrid.matmul_params(m) * 2
                             + gdn["bytes"] + 56 * 800 * 61440)
    assert 0.2 < gdn["bytes"] / step["bytes"] < 0.3
    assert costs_olmohybrid.least_seconds(step, peaks)["bound"] == "memory"


def _run(**kw):
    run = {"model": _section(),
           "config": {"engine": {"kv_page_size": 64}},
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
           "stats0": {"decode_steps": 0, "decode_pages_live": 0,
                      "decode_state_rows_live": 0,
                      "decode_state_rows_window": 0},
           "stats1": {"decode_steps": 10, "decode_pages_live": 10 * 56 * 13,
                      "decode_state_rows_live": 10 * 50 * 12,
                      "decode_state_rows_window": 10 * 57 * 12},
           "trace_contexts": [800] * 56,
           "trace": {"busy_s": 1.0,
                     "modules": {"jit__decode_paged_step":
                                 {"count": 5, "seconds": 0.1}},
                     "ops": {"gdn_decode_step.5": 0.02,
                             "paged_decode_attention.2": 0.03,
                             "fusion": 0.3}}}
    run.update(kw)
    return run


def test_olmohybrid_readers_read_and_read_none_without_the_counters():
    from benchmarks import run as runmod
    from benchmarks.harness import costs_olmohybrid
    run = _run()
    m, peaks = run["model"], run["peaks"]
    assert runmod.read_metric(BENCH, "gdn_kernel_dev_share", run) \
        == pytest.approx(2.0)
    # the step kernel's name does not count as an attention kernel
    assert runmod.read_metric(BENCH, "attention_kernel_dev_share", run) \
        == pytest.approx(3.0)
    assert runmod.read_metric(BENCH, "decode_live_state_share", run) \
        == pytest.approx(100 * 50 / 57)
    least = costs_olmohybrid.least_seconds(
        costs_olmohybrid.gdn_step(m, 50 * 12), peaks)["seconds"]
    got = runmod.read_metric(BENCH, "gdn_kernel_roofline", run)
    assert got == pytest.approx(100 * least / (0.02 / 5)) and got < 100
    least = costs_olmohybrid.least_seconds(
        costs_olmohybrid.paged_attention(m, 56 * 13, 64), peaks)["seconds"]
    got = runmod.read_metric(BENCH, "paged_kernel_roofline.hybrid", run)
    assert got == pytest.approx(100 * least / (0.03 / 5)) and got < 100
    # the reader the cell is NOT listed under counts K and V in all 16
    assert runmod.read_metric(BENCH, "paged_kernel_roofline", run) \
        == pytest.approx(4 * got) and 4 * got > 105
    least = costs_olmohybrid.least_seconds(
        costs_olmohybrid.decode_step(m, [800] * 56), peaks)["seconds"]
    got = runmod.read_metric(BENCH, "decode_step_roofline.hybrid", run)
    assert got == pytest.approx(100 * least / 0.02) and got < 100
    # a program without the counters, the kernel or the family: nothing
    old = _run(stats0={"decode_steps": 0, "decode_pages_live": 0},
               stats1={"decode_steps": 10, "decode_pages_live": 7280})
    del old["trace"]["ops"]["gdn_decode_step.5"]
    assert runmod.read_metric(BENCH, "gdn_kernel_dev_share", old) == 0.0
    dense = _run(model={"hidden_size": 4096, "num_hidden_layers": 16})
    for name in ("gdn_kernel_roofline", "decode_live_state_share"):
        assert runmod.read_metric(BENCH, name, old) is None, name
    for name in ("gdn_kernel_roofline", "paged_kernel_roofline.hybrid",
                 "decode_step_roofline.hybrid"):
        assert runmod.read_metric(BENCH, name, dense) is None, name
        assert runmod.read_metric(BENCH, name, _run(trace=None)) is None


def test_the_olmohybrid_cell_resolves_to_its_own_runner_and_files():
    from benchmarks import run as runmod
    from benchmarks.harness import modelcfg
    manifest = runmod.load_manifest()
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    found = runmod.resolve(manifest, HYBRID_CELL)
    assert found["cell"]["chips"] == 1
    cfg = modelcfg.load(found["config_path"], False)
    assert found["config_entry"]["reduced"] == list(cfg["reduced"]) == [
        "num_hidden_layers"]
    assert found["config_entry"]["source"] == cfg["source"]
    # every published key as published, but the one cut
    for key, published in PUBLISHED.items():
        if key == "num_hidden_layers":
            assert cfg["reduced"][key]["published"] == published
            assert cfg["reduced"][key]["here"] == cfg[key] == 16
        elif key == "layer_types":
            assert cfg[key] == published * 8          # kept whole
            assert _section()[key] == published * 4   # four whole periods
        else:
            assert cfg[key] == published, key
    assert cfg["head_dim"] * cfg["num_attention_heads"] == cfg["hidden_size"]
    assert cfg["rope_theta"] is None
    for key in ("norm_placement", "qk_norm", "conv_bias", "state_dtype",
                "linear_layer", "head_dim", "rope_theta"):
        assert len(cfg["assumed"][key]) > 40, key
    assert cfg["deployment_it_stands_for"] and cfg["memory_analysis"]["how"]
    runner = importlib.import_module("benchmarks.runners." + cfg["runner"])
    from benchmarks.runners import serve_http
    assert runner.serve_http is serve_http      # the one run(), not a copy
    assert set(runner.olmohybrid_family()) == set(serve_http.llama_family())
    assert runner.olmohybrid_family()["probe"].__name__ == "hybrid_preset"
    with open(runner.__file__) as f:
        assert len(f.read().splitlines()) < 30
    with open(found["traffic_path"]) as f:
        traffic = json.load(f)
    with open(os.path.join(BENCH, "traffic", "decode_sat_sarvam.json")) as f:
        sarvam = json.load(f)
    # decode_sat_sarvam's lengths: the two cells differ in the model
    for key in ("prompt_len", "output_len", "gaps", "block", "ramp_s",
                "drain_s", "trace"):
        assert traffic[key] == sarvam[key], key
    # 600 tokens, not a bucket size: the state's stop at the prompt's
    # true length inside the 1 024 bucket is part of `correct`
    assert traffic["check"] == {"prompt_len": 600, "new_tokens": 8}
    assert 600 not in cfg["engine"]["prefill_buckets"]
    from benchmarks.harness import schedule
    answers = schedule.length_multiset(traffic["output_len"], 32)
    assert traffic["rate_over_knee"] == pytest.approx(
        traffic["rate_rps"] / traffic["knee_rps"], abs=0.01) == 1.15
    assert traffic["offered_tok_s"] == pytest.approx(
        traffic["rate_rps"] * sum(answers) / 32, abs=0.1)
    small = modelcfg.load(found["config_path"], True)
    assert small["hidden_size"] == 64 and small["linear_key_head_dim"] == 8
    assert _section(True)["layer_types"] == PUBLISHED["layer_types"]
    for section in ("end_to_end", "per_layer"):
        for metric in runmod.cell_metrics(manifest, HYBRID_CELL, section):
            assert os.path.exists(os.path.join(
                BENCH, "metrics", metric["name"] + ".json")), metric["name"]
    layer = {x["name"] for x in runmod.cell_metrics(manifest, HYBRID_CELL,
                                                    "per_layer")}
    assert {"gdn_kernel_roofline", "gdn_kernel_dev_share",
            "paged_kernel_roofline.hybrid", "decode_step_roofline.hybrid",
            "decode_live_state_share", "engine_runtime_calls_per_step",
            "decode_live_page_share"} <= layer
    # K-and-V-in-every-layer arithmetic is not for this model
    assert not {"paged_kernel_roofline", "decode_step_roofline",
                "decode_step_roofline.moe",
                "decode_step_roofline.latent_moe"} & layer


def test_a_file_the_olmohybrid_family_cannot_take_is_refused_at_once():
    from benchmarks.harness import modelcfg, replica_olmohybrid
    cfg = modelcfg.load(CONFIG, False)
    with pytest.raises(SystemExit, match="derives head_dim"):
        replica_olmohybrid.model_section(dict(cfg, head_dim=96))
    with pytest.raises(SystemExit, match="no rotation"):
        replica_olmohybrid.model_section(dict(cfg, rope_theta=10000.0))
    with pytest.raises(SystemExit, match="every\\s+layer held"):
        replica_olmohybrid.model_section(dict(cfg, num_hidden_layers=40))
    with pytest.raises(SystemExit, match="lacks"):
        replica_olmohybrid.model_section(
            {k: v for k, v in cfg.items() if k != "linear_key_head_dim"})
    mistral = modelcfg.load(os.path.join(
        BENCH, "configs", "mistral-7b-v0.3-serve-l16.json"), False)
    with pytest.raises(SystemExit, match="lacks"):
        replica_olmohybrid.model_section(mistral)
    assert replica_olmohybrid.hybrid_preset()().d_model == 3840
