"""The LFM2-24B-A2B reference by itself (its independence, hand-computed
values at a tiny size, its agreement with the program's model code, its
controls), the costs and readers the cell adds, and that the cell's
runner, files and metrics resolve by name."""
import ast
import importlib
import json
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
LFM2_CELL = "lfm2moe24b_decode_sat"
LFM2_CONFIG = os.path.join(BENCH, "configs", "lfm2-24b-a2b-serve-l9.json")
PERIOD = ["conv", "conv", "full_attention", "conv"]
# config.json of LiquidAI/LFM2-24B-A2B as the catalog has it
LFM2_PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776, "layer_types": PERIOD * 10,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05,
    "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "vocab_size": 65536}
LFM2_CONTROLS = ("no_conv_gate_C", "conv_with_silu",
                 "conv_state_to_bucket_end", "select_without_bias",
                 "no_topk_norm", "no_qk_headnorm", "qk_norm_whole_width",
                 "rope_before_norm", "post_norm", "int8_weights")


def _lfm2_section(rehearse: bool = False) -> dict:
    from benchmarks.harness import modelcfg, replica_lfm2moe
    return replica_lfm2moe.model_section(modelcfg.load(LFM2_CONFIG,
                                                       rehearse))


def test_lfm2moe_reference_imports_nothing_of_the_program():
    path = os.path.join(BENCH, "harness", "reference_lfm2moe.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add((node.module or ".").split(".")[0])
            assert node.level == 0, "no relative import either"
    assert names <= {"__future__", "jax"}, names


def test_lfm2moe_conv_layer_by_hand():
    """One conv layer, K = 3, three tokens, width 2, every projection
    the identity: z = u * u, c_t = z_t + 10 z_{t-1} + 100 z_{t-2} (the
    program's kernel has row 0 on the current token), out = u * c."""
    import jax.numpy as jnp
    from benchmarks.harness import reference_lfm2moe as ref
    eye = np.eye(2, dtype=np.float32)
    p = {"in_proj": {"kernel": jnp.asarray(np.hstack([eye, eye, eye]))},
         "out_proj": {"kernel": jnp.asarray(eye)},
         "conv_kernel": jnp.asarray([[1., 1.], [10., 10.], [100., 100.]])}
    u = jnp.asarray([[1., 2.], [3., 4.], [5., 6.]])
    m = {"conv_L_cache": 3}
    np.testing.assert_allclose(
        ref.conv_mixer(u, p, m), [[1, 8], [57, 224], [1075, 3576]])
    np.testing.assert_allclose(
        ref.conv_mixer(u, p, dict(m, controls={"no_conv_gate_C"})),
        [[1, 4], [19, 56], [215, 596]])


def test_lfm2moe_expert_layer_by_hand():
    """Four experts, two a token, a bias that changes the selection:
    scores sigmoid(2, 1, 0, -1); by score experts 0 and 1, with the
    bias (0, -1, 0, 0.6) experts 0 and 3; the weights are the SCORES of
    those two over (their sum + 1e-6). Each expert is a SwiGLU of width
    1 on a hidden size of 1: E_e(h) = silu(h g_e) (h u_e) d_e."""
    import jax.numpy as jnp
    from benchmarks.harness import reference_lfm2moe as ref
    m = {"num_experts_per_tok": 2, "norm_topk_prob": True,
         "routed_scaling_factor": 1, "use_expert_bias": True}
    moe = {"router_kernel": jnp.asarray([[2., 1., 0., -1.]]),
           "router_bias": jnp.asarray([0., -1., 0., 0.6]),
           "experts_gate_kernel": jnp.asarray([1., 2., 3., 4.]
                                              ).reshape(4, 1, 1),
           "experts_up_kernel": jnp.asarray([1., 1., 1., 2.]
                                            ).reshape(4, 1, 1),
           "experts_down_kernel": jnp.asarray([1., 1., 1., 3.]
                                              ).reshape(4, 1, 1)}
    h = jnp.ones((1, 1))
    s = 1 / (1 + np.exp(-np.asarray([2., 1., 0., -1.])))
    w, info = ref.routing(h, moe, m)
    assert np.asarray(info["chosen"])[0].tolist() == [True, False, False,
                                                      True]
    want = np.zeros(4)
    want[[0, 3]] = s[[0, 3]] / (s[0] + s[3] + 1e-6)
    np.testing.assert_allclose(np.asarray(w)[0], want, rtol=1e-6)
    w0, info0 = ref.routing(h, moe, dict(m, controls={"select_without_bias"}))
    assert np.asarray(info0["chosen"])[0].tolist() == [True, True, False,
                                                       False]
    raw, _ = ref.routing(h, moe, dict(m, controls={"no_topk_norm"}))
    np.testing.assert_allclose(np.asarray(raw)[0, [0, 3]], s[[0, 3]],
                               rtol=1e-6)

    def silu(x):
        return x / (1 + np.exp(-x))
    y = ref.experts(h, w, moe, m)
    np.testing.assert_allclose(
        float(y[0, 0]), want[0] * silu(1.0) + want[3] * silu(4.0) * 2 * 3,
        rtol=1e-6)
    # a system's other choice is followed inside the margin, and
    # counted outside it
    theirs = jnp.asarray([[0, 2]])
    _, near = ref.routing(h, moe, m, follow=theirs, tie_margin=0.5)
    _, far = ref.routing(h, moe, m, follow=theirs, tie_margin=0.01)
    assert np.asarray(near["chosen"])[0].tolist() == [True, False, True,
                                                      False]
    assert not bool(near["not_followed"][0]) and bool(far["not_followed"][0])
    assert np.asarray(far["chosen"])[0].tolist() == [True, False, False,
                                                     True]


@pytest.fixture(scope="module")
def lfm2_toy():
    """The rehearsal's widths in float32, 1-d weights off their ones,
    37 tokens, the program's logits and the reference's."""
    import jax
    import jax.numpy as jnp
    from benchmarks.harness import (modelcfg, reference_lfm2moe,
                                    replica_lfm2moe)
    from ray_tpu.models import Hybrid
    cfg = modelcfg.load(LFM2_CONFIG, True)
    model = Hybrid(replica_lfm2moe.hybrid_config(
        cfg, param_dtype=jnp.float32, dtype=jnp.float32))
    params = model.init_params(jax.random.PRNGKey(0))
    params = jax.tree_util.tree_map(
        lambda a: a * (1 + 0.3 * jax.random.normal(
            jax.random.PRNGKey(a.size), a.shape, a.dtype))
        if a.ndim == 1 else a, params)
    tokens = np.random.default_rng(0).integers(1, cfg["vocab_size"], 37)
    with jax.default_matmul_precision("highest"):
        got, _ = model.apply({"params": params}, jnp.asarray(tokens)[None])
    m = _lfm2_section(True)
    ref = reference_lfm2moe.forward_logits(params, jnp.asarray(tokens), m)
    return {"params": params, "tokens": tokens, "m": m,
            "got": np.asarray(got[0]), "ref": np.asarray(ref)}


def test_lfm2moe_reference_against_the_model_code(lfm2_toy):
    scale = lfm2_toy["ref"].std()
    assert np.abs(lfm2_toy["got"] - lfm2_toy["ref"]).max() < 1e-4 * scale


@pytest.mark.parametrize("name", LFM2_CONTROLS)
def test_lfm2moe_controls_compute_another_model(lfm2_toy, name):
    import jax.numpy as jnp
    from benchmarks.harness import reference_lfm2moe
    assert reference_lfm2moe.CONTROLS == LFM2_CONTROLS
    wrong = np.asarray(reference_lfm2moe.forward_logits(
        lfm2_toy["params"], jnp.asarray(lfm2_toy["tokens"]),
        dict(lfm2_toy["m"], controls=frozenset([name]), bucket=64,
             prompt_len=30)))
    assert wrong.shape == lfm2_toy["ref"].shape
    err = np.abs(wrong - lfm2_toy["ref"]).max() / lfm2_toy["ref"].std()
    assert err > 0.2, err
    if name == "conv_state_to_bucket_end":
        # the prompt's own positions are the plain run's
        assert np.abs(wrong[:30] - lfm2_toy["ref"][:30]).max() \
            < 1e-4 * lfm2_toy["ref"].std()


def test_lfm2moe_costs_count_the_published_model_and_the_cut():
    import jax
    import jax.numpy as jnp
    from benchmarks.harness import costs_lfm2moe, modelcfg, replica_lfm2moe
    from ray_tpu.models import Hybrid
    m = _lfm2_section()
    assert (costs_lfm2moe.conv_layers(m), costs_lfm2moe.full_layers(m),
            costs_lfm2moe.expert_layers(m)) == (7, 2, 8)
    # ISSUE 40's arithmetic
    assert costs_lfm2moe.expert_params(m) == 9437184
    assert costs_lfm2moe.conv_mixer_params(m) == 16783360
    assert costs_lfm2moe.full_mixer_params(m) == 10485760
    assert costs_lfm2moe.dense_mlp_params(m) == 72351744
    assert costs_lfm2moe.kv_bytes_per_token(m) == 4096
    assert costs_lfm2moe.state_bytes_per_slot(m) == 57344
    # the count is the program's own parameter tree's
    model = Hybrid(replica_lfm2moe.hybrid_config(
        modelcfg.load(LFM2_CONFIG, False), param_dtype=jnp.bfloat16))
    shapes = jax.eval_shape(lambda k: model.init(
        k, jnp.zeros((1, 8), jnp.int32))["params"], jax.random.PRNGKey(0))
    held = sum(int(np.prod(a.shape))
               for a in jax.tree_util.tree_leaves(shapes))
    assert costs_lfm2moe.total_params(m) == held == 5177950976
    whole = costs_lfm2moe.total_params(dict(
        m, num_hidden_layers=40, num_dense_layers=2,
        layer_types=PERIOD * 10))
    assert 23.5e9 < whole < 24.1e9                            # 24B-class
    assert costs_lfm2moe.kv_bytes_per_token(dict(
        m, layer_types=PERIOD * 10)) == 20480
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    # a decode step of 128 rows: 512 pairs a layer over all 64 experts
    ex = costs_lfm2moe.expert_matmuls(m, 8 * 512, 8 * 64)
    assert ex["bytes"] == 8 * 64 * 9437184 * 2 + 2 * 8 * 512 * 2048 * 2
    assert costs_lfm2moe.least_seconds(ex, peaks)["bound"] == "memory"
    assert costs_lfm2moe.least_seconds(ex, peaks)["seconds"] \
        == pytest.approx(11.8e-3, rel=0.01)
    paged = costs_lfm2moe.paged_attention(m, 100.0, 64)
    assert paged["bytes"] == 100 * 64 * 4096
    step = costs_lfm2moe.decode_step(m, [800] * 128, 8 * 64, 8 * 512)
    assert step["bytes"] == (costs_lfm2moe.always_read_params(m) * 2
                             + ex["bytes"] + 128 * 800 * 4096
                             + 2 * 128 * 57344)
    assert 0.85 < ex["bytes"] / step["bytes"] < 0.95
    assert costs_lfm2moe.least_seconds(step, peaks)["bound"] == "memory"


def _lfm2_run(**kw):
    steps, layers = 10, 8
    run = {"model": _lfm2_section(),
           "config": {"engine": {"kv_page_size": 64}},
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
           "stats0": {"decode_steps": 0, "decode_pages_live": 0,
                      "prefill_calls": 0, "moe_assignments": 0,
                      "moe_experts_touched": 0},
           "stats1": {"decode_steps": steps, "prefill_calls": 0,
                      "decode_pages_live": steps * 128 * 13,
                      "moe_assignments": steps * layers * 512,
                      "moe_experts_touched": steps * layers * 64},
           "trace_contexts": [800] * 128,
           "trace": {"busy_s": 1.0,
                     "modules": {"jit__decode_paged_step":
                                 {"count": 5, "seconds": 0.1}},
                     "ops": {"gmm.3": 0.07,
                             "paged_decode_attention.2": 0.005,
                             "fusion": 0.02}}}
    run.update(kw)
    return run


def test_lfm2moe_readers_read_and_read_none_without_the_counters():
    from benchmarks import run as runmod
    from benchmarks.harness import costs_lfm2moe
    run = _lfm2_run()
    m, peaks = run["model"], run["peaks"]
    least = costs_lfm2moe.least_seconds(
        costs_lfm2moe.expert_matmuls(m, 8 * 512, 8 * 64), peaks)["seconds"]
    got = runmod.read_metric(BENCH, "expert_matmul_roofline.lfm2moe", run)
    assert got == pytest.approx(100 * least / (0.07 / 5)) and got < 100
    least = costs_lfm2moe.least_seconds(
        costs_lfm2moe.paged_attention(m, 128 * 13, 64), peaks)["seconds"]
    got = runmod.read_metric(BENCH, "paged_kernel_roofline.packed", run)
    assert got == pytest.approx(100 * least / (0.005 / 5)) and got < 100
    # the reader the cell is NOT listed under counts K and V in all 9
    # layers, not in the 2 that have them
    assert runmod.read_metric(BENCH, "paged_kernel_roofline", run) \
        == pytest.approx(4.5 * got) and 4.5 * got > 105
    least = costs_lfm2moe.least_seconds(costs_lfm2moe.decode_step(
        m, [800] * 128, 8 * 64, 8 * 512), peaks)["seconds"]
    got = runmod.read_metric(BENCH, "decode_step_roofline.lfm2moe", run)
    assert got == pytest.approx(100 * least / 0.02) and got < 100
    # a program without the counters, the kernels or the family: nothing
    old = _lfm2_run(stats0={"decode_steps": 0}, stats1={"decode_steps": 10})
    dense = _lfm2_run(model={"hidden_size": 4096, "num_hidden_layers": 16})
    for name in ("expert_matmul_roofline.lfm2moe",
                 "paged_kernel_roofline.packed",
                 "decode_step_roofline.lfm2moe"):
        assert runmod.read_metric(BENCH, name, old) is None, name
        assert runmod.read_metric(BENCH, name, dense) is None, name
        assert runmod.read_metric(BENCH, name, _lfm2_run(trace=None)) is None
        assert runmod.read_metric(BENCH, name, {}) is None


def test_the_lfm2moe_cell_resolves_to_its_own_runner_and_files():
    from benchmarks import run as runmod
    from benchmarks.harness import modelcfg, schedule
    manifest = runmod.load_manifest()
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    found = runmod.resolve(manifest, LFM2_CELL)
    assert found["cell"]["chips"] == 1
    cfg = modelcfg.load(found["config_path"], False)
    assert found["config_entry"]["reduced"] == list(cfg["reduced"]) == [
        "num_hidden_layers", "num_dense_layers"]
    assert found["config_entry"]["source"] == cfg["source"]
    # every published key as published, but the two cuts
    here = {"num_hidden_layers": 9, "num_dense_layers": 1}
    for key, published in LFM2_PUBLISHED.items():
        if key in here:
            assert cfg["reduced"][key]["published"] == published
            assert cfg["reduced"][key]["here"] == cfg[key] == here[key]
        else:
            assert cfg[key] == published, key
    # two whole periods behind the leading layer
    assert _lfm2_section()["layer_types"] == (PERIOD * 3)[:9]
    assert cfg["head_dim"] * cfg["num_attention_heads"] == cfg["hidden_size"]
    for key in ("head_dim", "tie_word_embeddings", "block", "conv_layer",
                "qk_norm", "rope", "routing", "weights", "eos", "tokenizer"):
        assert len(cfg["assumed"][key]) > 40, key
    assert cfg["deployment_it_stands_for"] and cfg["memory_analysis"]["how"]
    mem = cfg["memory_analysis"]
    assert (mem["kv_bytes_per_token"], mem["state_bytes_per_slot"],
            mem["parameters"]) == (4096, 57344, 5177950976)
    assert mem["kv_bytes_per_token_as_published"] == 4096
    eng = cfg["engine"]
    assert (eng["max_slots"], eng["max_seq_len"], eng["kv_page_size"],
            eng["prefill_chunk"], eng["decode_block"],
            eng["eos_token_id"]) == (128, 4096, 64, 0, 1, None)
    assert eng["kv_pool_tokens"] >= 1280 * 128
    assert cfg["deployment"]["max_ongoing_requests"] == 128
    runner = importlib.import_module("benchmarks.runners." + cfg["runner"])
    from benchmarks.runners import serve_http
    assert runner.serve_http is serve_http      # the one run(), not a copy
    assert set(runner.lfm2moe_family()) == set(serve_http.llama_family())
    assert runner.lfm2moe_family()["probe"].__name__ == "lfm2_preset"
    with open(runner.__file__) as f:
        assert len(f.read().splitlines()) < 30
    with open(found["traffic_path"]) as f:
        traffic = json.load(f)
    with open(os.path.join(BENCH, "traffic", "decode_sat_sarvam.json")) as f:
        sarvam = json.load(f)
    # decode_sat_sarvam's lengths: the cells differ in the model alone
    for key in ("prompt_len", "output_len", "gaps", "block", "ramp_s",
                "drain_s", "trace", "request_timeout_s"):
        assert traffic[key] == sarvam[key], key
    # 600 tokens, not a bucket size: the conv state's stop at the
    # prompt's true length inside the 1 024 bucket is part of `correct`
    assert traffic["check"] == {"prompt_len": 600, "new_tokens": 8}
    assert 600 not in eng["prefill_buckets"]
    assert cfg["check"]["busy_new_tokens"] >= 32
    answers = schedule.length_multiset(traffic["output_len"], 32)
    assert traffic["rate_over_knee"] == pytest.approx(
        traffic["rate_rps"] / traffic["knee_rps"], abs=0.01) == 1.15
    assert traffic["offered_tok_s"] == pytest.approx(
        traffic["rate_rps"] * sum(answers) / 32, abs=0.1)
    small = modelcfg.load(found["config_path"], True)
    assert small["hidden_size"] == 64 and small["num_experts"] == 8
    assert _lfm2_section(True)["layer_types"] == PERIOD + ["conv"]
    layer = {x["name"]: x for x in runmod.cell_metrics(manifest, LFM2_CELL,
                                                       "per_layer")}
    for name in ("expert_matmul_roofline.lfm2moe",
                 "paged_kernel_roofline.packed",
                 "decode_step_roofline.lfm2moe"):
        assert layer[name]["workloads"] == [LFM2_CELL]
        assert (layer[name]["moves"], layer[name]["unit"],
                layer[name]["better"]) == ("out_tok_s", "%", "higher")
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            assert json.load(f)["reader"] == "lfm2moe_roofline"
    assert {"moe_dev_share", "moe_expert_load_max_over_mean",
            "moe_pad_row_share", "decode_live_state_share",
            "decode_live_page_share", "engine_runtime_calls_per_step",
            "consumer_loop_cpu_share", "device_idle_share"} <= set(layer)
    # K-and-V-in-every-layer and other-width arithmetic is not for it
    assert not {"paged_kernel_roofline", "decode_step_roofline",
                "expert_matmul_roofline", "decode_step_roofline.moe",
                "paged_kernel_roofline.hybrid",
                "decode_step_roofline.hybrid"} & set(layer)
    e2e = {x["name"] for x in runmod.cell_metrics(manifest, LFM2_CELL,
                                                  "end_to_end")}
    assert e2e == {"out_tok_s", "setup_s"}


def test_a_file_the_lfm2moe_family_cannot_take_is_refused_at_once():
    from benchmarks.harness import modelcfg, replica_lfm2moe
    cfg = modelcfg.load(LFM2_CONFIG, False)
    with pytest.raises(SystemExit, match="derives head_dim"):
        replica_lfm2moe.model_section(dict(cfg, head_dim=128))
    for wrong in (dict(conv_bias=True), dict(tie_word_embeddings=False),
                  dict(num_hidden_layers=48), dict(rope_theta=10000.0),
                  dict(num_dense_layers=9), dict(rms_norm_eps=1e-6)):
        with pytest.raises(SystemExit, match="this file disagrees"):
            replica_lfm2moe.model_section(dict(cfg, **wrong))
    with pytest.raises(SystemExit, match="lacks"):
        replica_lfm2moe.model_section(
            {k: v for k, v in cfg.items() if k != "conv_L_cache"})
    mistral = modelcfg.load(os.path.join(
        BENCH, "configs", "mistral-7b-v0.3-serve-l16.json"), False)
    with pytest.raises(SystemExit, match="lacks"):
        replica_lfm2moe.model_section(mistral)
    preset = replica_lfm2moe.lfm2_preset()()
    assert (preset.d_model, preset.n_layers) == (2048, 40)
