"""The Xing4.0 reference by itself (its independence, its residual path
entry by entry, its routing and its controls), the costs the cell adds,
and that the cell's runner, files and metrics resolve by name."""
import ast
import json
import math
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
XING_CELL = "xing29b_decode_sat"
CONFIG = os.path.join(BENCH, "configs", "xing4.0-29b-a4b-serve-l6.json")
PUBLISHED = {"num_hidden_layers": 40, "first_k_dense_replace": 2}


def _file() -> dict:
    with open(CONFIG) as f:
        return json.load(f)


def _section(rehearse: bool = False) -> dict:
    from benchmarks.harness import modelcfg, replica_xing
    return replica_xing.model_section(modelcfg.load(CONFIG, rehearse))


def test_xing_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "harness", "reference_xing.py")) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            names.add((node.module or ".").split(".")[0])
            assert node.level == 0, "no relative import either"
    assert names <= {"__future__", "jax", "math"}, names


def test_xing_file_holds_every_published_number_and_states_its_cut():
    cfg = _file()
    published = {
        "hidden_size": 3584, "intermediate_size": 9216,
        "kv_lora_rank": 512, "q_lora_rank": 768,
        "moe_intermediate_size": 1024, "n_routed_experts": 64,
        "n_shared_experts": 1, "num_attention_heads": 32,
        "num_key_value_heads": 32, "num_experts_per_tok": 4,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "vocab_size": 131072, "hc_mult": 4, "hc_sinkhorn_iters": 20,
        "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
        "mhc_h_res_clamp_max": 30, "routed_scaling_factor": 2,
        "n_group": 1, "topk_group": 1, "num_nextn_predict_layers": 1,
        "max_position_embeddings": 262144, "rope_theta": 10000,
        "moe_layer_freq": 1, "ep_size": 1, "rms_norm_eps": 1e-6}
    assert {k: cfg[k] for k in published} == published
    assert cfg["rope_scaling"]["factor"] == 64 \
        and cfg["rope_scaling"]["original_max_position_embeddings"] == 4096
    assert (cfg["scoring_func"], cfg["topk_method"], cfg["model_type"]) == (
        "sigmoid", "noaux_tc", "xing4_0")
    # the cut: depth only, and what it stands for
    assert set(cfg["reduced"]) == set(PUBLISHED)
    assert {k: (v["published"], v["here"])
            for k, v in cfg["reduced"].items()} == {
        "num_hidden_layers": (40, 6), "first_k_dense_replace": (2, 1)}
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"]) == (6, 1)
    assert "7 chips" in cfg["deployment_it_stands_for"]
    assert cfg["num_nextn_predict_layers_served"]["here"] == 0
    for key in ("sinkhorn_order", "hc_eps", "res_clamp",
                "stream_entry_exit", "mapping_norm", "mapping_draws",
                "block_norms"):
        assert key in cfg["assumed"], key
    eng = cfg["engine"]
    assert (eng["max_slots"], eng["kv_page_size"], eng["kv_pool_tokens"],
            eng["max_seq_len"], eng["prefill_buckets"]) == (
                128, 64, 262144, 4096, [128, 256, 512, 1024, 2048])
    assert eng["max_slots"] * cfg["num_experts_per_tok"] \
        / cfg["n_routed_experts"] == 8          # rows an expert a step
    with open(os.path.join(BENCH, "traffic", "decode_sat_xing.json")) as f:
        traffic = json.load(f)
    with open(os.path.join(BENCH, "traffic", "decode_sat_sarvam.json")) as f:
        sarvam = json.load(f)
    for key in ("prompt_len", "output_len", "gaps", "block", "ramp_s",
                "drain_s", "rate_over_knee"):
        assert traffic[key] == sarvam[key], key
    assert traffic["check"] == {"prompt_len": 600, "new_tokens": 8}
    assert traffic["rate_rps"] == pytest.approx(
        1.15 * traffic["knee_rps"], rel=0.01)


def test_xing_section_refuses_a_file_the_reference_cannot_compute():
    from benchmarks.harness import replica_xing
    cfg = dict(_file())
    assert replica_xing.model_section(cfg)["hc_mult"] == 4
    for key, wrong in (("scoring_func", "softmax"), ("n_group", 8),
                       ("tie_word_embeddings", True),
                       ("first_k_dense_replace", 6)):
        with pytest.raises(SystemExit, match="disagrees"):
            replica_xing.model_section(dict(cfg, **{key: wrong}))
    with pytest.raises(SystemExit, match="lacks"):
        replica_xing.model_section({k: v for k, v in cfg.items()
                                    if k != "hc_sinkhorn_iters"})


def test_a_program_without_the_preset_fails_the_probe_at_once(monkeypatch):
    import ray_tpu.models as models
    from benchmarks.harness import replica_xing
    assert callable(replica_xing.xing_preset())

    class Parent:                   # a LatentMoEConfig before this family
        sarvam_105b = staticmethod(lambda **kw: None)
    monkeypatch.setattr(models, "LatentMoEConfig", Parent)
    with pytest.raises(SystemExit, match="cannot run a Xing4.0"):
        replica_xing.xing_preset()


def test_xing_sinkhorn_is_the_loop_over_one_tokens_entries():
    import jax.numpy as jnp
    from benchmarks.harness import reference_xing as ref
    m = dict(_section(True))
    rng = np.random.default_rng(0)
    mat = np.exp(rng.normal(0, 1.5, (4, 4)))
    want = mat.copy()
    for _ in range(20):
        want = want / (want.sum(0, keepdims=True) + 1e-6)
        want = want / (want.sum(1, keepdims=True) + 1e-6)
    got = ref._sinkhorn_token(jnp.asarray(mat, jnp.float32), m, jnp.float32)
    np.testing.assert_allclose(got, want, rtol=2e-5)
    assert abs(float(got.sum(0).max()) - 1) < 1e-3
    two = ref._sinkhorn_token(jnp.asarray(mat, jnp.float32),
                              dict(m, controls={"sinkhorn_2_iters"}),
                              jnp.float32)
    assert float(jnp.abs(two - got).max()) > 5e-3
    half = ref._sinkhorn_token(jnp.asarray(mat, jnp.bfloat16),
                               dict(m, controls={"bf16_mapping"}),
                               jnp.bfloat16)
    assert half.dtype == jnp.bfloat16
    assert 1e-4 < float(jnp.abs(half.astype(jnp.float32) - got).max()) < 0.1


def test_xing_sub_layer_mixes_the_streams_as_the_equations_say():
    import jax.numpy as jnp
    from benchmarks.harness import reference_xing as ref
    m = dict(_section(True))
    n, c, s = m["hc_mult"], m["hidden_size"], 5
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(s, n * c)), jnp.float32)
    p = {"hc_attn_phi": jnp.asarray(
        rng.normal(size=(n * n + 2 * n, n * c)) / math.sqrt(n * c),
        jnp.float32),
        "hc_attn_b": jnp.asarray(rng.normal(size=(n * n + 2 * n,)),
                                 jnp.float32),
        "hc_attn_a": jnp.asarray([1.0, 0.5, 2.0], jnp.float32)}
    pre, post, res = ref.mappings(x, p, "attn", m)
    assert pre.shape == (s, n) and res.shape == (s, n, n)
    assert float(pre.min()) > 0 and float(pre.max()) < 1
    assert float(post.min()) > 0 and float(post.max()) < 2
    np.testing.assert_allclose(res.sum(-1), 1.0, atol=1e-3)
    np.testing.assert_allclose(res.sum(-2), 1.0, atol=5e-3)
    out, extra = ref.sub_layer(x, p, "attn", m, lambda h: (2.0 * h, "e"))
    assert extra == "e"
    xs = np.asarray(x).reshape(s, n, c)
    h = np.einsum("si,sic->sc", np.asarray(pre), xs)
    want = (np.einsum("sij,sjc->sic", np.asarray(res), xs)
            + np.asarray(post)[:, :, None] * (2.0 * h)[:, None, :])
    np.testing.assert_allclose(np.asarray(out).reshape(s, n, c), want,
                               rtol=1e-5, atol=1e-5)
    without, _ = ref.sub_layer(x, p, "attn", dict(
        m, controls={"hpost_without_2"}), lambda h: (2.0 * h, None))
    assert float(jnp.abs(without - out).max()) > 1e-2


def test_xing_reference_routes_by_biased_sigmoid_with_no_group_stage():
    import jax.numpy as jnp
    from benchmarks.harness import reference_xing as ref
    m = dict(_section(True), num_experts_per_tok=2, n_routed_experts=4,
             routed_scaling_factor=2.0)
    g = jnp.eye(4, dtype=jnp.float32)[:1]
    moe = {"router_kernel": jnp.asarray(
        [[2.0, 0.0, -1.0, 1.0], [0.0] * 4, [0.0] * 4, [0.0] * 4],
        jnp.float32),
        "router_bias": jnp.asarray([-1.0, 0.0, 1.0, 0.0], jnp.float32)}
    s = 1.0 / (1.0 + np.exp(-np.asarray([2.0, 0.0, -1.0, 1.0])))
    weights, info = ref.routing(g, moe, m)
    assert np.asarray(info["chosen"][0]).tolist() == [False, False, True,
                                                      True]
    np.testing.assert_allclose(
        weights[0], [0, 0, *(2.0 * s[2:] / s[2:].sum())], rtol=1e-6)
    plain, _ = ref.routing(g, moe, dict(m, controls={"select_without_bias"}))
    assert float(plain[0, 0]) > 0
    unscaled, _ = ref.routing(g, moe, dict(m, controls={"no_scaling"}))
    np.testing.assert_allclose(unscaled * 2.0, weights, rtol=1e-6)
    assert ref.softmax_scale(_section()) == pytest.approx(0.14468, abs=5e-6)
    assert ref.softmax_scale(dict(_section(), controls={
        "scale_without_yarn"})) == pytest.approx(192 ** -0.5)


def test_xing_costs_count_the_published_model_and_the_cut():
    import jax
    import jax.numpy as jnp
    from benchmarks.harness import costs_xing, modelcfg, replica_xing
    from ray_tpu.models import LatentMoE
    m = _section()
    assert costs_xing.attention_params(m) == 28409856
    assert costs_xing.expert_params(m) == 11010048
    assert 2 * costs_xing.mapping_params(m) == 688128
    # an expert layer 744.98 M, the dense layer 128.19 M, embedding +
    # head 939.52 M (ISSUE 48's arithmetic, to the norm weights)
    cut = costs_xing.total_params(m)
    assert cut == 4792669828
    # the program's own count at the cut, from abstract shapes
    model = LatentMoE(replica_xing.latent_moe_config(
        modelcfg.load(CONFIG, False), param_dtype=jnp.bfloat16))
    shapes = jax.eval_shape(lambda k: model.init(
        k, jnp.zeros((1, 8), jnp.int32))["params"], jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_leaves(shapes)
    assert sum(math.prod(a.shape) for a in leaves) == cut
    assert sum(math.prod(a.shape) * a.dtype.itemsize
               for a in leaves) / 2 ** 30 == pytest.approx(8.93, abs=0.005)
    whole = costs_xing.total_params(dict(m, **PUBLISHED))
    assert whole == pytest.approx(29.5e9, rel=0.01)
    assert whole == 38 * 744_980_000 + 2 * 128_190_000 + 939_520_000 \
        or abs(whole - (38 * 744.98e6 + 2 * 128.19e6 + 939.52e6)) < 2e6
    # the pool row as it lies in HBM, and a decode step's bytes
    assert costs_xing.pool_row_bytes(m) == 1280
    rows = costs_xing.hc_kernels(m, 129 * 12, 12)
    assert rows["bytes"] == 129 * 12 * ((3 * 4 + 2) * 3584 * 2 + 2 * 26 * 4) \
        + 12 * 24 * 14336 * 2
    step = costs_xing.decode_step(m, [900] * 128, 5 * 64, 128 * 4 * 5)
    assert 9.0e9 < step["bytes"] < 11.5e9
    least = costs_xing.least_seconds(step, {"bf16_flops": 197e12,
                                            "hbm_bytes_per_s": 819e9})
    assert least["bound"] == "memory"


def test_the_xing_cell_resolves_to_its_own_runner_and_files():
    from benchmarks import run as runmod
    whole = runmod.load_manifest()
    found = runmod.resolve(whole, XING_CELL)
    assert found["config_path"] == CONFIG
    assert found["traffic_path"].endswith("decode_sat_xing.json")
    cfg = _file()
    assert cfg["runner"] == "serve_http_xing"
    from benchmarks.runners import serve_http_xing
    family = serve_http_xing.xing_family()
    assert family["probe"] is not None
    assert family["server_cls"].__name__ == "XingBenchServer"
    names = {m["name"] for m in runmod.cell_metrics(whole, XING_CELL,
                                                    "per_layer")}
    assert {"hc_kernel_dev_share", "hc_kernel_roofline",
            "hc_unconverged_share", "hc_clamped_share",
            "latent_kernel_roofline.xing", "expert_matmul_roofline.xing",
            "decode_step_roofline.xing", "latent_kernel_dev_share",
            "moe_dev_share", "device_idle_share",
            "engine_device_wait_share"} <= names
    assert not {"latent_kernel_roofline", "expert_matmul_roofline.share",
                "decode_step_roofline.latent_moe"} & names
    assert {m["name"] for m in runmod.cell_metrics(
        whole, XING_CELL, "end_to_end")} == {"out_tok_s", "setup_s"}
    check = cfg["check"]
    for key in ("logit_tol_rel", "logit_mean_tol_rel",
                "logit_decode_mean_tol_rel", "argmax_tol_rel",
                "tie_margin_rel", "busy_new_tokens", "why"):
        assert key in check, key
