"""The Solar-Open2-250B reference by itself (its independence, the delta
rule with a decay a channel against the matrix equation written out in
numpy, the expert share by hand, its agreement with the program's model
code, its controls), the costs and readers the cell adds, and that the
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
SOLAR_CELL = "solar250b_decode_sat"
SOLAR_CONFIG = os.path.join(BENCH, "configs",
                            "solar-open2-250b-serve-ep8-l4.json")
# config.json of upstage/Solar-Open2-250B as the catalog has it
SOLAR_PUBLISHED = {
    "model_type": "solar_open2", "partial_rotary_factor": 1,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                           "num_heads": 64, "num_kv_heads": None},
    "hidden_size": 4096, "num_hidden_layers": 48,
    "num_attention_heads": 64, "head_dim": 128, "num_key_value_heads": 8,
    "vocab_size": 196608, "intermediate_size": 10240,
    "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05,
    "rope_theta": 10000, "tie_word_embeddings": False,
    "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
    "use_rope": False, "gqa_interval": 3,
    "gqa_layers": list(range(0, 48, 4)), "use_gqa_gate": True,
    "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
    "n_routed_experts": 320, "n_shared_experts": 1, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "num_experts_per_tok": 8}
SOLAR_CONTROLS = ("bf16_state", "scalar_decay", "beta_without_2",
                  "no_decay", "state_to_bucket_end", "no_out_gate",
                  "no_shared", "norm_over_held", "int8_weights")


def _solar_section(rehearse: bool = False) -> dict:
    from benchmarks.harness import modelcfg, replica_solar
    return replica_solar.model_section(modelcfg.load(SOLAR_CONFIG,
                                                     rehearse))


def test_solar_reference_imports_nothing_of_the_program():
    path = os.path.join(BENCH, "harness", "reference_solar.py")
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


def _kda_params(rng, d, h, hd, r, k):
    import jax.numpy as jnp

    def mat(*shape):
        return jnp.asarray(rng.normal(size=shape) * shape[0] ** -0.5,
                           jnp.float32)
    return {"qkv_proj": {"kernel": mat(d, 3 * h * hd)},
            "f_a_proj": {"kernel": mat(d, r)},
            "f_b_proj": {"kernel": mat(r, h * hd)},
            "g_a_proj": {"kernel": mat(d, r)},
            "g_b_proj": {"kernel": mat(r, h * hd)},
            "b_proj": {"kernel": mat(d, h)},
            "o_proj": {"kernel": mat(h * hd, d)},
            "conv_kernel": jnp.asarray(rng.normal(size=(k, 3 * h * hd)),
                                       jnp.float32),
            "A_log": jnp.asarray(np.log(rng.uniform(0.1, 4.0, h)),
                                 jnp.float32),
            "dt_bias": jnp.asarray(rng.normal(size=(h, hd)), jnp.float32),
            "o_norm": jnp.asarray(1 + 0.2 * rng.normal(size=hd),
                                  jnp.float32)}


def test_solar_kda_layer_is_the_matrix_equation_written_out():
    """S_t = S_{t-1} Diag(alpha_t) (I - beta_t k_t k_t^T) + beta_t v_t
    k_t^T, o_t = S_t q_t, a head at a time in float64 numpy with the
    matrices formed, against the reference's scan; and a decay equal in
    every channel of a head is the scalar rule alpha S (I - ...)."""
    import jax
    from benchmarks.harness import reference_solar as ref
    rng = np.random.default_rng(0)
    d, h, hd, r, kk, s = 12, 2, 4, 3, 4, 9
    p = _kda_params(rng, d, h, hd, r, kk)
    m = {"linear_attn_config": {"num_heads": h, "head_dim": hd,
                                "short_conv_kernel_size": kk},
         "kda_allow_neg_eigval": True, "rms_norm_eps": 1e-5}
    u = np.asarray(rng.normal(size=(s, d)), np.float32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.kda_mixer(u, p, m))
    f = {k: np.asarray(v["kernel"] if isinstance(v, dict) else v,
                       np.float64) for k, v in p.items()}
    x = u.astype(np.float64)
    qkv = np.vstack([np.zeros((kk - 1, 3 * h * hd)), x @ f["qkv_proj"]])
    conv = sum(f["conv_kernel"][j] * qkv[kk - 1 - j:kk - 1 - j + s]
               for j in range(kk))
    conv = conv / (1 + np.exp(-conv))                         # SiLU

    def heads(block):
        return conv[:, block * h * hd:(block + 1) * h * hd].reshape(
            s, h, hd)
    q, k, v = heads(0), heads(1), heads(2)
    q = q / np.sqrt((q * q).sum(-1, keepdims=True) + 1e-6) * hd ** -0.5
    k = k / np.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    a = (x @ f["f_a_proj"] @ f["f_b_proj"]).reshape(s, h, hd)
    alpha = np.exp(-np.exp(f["A_log"])[:, None]
                   * np.log1p(np.exp(a + f["dt_bias"])))
    beta = 2 / (1 + np.exp(-(x @ f["b_proj"])))
    out = np.zeros((s, h, hd))
    for head in range(h):
        state = np.zeros((hd, hd))                            # (d_v, d_k)
        for t in range(s):
            kt = k[t, head][:, None]
            state = state @ np.diag(alpha[t, head]) @ (
                np.eye(hd) - beta[t, head] * kt @ kt.T) \
                + beta[t, head] * v[t, head][:, None] @ kt.T
            out[t, head] = state @ q[t, head]
    out = out / np.sqrt((out * out).mean(-1, keepdims=True) + 1e-5) \
        * f["o_norm"]
    gate = 1 / (1 + np.exp(-(x @ f["g_a_proj"] @ f["g_b_proj"])))
    want = (out.reshape(s, h * hd) * gate) @ f["o_proj"]
    np.testing.assert_allclose(got, want, atol=2e-5 * np.abs(want).max())
    # the control: one rate a head, the mean of its channels'
    with jax.default_matmul_precision("highest"):
        scalar = np.asarray(ref.kda_mixer(
            u, p, dict(m, controls={"scalar_decay"})))
    assert np.abs(scalar - got).max() > 1e-2 * np.abs(got).max()


def test_solar_expert_share_by_hand():
    """Four experts, two a token, a bias that changes the selection, a
    share that holds experts 2 and 3: scores sigmoid(2, 1, 0, -1); by
    score experts 0 and 1, with the bias (0, -1, 0, 0.6) experts 0 and
    3; the weights are the SCORES of those two over (their sum + 1e-6),
    normalised over both wherever they live; the share computes expert
    3's part and the shared expert. Each expert is a SwiGLU of width 1
    whose gate and up read h[0] and whose down writes e + 1 into
    column 0."""
    import jax
    import jax.numpy as jnp
    from benchmarks.harness import reference_solar as ref
    hdim = 4
    gate = np.zeros((4, hdim, 1), np.float32)
    gate[:, 0, 0] = 1.0
    down = np.zeros((4, 1, hdim), np.float32)
    down[:, 0, 0] = [1., 2., 3., 4.]
    shared = {"gate_proj": {"kernel": jnp.asarray(gate[0])},
              "up_proj": {"kernel": jnp.asarray(gate[0])},
              "down_proj": {"kernel": jnp.asarray(10 * down[0])}}
    router = np.zeros((hdim, 4), np.float32)
    router[1] = [2., 1., 0., -1.]
    moe = {"router_kernel": jnp.asarray(router),
           "router_bias": jnp.asarray([0., -1., 0., 0.6]),
           "experts_gate_kernel": jnp.asarray(gate[2:]),
           "experts_up_kernel": jnp.asarray(gate[2:]),
           "experts_down_kernel": jnp.asarray(down[2:]), "shared": shared}
    m = {"num_experts_per_tok": 2, "norm_topk_prob": True,
         "routed_scaling_factor": 1.0, "num_experts": 2, "expert_first": 2,
         "router_width": 4, "n_shared_experts": 1}
    h = jnp.asarray([[2., 1., 0., 0.]])
    with jax.default_matmul_precision("highest"):
        y, info = ref.expert_layer(h, moe, m)
        every, _ = ref.routing(h, moe, m)
    s0, s3 = 1 / (1 + np.exp(-2.0)), 1 / (1 + np.exp(1.0))
    assert np.asarray(info["chosen"]).tolist() == [[True, False, False,
                                                    True]]
    np.testing.assert_allclose(
        np.asarray(every)[0], [s0 / (s0 + s3 + 1e-6), 0, 0,
                               s3 / (s0 + s3 + 1e-6)], rtol=1e-6)
    act = 2.0 / (1 + np.exp(-2.0)) * 2.0          # SiLU(2) * 2
    want = s3 / (s0 + s3 + 1e-6) * 4 * act + 10 * act
    np.testing.assert_allclose(np.asarray(y)[0], [want, 0, 0, 0],
                               rtol=1e-5)
    # the controls: the shared expert left out; normalised over the one
    # selected expert held (weight 1)
    with jax.default_matmul_precision("highest"):
        bare, _ = ref.expert_layer(h, moe, dict(m, controls={"no_shared"}))
        held, _ = ref.expert_layer(h, moe,
                                   dict(m, controls={"norm_over_held"}))
    np.testing.assert_allclose(np.asarray(bare)[0, 0], want - 10 * act,
                               rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(held)[0, 0], s3 / (s3 + 1e-6) * 4 * act + 10 * act,
        rtol=1e-5)
    # a system's other choice is followed inside the margin only
    theirs = jnp.asarray([[0, 2]])
    _, near = ref.routing(h, moe, m, follow=theirs, tie_margin=0.9)
    _, far = ref.routing(h, moe, m, follow=theirs, tie_margin=0.01)
    assert not bool(near["not_followed"][0]) and not bool(near["own"][0])
    assert bool(far["not_followed"][0])
    assert np.asarray(near["chosen"]).tolist() == [[True, False, True,
                                                    False]]


@pytest.fixture(scope="module")
def solar_toy():
    """The program's tiny model in float32 holding experts 2..5 of 8,
    its logits on 50 tokens, and the reference's."""
    import jax
    import jax.numpy as jnp
    from benchmarks.harness import reference_solar
    from ray_tpu.models import Hybrid, HybridConfig
    cfg = HybridConfig.solar_debug(dtype=jnp.float32,
                                   param_dtype=jnp.float32, expert_first=2,
                                   expert_count=4)
    model = Hybrid(cfg)
    params = model.init_params(jax.random.PRNGKey(1))
    m = {"hidden_size": cfg.d_model, "num_hidden_layers": cfg.n_layers,
         "num_attention_heads": cfg.n_heads,
         "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
         "moe_intermediate_size": cfg.d_expert,
         "num_experts": cfg.experts_held, "router_width": cfg.n_experts,
         "expert_first": cfg.expert_first,
         "n_shared_experts": cfg.n_shared_experts,
         "num_experts_per_tok": cfg.experts_per_token,
         "norm_topk_prob": True, "routed_scaling_factor": 1.0,
         "rms_norm_eps": cfg.norm_eps, "vocab_size": cfg.vocab_size,
         "use_gqa_gate": True, "kda_allow_neg_eigval": True,
         "kda_rank": cfg.kda_rank, "gqa_layers": [0],
         "linear_attn_config": {"num_heads": cfg.linear_n_heads,
                                "head_dim": cfg.linear_key_dim,
                                "short_conv_kernel_size": 4}}
    tokens = np.random.default_rng(2).integers(1, 256, 50)
    with jax.default_matmul_precision("highest"):
        got, _ = model.apply({"params": params}, jnp.asarray(tokens)[None])
    ref = reference_solar.forward_logits(params, jnp.asarray(tokens), m)
    return {"params": params, "m": m, "tokens": tokens,
            "got": np.asarray(got[0]), "ref": np.asarray(ref)}


def test_solar_reference_against_the_model_code(solar_toy):
    scale = solar_toy["ref"].std()
    assert np.abs(solar_toy["got"] - solar_toy["ref"]).max() < 2e-4 * scale


@pytest.mark.parametrize("name", SOLAR_CONTROLS)
def test_solar_controls_compute_another_model(solar_toy, name):
    import jax.numpy as jnp
    from benchmarks.harness import reference_solar
    assert reference_solar.CONTROLS == SOLAR_CONTROLS
    wrong = np.asarray(reference_solar.forward_logits(
        solar_toy["params"], jnp.asarray(solar_toy["tokens"]),
        dict(solar_toy["m"], controls=frozenset([name]), bucket=64,
             prompt_len=30)))
    assert wrong.shape == solar_toy["ref"].shape
    err = np.abs(wrong - solar_toy["ref"]).max() / solar_toy["ref"].std()
    # the two of precision move a float32 toy's logits least
    assert err > (1e-3 if name in ("bf16_state", "int8_weights") else 0.2), \
        err
    if name == "state_to_bucket_end":
        # the prompt's own positions are the plain run's
        assert np.abs(wrong[:30] - solar_toy["ref"][:30]).max() \
            < 1e-4 * solar_toy["ref"].std()


def test_solar_costs_count_the_published_model_and_the_cut():
    """ISSUE 52's arithmetic: an expert 15.73 M, 40 held 629.1 M a
    layer, a delta-rule layer beside them 154.7 M, the full layer
    126.1 M, embedding and head 201 M: 3 308 M parameters; 12.4 MiB of
    state a slot, 4 096 B of K and V a token."""
    from benchmarks.harness import costs_solar as c
    m = _solar_section()
    assert (c.kda_layers(m), c.full_layers(m)) == (3, 1)
    assert c.expert_params(m) == 3 * 4096 * 1280 == 15_728_640
    assert c.state_elements(m) * 4 == 4 * 2 ** 20
    assert c.conv_width(m) == 3 * 8192
    kda = c.kda_mixer_params(m)
    assert kda == 4 * 4096 * 8192 + 2 * (4096 * 128 + 128 * 8192) \
        + 4096 * 64
    full = c.full_mixer_params(m)
    assert full == 3 * 4096 * 8192 + 2 * 4096 * 1024
    beside = c.expert_params(m) + c.router_params(m)
    assert round((kda + beside) / 1e6, 1) == 154.7
    assert round((full + beside) / 1e6, 1) == 126.1
    total = c.total_params(m)
    assert 3.30e9 < total < 3.32e9
    assert c.state_bytes_per_slot(m) == 3 * (4 * 2 ** 20 + 3 * 24576 * 2)
    assert round(c.state_bytes_per_slot(m) / 2 ** 20, 1) == 12.4
    assert c.kv_bytes_per_token(m) == 4096
    # the whole published model: 250 B parameters, ~15 B of them a token
    whole = dict(m, num_hidden_layers=48, num_experts=320,
                 vocab_size=196608, gqa_layers=list(range(0, 48, 4)))
    from benchmarks.harness import reference_solar
    whole["layer_types"] = reference_solar.layer_types(whole)
    assert 2.45e11 < c.total_params(whole) < 2.55e11
    active = c.always_read_params(whole) + 48 * 8 * c.expert_params(whole)
    assert 1.35e10 < active < 1.6e10
    # a decode step of 128 rows at ~900 tokens: the state kernel's bytes
    # a third, the experts' about half
    step = c.decode_step(m, [900] * 128, touched=4 * 38.5,
                         assignments=128 * 8 * 4 / 8)
    kda_bytes = c.kda_step(m, 128 * 3)["bytes"]
    experts = c.expert_matmuls(m, 128 * 4, 4 * 38.5)["bytes"]
    assert kda_bytes == 128 * 3 * 2 * 4 * 2 ** 20
    assert 0.28 < kda_bytes / step["bytes"] < 0.36
    assert 0.42 < experts / step["bytes"] < 0.55
    assert 9.0e9 < step["bytes"] < 11.0e9
    from benchmarks.harness.peaks import PEAKS
    least = c.least_seconds(step, PEAKS["TPU v5e"])
    assert least["bound"] == "memory" and 0.011 < least["seconds"] < 0.0135


def test_solar_readers_read_and_read_none_without_the_counters():
    import sys
    readers = os.path.join(BENCH, "readers")
    if readers not in sys.path:
        sys.path.insert(0, readers)
    import solar_roofline
    from benchmarks.harness import modelcfg
    from benchmarks.harness.peaks import PEAKS
    cfg = modelcfg.load(SOLAR_CONFIG, False)

    def reading(k, state=True):
        out = {"decode_steps": k, "prefill_calls": k // 40,
               "decode_pages_live": k * 128 * 14,
               "moe_assignments": k * 128 * 4,
               "moe_experts_touched": k * 38 * 4}
        if state:
            out["decode_state_rows_live"] = k * 128 * 3
        return out
    run = {"stats0": reading(1000), "stats1": reading(2000),
           "trace": {"busy_s": 2.0,
                     "ops": {"kda_decode_step": 0.5, "gmm": 0.7,
                             "paged_decode_attention": 0.08},
                     "modules": {"jit__decode_paged_step":
                                 {"count": 100, "seconds": 1.6}}},
           "peaks": PEAKS["TPU v5e"], "config": cfg,
           "model": _solar_section(), "trace_contexts": [900] * 128}
    args = dict(module_re="decode_paged")
    kda = solar_roofline.read(run, "kda_kernel", name_re="^kda_decode_step",
                              **args)
    assert kda == pytest.approx(
        100 * 128 * 3 * 8 * 2 ** 20 / 819e9 / 5e-3, rel=1e-3)
    experts = solar_roofline.read(run, "experts", name_re="^gmm", **args)
    paged = solar_roofline.read(run, "paged_kernel",
                                name_re="^paged_decode_attention", **args)
    step = solar_roofline.read(run, "step", **args)
    for value in (kda, experts, paged, step):
        assert 0.0 < value < 100.0
    assert set(run["notes"].values()) == {"memory"}
    with pytest.raises(ValueError):
        solar_roofline.read(run, "nothing", **args)
    # a program without the state counter, a trace without the kernel,
    # another family's section, an empty run: None, no exception
    bare = dict(run, stats0=reading(1000, False),
                stats1=reading(2000, False))
    assert solar_roofline.read(bare, "kda_kernel",
                               name_re="^kda_decode_step", **args) is None
    no_kernel = dict(run, trace=dict(run["trace"], ops={"fusion": 1.0}))
    for what, name_re in (("kda_kernel", "^kda_decode_step"),
                          ("experts", "^gmm"),
                          ("paged_kernel", "^paged_decode_attention")):
        assert solar_roofline.read(no_kernel, what, name_re=name_re,
                                   **args) is None
    other = dict(run, model={"hidden_size": 4096, "conv_L_cache": 3})
    assert solar_roofline.read(other, "step", **args) is None
    assert solar_roofline.read({}, "step", **args) is None


def test_the_solar_cell_resolves_to_its_own_runner_and_files():
    from benchmarks import run as runmod
    from benchmarks.harness import costs_solar, modelcfg, schedule
    manifest = runmod.load_manifest()
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) == 1
    found = runmod.resolve(manifest, SOLAR_CELL)
    assert found["cell"]["chips"] == 1
    cfg = modelcfg.load(found["config_path"], False)
    assert found["config_entry"]["reduced"] == list(cfg["reduced"]) == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert found["config_entry"]["source"] == cfg["source"] \
        == "https://huggingface.co/upstage/Solar-Open2-250B/blob/main/" \
           "config.json"
    # every published key as published, but the three cuts
    here = {"num_hidden_layers": 4, "n_routed_experts": 40,
            "vocab_size": 24576}
    for key, published in SOLAR_PUBLISHED.items():
        if key in here:
            assert cfg["reduced"][key]["published"] == published
            assert cfg["reduced"][key]["here"] == cfg[key] == here[key]
            assert len(cfg["reduced"][key]["why"]) > 40
        else:
            assert cfg[key] == published, key
    # the guide's floors: a whole period, 8 experts, an eighth of the
    # vocabulary; no width among the cuts
    m = _solar_section()
    assert m["layer_types"] == ["full_attention", "kda", "kda", "kda"]
    assert cfg["vocab_size"] * 8 == SOLAR_PUBLISHED["vocab_size"]
    ep = cfg["expert_parallel"]
    assert (ep["ways"], ep["rank"], ep["router_width"]) == (8, 0, 320)
    assert (m["num_experts"], m["router_width"], m["expert_first"]) \
        == (40, 320, 0)
    assert cfg["kda_rank"] == 128
    for key in ("kda_rank", "linear_layer", "kda_use_full_proj",
                "use_gqa_gate", "norm_placement", "scoring",
                "expert_groups", "state_dtype", "conv_bias", "weights",
                "eos", "tokenizer"):
        assert len(cfg["assumed"][key]) > 20, key
    assert "8 v5e chips" in cfg["deployment_it_stands_for"]
    mem = cfg["memory_analysis"]
    assert mem["how"] and mem["parameters"] == costs_solar.total_params(m)
    assert mem["state_bytes_per_slot"] \
        == costs_solar.state_bytes_per_slot(m)
    assert mem["kv_bytes_per_token"] == 4096
    # the fullest program over a quarter of the chip
    assert mem["prefill_2048x2_total_GiB"] > 0.25 * 15.75
    eng = cfg["engine"]
    assert (eng["max_slots"], eng["max_seq_len"], eng["kv_page_size"],
            eng["kv_pool_tokens"], eng["max_prefill_batch"],
            eng["pipeline_depth"], eng["prefill_chunk"],
            eng["decode_block"], eng["eos_token_id"]) \
        == (192, 4096, 64, 262144, 2, 10, 0, 1, None)
    assert eng["prefill_buckets"] == [128, 256, 512, 1024, 2048]
    assert cfg["deployment"]["max_ongoing_requests"] == 192
    runner = importlib.import_module("benchmarks.runners." + cfg["runner"])
    from benchmarks.runners import serve_http
    assert runner.serve_http is serve_http      # the one run(), not a copy
    assert set(runner.solar_family()) == set(serve_http.llama_family())
    assert runner.solar_family()["probe"].__name__ == "solar_preset"
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
    # 600 tokens, not a bucket size: the state's stop at the prompt's
    # true length inside the 1 024 bucket is part of `correct`
    assert traffic["check"] == {"prompt_len": 600, "new_tokens": 8}
    assert 600 not in eng["prefill_buckets"]
    assert cfg["check"]["busy_new_tokens"] == 32
    answers = schedule.length_multiset(traffic["output_len"], 32)
    assert traffic["rate_over_knee"] == pytest.approx(
        traffic["rate_rps"] / traffic["knee_rps"], abs=0.01) == 1.15
    assert traffic["offered_tok_s"] == pytest.approx(
        traffic["rate_rps"] * sum(answers) / 32, abs=0.1)
    assert "seed" in traffic["knee_note"]
    small = modelcfg.load(found["config_path"], True)
    assert small["hidden_size"] == 64 and small["n_routed_experts"] == 2
    tiny = _solar_section(True)
    assert (tiny["num_experts"], tiny["router_width"]) == (2, 8)
    assert tiny["layer_types"] == m["layer_types"]
    layer = {x["name"]: x for x in runmod.cell_metrics(manifest, SOLAR_CELL,
                                                       "per_layer")}
    for name in ("kda_kernel_roofline", "expert_matmul_roofline.solar",
                 "paged_kernel_roofline.solar",
                 "decode_step_roofline.solar"):
        assert layer[name]["workloads"] == [SOLAR_CELL]
        assert (layer[name]["moves"], layer[name]["unit"],
                layer[name]["better"]) == ("out_tok_s", "%", "higher")
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            assert json.load(f)["reader"] == "solar_roofline"
    # a kernel's share by its name (the expert layer's: its grouped
    # matmuls); the chunk scan's loops over chunks by the replica's own
    # reading of the trace, because they and the grouped matmul's binary
    # search are both a `while`, which the accepted `moe_dev_share`
    # counts by name: the cell is not on that list
    for name, reader in (("kda_kernel_dev_share", "trace_share"),
                         ("kda_scan_dev_share", "solar_roofline"),
                         ("moe_dev_share.solar", "trace_share")):
        assert layer[name]["workloads"] == [SOLAR_CELL]
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            assert json.load(f)["reader"] == reader
    assert "moe_dev_share" not in layer
    assert {"moe_expert_load_max_over_mean",
            "moe_pad_row_share", "moe_local_assignment_share",
            "decode_live_state_share", "decode_live_page_share",
            "decode_step_dev_ms", "prefill_dev_share",
            "attention_kernel_dev_share", "engine_runtime_calls_per_step",
            "consumer_loop_cpu_share", "device_idle_share",
            "compiles_in_window", "sat_backlog_end"} <= set(layer)
    # another model's cost arithmetic, and the sibling's kernel, are not
    # for it
    assert not {"paged_kernel_roofline", "decode_step_roofline",
                "expert_matmul_roofline", "decode_step_roofline.moe",
                "paged_kernel_roofline.hybrid", "gdn_kernel_roofline",
                "gdn_kernel_dev_share", "decode_step_roofline.hybrid",
                "paged_kernel_roofline.packed", "latent_kernel_dev_share",
                "decode_step_roofline.lfm2moe"} & set(layer)
    e2e = {x["name"] for x in runmod.cell_metrics(manifest, SOLAR_CELL,
                                                  "end_to_end")}
    assert e2e == {"out_tok_s", "setup_s"}


def test_a_file_the_solar_family_cannot_take_is_refused_at_once():
    from benchmarks.harness import modelcfg, replica_solar
    cfg = modelcfg.load(SOLAR_CONFIG, False)
    for wrong in (dict(use_rope=True), dict(tie_word_embeddings=True),
                  dict(use_gqa_gate=False), dict(kda_use_full_proj=True),
                  dict(first_k_dense_replace=1), dict(n_shared_experts=2)):
        with pytest.raises(SystemExit, match="this file disagrees"):
            replica_solar.model_section(dict(cfg, **wrong))
    with pytest.raises(SystemExit, match="router's width"):
        replica_solar.model_section(dict(cfg, n_routed_experts=32))
    with pytest.raises(SystemExit, match="lacks"):
        replica_solar.model_section(
            {k: v for k, v in cfg.items() if k != "kda_rank"})
    mistral = modelcfg.load(os.path.join(
        BENCH, "configs", "mistral-7b-v0.3-serve-l16.json"), False)
    with pytest.raises(SystemExit, match="lacks"):
        replica_solar.model_section(mistral)
    preset = replica_solar.solar_preset()()
    assert (preset.d_model, preset.n_layers, preset.n_experts) \
        == (4096, 48, 320)
