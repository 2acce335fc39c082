"""The Nemotron-3-Super reference by itself (its independence, the
state-space layer against the recurrence written out in numpy, the
latent expert share by hand, its agreement with the program's model
code, its controls), the costs and readers the cell adds, and that the
cell's runner, files and metrics resolve by name."""
import ast
import json
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
CELL = "nemotron120b_decode_sat"
CONFIG = os.path.join(BENCH, "configs",
                      "nemotron-3-super-120b-serve-ep8-l11.json")
PATTERN_88 = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
              "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
# the numbers of config.json of nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-
# BF16 as the catalog has them
PUBLISHED = {
    "chunk_size": 128, "conv_kernel": 4, "expand": 2, "head_dim": 128,
    "hidden_size": 4096, "intermediate_size": 2688,
    "layer_norm_epsilon": 1e-05, "mamba_head_dim": 64,
    "mamba_num_heads": 128, "max_position_embeddings": 262144,
    "moe_intermediate_size": 2688, "moe_latent_size": 1024,
    "moe_shared_expert_intermediate_size": 5376, "n_group": 1,
    "n_groups": 8, "n_routed_experts": 512, "n_shared_experts": 1,
    "norm_eps": 1e-05, "num_attention_heads": 32,
    "num_experts_per_tok": 22, "num_hidden_layers": 88,
    "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
    "rope_theta": 10000, "routed_scaling_factor": 5,
    "ssm_state_size": 128, "time_step_floor": 0.0001,
    "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
    "vocab_size": 131072}
CONTROLS = ("bf16_state", "no_decay", "dt_without_bias", "no_D",
            "norm_before_gate", "one_norm_group", "bc_head_modulo",
            "no_conv_bias", "relu_not_squared", "shared_in_latent",
            "no_scaling", "norm_over_held", "bias_in_weights",
            "rope_10000", "state_to_bucket_end", "int8_weights")


def _section(rehearse: bool = False) -> dict:
    from benchmarks.harness import modelcfg, replica_nemotron
    return replica_nemotron.model_section(modelcfg.load(CONFIG, rehearse))


def test_nemotron_reference_imports_nothing_of_the_program():
    path = os.path.join(BENCH, "harness", "reference_nemotron.py")
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


def test_the_file_holds_every_published_number_but_the_three_reduced():
    with open(CONFIG) as f:
        cfg = json.load(f)
    reduced = cfg["reduced"]
    assert set(reduced) == {"num_hidden_layers", "n_routed_experts",
                            "vocab_size"}
    for key, value in PUBLISHED.items():
        if key in reduced:
            assert reduced[key]["published"] == value
            assert reduced[key]["here"] == cfg[key] != value
        else:
            assert cfg[key] == value, key
    assert cfg["hybrid_override_pattern"] == PATTERN_88[:11] == "MEMEMEM*EME"
    assert cfg["mtp_hybrid_override_pattern"] == "*E"
    assert (cfg["mlp_hidden_act"], cfg["mamba_hidden_act"],
            cfg["use_conv_bias"]) == ("relu2", "silu", True)
    ep = cfg["expert_parallel"]
    assert (ep["ways"], ep["rank"], ep["router_width"]) == (8, 0, 512)
    for key in ("rotation", "gate_and_norm", "latent", "state_dtype",
                "scoring", "mtp"):
        assert key in cfg["assumed"], key
    assert "engine.py" not in json.dumps(cfg["engine"])
    m = _section()
    assert (m["num_experts"], m["router_width"], m["expert_first"]) \
        == (64, 512, 0)
    # the rehearsal is the same eleven layers at toy widths
    small = _section(rehearse=True)
    assert small["hybrid_override_pattern"] == m["hybrid_override_pattern"]
    assert (small["num_experts"], small["router_width"]) == (2, 8)


def _ssm_params(rng, d, h, p, grp, n, k):
    inner, width = h * p, h * p + 2 * grp * n
    return {"in_proj": {"kernel": rng.normal(size=(d, inner + width + h))
                        .astype(np.float32) * d ** -0.5},
            "conv_kernel": rng.uniform(-0.5, 0.5, (k, width))
            .astype(np.float32),
            "conv_bias": rng.uniform(-0.5, 0.5, width).astype(np.float32),
            "A_log": np.log(rng.uniform(1, 16, h)).astype(np.float32),
            "dt_bias": rng.normal(size=h).astype(np.float32) - 3.0,
            "D": rng.normal(size=h).astype(np.float32),
            "norm": (1 + 0.2 * rng.normal(size=inner)).astype(np.float32),
            "out_proj": {"kernel": rng.normal(size=(inner, d))
                         .astype(np.float32) * inner ** -0.5}}


def test_nemotron_mamba_layer_is_the_recurrence_written_out():
    """One `M` layer against numpy loops over tokens, heads and taps:
    the convolution WITH its bias, B and C a group's, a scalar decay a
    head, the skip, the gate before the norm a group."""
    import jax.numpy as jnp
    from benchmarks.harness import reference_nemotron as ref
    rng = np.random.default_rng(0)
    d, h, p, grp, n, k, s = 16, 4, 4, 2, 6, 4, 9
    m = {"mamba_num_heads": h, "mamba_head_dim": p, "ssm_state_size": n,
         "n_groups": grp, "conv_kernel": k, "use_conv_bias": True,
         "layer_norm_epsilon": 1e-5}
    prm = _ssm_params(rng, d, h, p, grp, n, k)
    u = rng.normal(size=(s, d)).astype(np.float32)
    got = np.asarray(ref.mamba_layer(
        jnp.asarray(u), {a: ({"kernel": jnp.asarray(b["kernel"])}
                             if isinstance(b, dict) else jnp.asarray(b))
                         for a, b in prm.items()}, m))
    inner = h * p
    proj = u @ prm["in_proj"]["kernel"]
    z, xbc, dt_raw = proj[:, :inner], proj[:, inner:-h], proj[:, -h:]
    conv = np.zeros_like(xbc)
    for t in range(s):
        for j in range(k):
            if t - j >= 0:
                conv[t] += prm["conv_kernel"][j] * xbc[t - j]
        conv[t] += prm["conv_bias"]
    conv = conv / (1 + np.exp(-conv))
    silu_z = z / (1 + np.exp(-z))
    state = np.zeros((h, p, n))
    y = np.zeros((s, inner))
    for t in range(s):
        xs = conv[t, :inner].reshape(h, p)
        bm = conv[t, inner:inner + grp * n].reshape(grp, n)
        cm = conv[t, inner + grp * n:].reshape(grp, n)
        for head in range(h):
            g = head // (h // grp)
            dt = np.log1p(np.exp(dt_raw[t, head] + prm["dt_bias"][head]))
            a = np.exp(-np.exp(prm["A_log"][head]) * dt)
            state[head] = a * state[head] + dt * np.outer(xs[head], bm[g])
            y[t, head * p:(head + 1) * p] = state[head] @ cm[g] \
                + prm["D"][head] * xs[head]
    gated = (y * silu_z).reshape(s, grp, inner // grp)
    normed = (gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + 1e-5)
              ).reshape(s, inner) * prm["norm"]
    want = normed @ prm["out_proj"]["kernel"]
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()


def test_nemotron_expert_share_by_hand():
    """Three of a router's eight experts chosen by s + b, weighted by s
    over the three's sum times 5; two held here (experts 2..3), computed
    in the latent without a gate and projected up; the shared expert at
    full width."""
    import jax.numpy as jnp
    from benchmarks.harness import reference_nemotron as ref
    rng = np.random.default_rng(1)
    s, d, lat, f, fs, e = 7, 12, 6, 10, 14, 8
    m = {"num_experts_per_tok": 3, "norm_topk_prob": True,
         "routed_scaling_factor": 5.0, "num_experts": 2, "expert_first": 2,
         "router_width": e}
    moe = {"router_kernel": rng.normal(size=(d, e)).astype(np.float32),
           "router_bias": 0.3 * rng.normal(size=e).astype(np.float32),
           "latent_down_proj": {"kernel": rng.normal(size=(d, lat))
                                .astype(np.float32)},
           "latent_up_proj": {"kernel": rng.normal(size=(lat, d))
                              .astype(np.float32)},
           "experts_up_kernel": rng.normal(size=(2, lat, f))
           .astype(np.float32),
           "experts_down_kernel": rng.normal(size=(2, f, lat))
           .astype(np.float32),
           "shared": {"up_proj": {"kernel": rng.normal(size=(d, fs))
                                  .astype(np.float32)},
                      "down_proj": {"kernel": rng.normal(size=(fs, d))
                                    .astype(np.float32)}}}
    h = rng.normal(size=(s, d)).astype(np.float32)
    as_jnp = lambda t: ({k: as_jnp(v) for k, v in t.items()}  # noqa: E731
                        if isinstance(t, dict) else jnp.asarray(t))
    got, info = ref.expert_layer(jnp.asarray(h), as_jnp(moe), m)
    score = 1 / (1 + np.exp(-(h @ moe["router_kernel"])))
    want = np.zeros((s, d))
    for t in range(s):
        top = np.argsort(-(score[t] + moe["router_bias"]))[:3]
        assert set(top) == set(np.flatnonzero(np.asarray(info["chosen"][t])))
        total = score[t, top].sum()
        latent = h[t] @ moe["latent_down_proj"]["kernel"]
        part = np.zeros(lat)
        for ex in top:
            if 2 <= ex < 4:
                hid = np.maximum(latent @ moe["experts_up_kernel"][ex - 2],
                                 0) ** 2
                part += 5.0 * score[t, ex] / total \
                    * (hid @ moe["experts_down_kernel"][ex - 2])
        sh = moe["shared"]
        want[t] = part @ moe["latent_up_proj"]["kernel"] + np.maximum(
            h[t] @ sh["up_proj"]["kernel"], 0) ** 2 @ sh["down_proj"]["kernel"]
    assert np.abs(np.asarray(got) - want).max() < 1e-4 * np.abs(want).max()
    assert not np.asarray(info["not_followed"]).any()


@pytest.fixture(scope="module")
def nemotron_toy():
    """The program's tiny model in float32 holding experts 2..5 of 8,
    its logits on 50 tokens, and the reference's."""
    import jax
    import jax.numpy as jnp
    from benchmarks.harness import reference_nemotron
    from ray_tpu.models import Hybrid, HybridConfig
    cfg = HybridConfig.nemotron_debug(dtype=jnp.float32,
                                      param_dtype=jnp.float32,
                                      expert_first=2, expert_count=4)
    model = Hybrid(cfg)
    params = model.init_params(jax.random.PRNGKey(1))
    m = {"hidden_size": cfg.d_model, "num_hidden_layers": 11,
         "hybrid_override_pattern": "MEMEMEM*EME",
         "num_attention_heads": cfg.n_heads,
         "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
         "mamba_num_heads": cfg.ssm_n_heads,
         "mamba_head_dim": cfg.ssm_head_dim,
         "ssm_state_size": cfg.ssm_state, "n_groups": cfg.ssm_groups,
         "conv_kernel": cfg.ssm_conv_kernel, "use_conv_bias": True,
         "moe_intermediate_size": cfg.d_expert,
         "moe_latent_size": cfg.moe_latent_dim,
         "moe_shared_expert_intermediate_size": cfg.d_shared,
         "num_experts": cfg.experts_held, "router_width": cfg.n_experts,
         "expert_first": cfg.expert_first,
         "num_experts_per_tok": cfg.experts_per_token,
         "norm_topk_prob": True, "routed_scaling_factor": 5.0,
         "rope_theta": 10000, "layer_norm_epsilon": cfg.norm_eps,
         "vocab_size": cfg.vocab_size}
    tokens = np.random.default_rng(2).integers(1, 256, 50)
    with jax.default_matmul_precision("highest"):
        got, _ = model.apply({"params": params}, jnp.asarray(tokens)[None])
    ref = reference_nemotron.forward_logits(params, jnp.asarray(tokens), m)
    return {"params": params, "m": m, "tokens": tokens,
            "got": np.asarray(got[0]), "ref": np.asarray(ref)}


def test_nemotron_reference_against_the_model_code(nemotron_toy):
    toy = nemotron_toy
    assert np.abs(toy["got"] - toy["ref"]).max() < 2e-4 * toy["ref"].std()


def test_the_reference_walks_the_published_layers_not_the_blocks(
        nemotron_toy):
    from benchmarks.harness import reference_nemotron
    toy = nemotron_toy
    layers = reference_nemotron.published_layers(toy["params"], toy["m"])
    assert "".join(kind for kind, _w, _p in layers) == "MEMEMEM*EME"
    assert [sorted(p)[0] for _k, _w, p in layers][:2] == ["A_log",
                                                          "experts_down_kernel"]
    with pytest.raises(ValueError):
        reference_nemotron.published_layers(
            toy["params"], dict(toy["m"], hybrid_override_pattern="M-"))


@pytest.mark.parametrize("name", CONTROLS)
def test_nemotron_controls_compute_another_model(nemotron_toy, name):
    import jax.numpy as jnp
    from benchmarks.harness import reference_nemotron
    toy = nemotron_toy
    assert reference_nemotron.CONTROLS == CONTROLS
    wrong = np.asarray(reference_nemotron.forward_logits(
        toy["params"], jnp.asarray(toy["tokens"]),
        dict(toy["m"], controls=frozenset([name]), bucket=64,
             prompt_len=30)))
    assert wrong.shape == toy["ref"].shape
    err = np.abs(wrong - toy["ref"]).max() / toy["ref"].std()
    # the two of precision move a float32 toy's logits least
    assert err > (1e-4 if name in ("bf16_state", "int8_weights") else 0.05), \
        (name, err)
    if name == "state_to_bucket_end":
        # the prompt's own positions are the plain run's
        assert np.abs(wrong[:30] - toy["ref"][:30]).max() \
            < 1e-4 * toy["ref"].std()


def test_nemotron_costs_count_the_published_model_and_the_cut():
    """ISSUE 56's arithmetic: a Mamba-2 layer 109.6 M, the attention
    layer 35.7 M, an expert 5.505 M, an `E` layer beside its experts
    54.5 M, 120.7 B in all and 12.8 B a token; the cut 2 752 M; 20.3 MiB
    of state a slot, 1 024 B of K and V a token as published."""
    from benchmarks.harness import costs_nemotron as c
    m = _section()
    assert (c.layers(m, "M"), c.layers(m, "E"), c.layers(m, "*")) \
        == (5, 5, 1)
    assert c.expert_params(m) == 2 * 1024 * 2688 == 5_505_024
    assert c.state_elements(m) * 4 == 4 * 2 ** 20
    assert c.conv_width(m) == 10240 and c.inner_width(m) == 8192
    assert round(c.mamba_params(m) / 1e6, 1) == 109.6
    assert round(c.attention_params(m) / 1e6, 1) == 35.7
    assert round(c.expert_layer_dense_params(m) / 1e6, 1) == 54.5
    total = c.total_params(m)
    assert 2.745e9 < total < 2.760e9
    assert c.state_bytes_per_slot(m) == 5 * (4 * 2 ** 20 + 3 * 10240 * 2)
    assert round(c.state_bytes_per_slot(m) / 2 ** 20, 1) == 20.3
    assert c.kv_bytes_per_token(m) == 1024
    whole = dict(m, hybrid_override_pattern=PATTERN_88, num_experts=512,
                 vocab_size=131072)
    assert 1.205e11 < c.total_params(whole) < 1.21e11
    # every matmul weight a token reads and the embedding's table
    active = c.always_read_params(whole) + 40 * 22 * c.expert_params(whole) \
        + 4096 * 131072
    assert 1.27e10 < active < 1.29e10
    # a decode step of 179 live rows at ~900 tokens: the state kernel's
    # bytes over half, the experts' a quarter
    step = c.decode_step(m, [900] * 179, touched=5 * 64,
                         assignments=179 * 22 * 5 / 8)
    scan = c.ssm_step(m, 179 * 5)["bytes"]
    experts = c.expert_matmuls(m, 179 * 22 * 5 / 8, 5 * 64)["bytes"]
    assert scan == 179 * 5 * 2 * 4 * 2 ** 20
    assert 0.5 < scan / step["bytes"] < 0.6
    assert 0.22 < experts / step["bytes"] < 0.3
    assert 1.25e10 < step["bytes"] < 1.4e10
    from benchmarks.harness.peaks import PEAKS
    least = c.least_seconds(step, PEAKS["TPU v5e"])
    assert least["bound"] == "memory" and 0.015 < least["seconds"] < 0.0175


def _window(state=True):
    from benchmarks.harness import modelcfg
    from benchmarks.harness.peaks import PEAKS
    cfg = modelcfg.load(CONFIG, False)

    def reading(k):
        out = {"decode_steps": k, "prefill_calls": k // 40,
               "decode_pages_live": k * 180 * 14,
               "moe_assignments": k * 180 * 22 * 5 // 8,
               "moe_experts_touched": k * 64 * 5}
        if state:
            out["decode_state_rows_live"] = k * 180 * 5
        return out
    return {"stats0": reading(1000), "stats1": reading(2000),
            "trace": {"busy_s": 4.0, "ssm_scan_s": 0.2,
                      "ops": {"ssm_decode_step": 1.6, "gmm": 0.8,
                              "paged_decode_attention": 0.1},
                      "modules": {"jit__decode_paged_step":
                                  {"count": 160, "seconds": 3.6}}},
            "peaks": PEAKS["TPU v5e"], "config": cfg,
            "model": _section(), "trace_contexts": [900] * 180}


def test_nemotron_readers_read_and_read_none_without_the_counters():
    import sys
    readers = os.path.join(BENCH, "readers")
    if readers not in sys.path:
        sys.path.insert(0, readers)
    import nemotron_roofline
    run = _window()
    args = dict(module_re="decode_paged")
    ssm = nemotron_roofline.read(run, "ssm_kernel",
                                 name_re="^ssm_decode_step", **args)
    assert ssm == pytest.approx(
        100 * 180 * 5 * 8 * 2 ** 20 / 819e9 / 10e-3, rel=1e-3)
    experts = nemotron_roofline.read(run, "experts", name_re="^gmm", **args)
    paged = nemotron_roofline.read(run, "paged_kernel",
                                   name_re="^paged_decode_attention", **args)
    step = nemotron_roofline.read(run, "step", **args)
    for value in (ssm, experts, paged, step):
        assert 0.0 < value < 100.0
    assert set(run["notes"].values()) == {"memory"}
    assert nemotron_roofline.read(run, "ssm_scan") == pytest.approx(5.0)
    with pytest.raises(ValueError):
        nemotron_roofline.read(run, "nothing", **args)
    # a program without the state counter, a trace without the kernel or
    # the scope, another family's section, an empty run: None
    assert nemotron_roofline.read(_window(state=False), "ssm_kernel",
                                  name_re="^ssm_decode_step", **args) is None
    no_kernel = dict(run, trace={"busy_s": 4.0, "ops": {"fusion": 1.0},
                                 "modules": run["trace"]["modules"]})
    for what, name_re in (("ssm_kernel", "^ssm_decode_step"),
                          ("experts", "^gmm"),
                          ("paged_kernel", "^paged_decode_attention")):
        assert nemotron_roofline.read(no_kernel, what, name_re=name_re,
                                      **args) is None
    assert nemotron_roofline.read(no_kernel, "ssm_scan") is None
    other = dict(run, model={"hidden_size": 4096, "kda_rank": 128})
    assert nemotron_roofline.read(other, "step", **args) is None
    assert nemotron_roofline.read({}, "step", **args) is None


def test_the_nemotron_cell_resolves_to_its_own_runner_and_files():
    from benchmarks import run as runmod
    from benchmarks.harness import modelcfg, replica_nemotron, schedule
    from benchmarks.runners import serve_http_nemotron
    whole = runmod.load_manifest()
    found = runmod.resolve(whole, CELL)
    assert found["config_path"] == CONFIG
    assert found["traffic_path"].endswith("decode_sat_nemotron.json")
    cfg = modelcfg.load(CONFIG, False)
    assert cfg["runner"] == "serve_http_nemotron"
    fam = serve_http_nemotron.nemotron_family()
    assert fam["server_cls"] is replica_nemotron.NemotronBenchServer
    assert fam["probe"] is replica_nemotron.nemotron_preset
    assert fam["probe"]().__name__ == "nemotron_3_super_120b"
    with open(found["traffic_path"]) as f:
        traffic = json.load(f)
    with open(os.path.join(BENCH, "traffic", "decode_sat_sarvam.json")) as f:
        sarvam = json.load(f)
    # the accepted cells' lengths, so that cells differ in the model alone
    for key in ("prompt_len", "output_len", "gaps", "block", "ramp_s",
                "drain_s"):
        assert traffic[key] == sarvam[key], key
    assert traffic["rate_over_knee"] == 1.15
    assert traffic["rate_rps"] == pytest.approx(
        1.15 * traffic["knee_rps"], rel=0.01)
    reqs = schedule.build(traffic, 1, 51.0)
    # every id from the 16 384-row slice, prompts and answers inside the
    # engine's reach
    assert all(1 <= t < cfg["vocab_size"] for r in reqs[:50]
               for t in r.prompt(1, cfg["vocab_size"])[:20])
    assert max(r.prompt_len + r.max_tokens for r in reqs) \
        <= cfg["engine"]["max_seq_len"]
    assert cfg["engine"]["max_slots"] == 192 \
        == cfg["deployment"]["max_ongoing_requests"]
    mem = cfg["memory_analysis"]
    assert mem["kv_bytes_per_token"] == 4096
    assert mem["kv_bytes_per_token_as_published"] == 1024
    assert mem["state_bytes_per_slot"] == 5 * (4 * 2 ** 20 + 61440)
    for key in ("logit_tol_rel", "logit_mean_tol_rel",
                "logit_decode_mean_tol_rel", "argmax_tol_rel",
                "tie_margin_rel", "recurrence_tol_rel", "why"):
        assert key in cfg["check"], key


def test_a_file_the_nemotron_family_cannot_take_is_refused_at_once():
    from benchmarks.harness import modelcfg, replica_nemotron
    cfg = modelcfg.load(CONFIG, False)
    for change in ({"mlp_hidden_act": "silu"}, {"use_conv_bias": False},
                   {"hybrid_override_pattern": "MEMEMEM*EM-"},
                   {"num_hidden_layers": 12}, {"n_group": 8},
                   {"tie_word_embeddings": True}):
        with pytest.raises(SystemExit, match="this file disagrees"):
            replica_nemotron.model_section(dict(cfg, **change))
    with pytest.raises(SystemExit, match="router's width"):
        replica_nemotron.model_section(dict(cfg, n_routed_experts=32))
    with pytest.raises(SystemExit, match="lacks"):
        replica_nemotron.model_section(
            {k: v for k, v in cfg.items() if k != "moe_latent_size"})
