"""The fourth hybrid family (models/hybrid.py, Nemotron-3-Super-120B-A12B):
the state-space recurrence in its three forms and its arm of the step
kernel, the tiny decoder against the benchmark's plain reference and
through LLMEngine's slot state and pages, the latent expert share summed
over its eight ranks, the six-block pairing against eleven one-sub-layer
layers, and the sibling families' programs unmoved (tests/test_solar.py
pins all twelve, the three expert families outside this file among
them; the case here holds the new fields' defaults)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import reference_nemotron, replica_nemotron
from ray_tpu.models import Hybrid, HybridConfig, get_model
from ray_tpu.models.latent_moe import ShareMoE
from ray_tpu.ops import gated_deltanet as gdn
from ray_tpu.ops import moe, ssm
from ray_tpu.ops.attention import PagedKV, SlotState, kv_cache_spec
from ray_tpu.ops.pallas.gdn_decode import (gdn_decode_step, heads_per_group,
                                           kda_decode_step, ssm_decode_step)
from ray_tpu.serve.llm.engine import LLMEngine, LLMEngineConfig

BUCKETS = (16, 32, 64)
CONTROLS = reference_nemotron.CONTROLS
PATTERN = "MEMEMEM*EME"
# float32 model against the float32 reference: rounding of two orders of
# summation only
TIGHT = {"logit_tol_rel": 1e-3, "logit_mean_tol_rel": 1e-3,
         "logit_decode_mean_tol_rel": 1e-3, "argmax_tol_rel": 1e-3,
         "tie_margin_rel": 1e-4, "recurrence_tol_rel": 1e-5,
         "busy_new_tokens": 7}
# the largest rate the initialisers draw: A = 16 and a step of 0.1
# (models/hybrid.py), before the projection's own term
TOP_RATE = 1.6


def _draw(seed, b, s, h, p, grp, n, rate, constant=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.nn.silu(jax.random.normal(ks[0], (b, s, grp, n)))
    k = jax.nn.silu(jax.random.normal(ks[1], (b, s, grp, n)))
    v = jax.random.normal(ks[2], (b, s, h, p))
    beta = 0.1 * (jnp.ones((b, s, h)) if constant
                  else jax.random.uniform(ks[3], (b, s, h)))
    g = -rate / 0.1 * beta
    state = jax.random.normal(ks[5], (b, n, h * p))
    return q, k, v, g, beta, state


# ---- (a) the operator's three forms --------------------------------------

@pytest.mark.parametrize("chunk", [16, 128])
@pytest.mark.parametrize("rate,constant", [(0.1, False), (TOP_RATE, True),
                                           (20.0, False)])
def test_chunk_scan_is_the_recurrence(chunk, rate, constant):
    q, k, v, g, beta, state = _draw(0, 2, 150, 8, 16, 2, 32, rate, constant)
    want_o, want_s = ssm.recurrent(q, k, v, g, beta, state)
    got_o, got_s = ssm.chunk_scan(q, k, v, g, beta, state, chunk=chunk)
    assert np.isfinite(np.asarray(got_o)).all()
    # float32 rounding of two orders of summation, over up to 128 terms
    scale = float(jnp.abs(want_o).max())
    assert float(jnp.abs(got_o - want_o).max()) < 1e-5 * scale
    assert float(jnp.abs(got_s - want_s).max()) < 1e-5 * float(
        jnp.abs(want_s).max())


@pytest.mark.parametrize("chunk", [16, 128])
def test_rows_ending_inside_a_chunk_stop_their_state_there(chunk):
    """A row frozen behind its true length (dt = 0: `freeze`) leaves the
    scan with the state at that length, whatever follows in the bucket."""
    q, k, v, g, beta, state = _draw(1, 3, 150, 8, 16, 2, 32, TOP_RATE)
    n_new = jnp.asarray([150, 37, 0])
    gf, bf = gdn.freeze(g, beta, jnp.arange(150)[None, :] < n_new[:, None])
    _, got = ssm.chunk_scan(q, k, v, gf, bf, state, chunk=chunk)
    for row, n in enumerate(n_new.tolist()):
        _, want = ssm.recurrent(*(x[row:row + 1, :n] for x in
                                  (q, k, v, g, beta)), state[row:row + 1])
        assert float(jnp.abs(got[row] - want[0]).max()) \
            < 1e-5 * float(jnp.abs(want).max())
    assert bool((got[2] == state[2]).all())


def test_step_is_one_token_of_the_recurrence_and_the_delta_rule_less_its_correction():
    q, k, v, g, beta, state = _draw(2, 2, 5, 8, 16, 2, 32, 1.0)
    want_o, want_s = ssm.recurrent(q, k, v, g, beta, state)
    st, outs = state, []
    for t in range(5):
        o, st = ssm.step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], st)
        outs.append(o)
    assert float(jnp.abs(jnp.stack(outs, 1) - want_o).max()) < 1e-5
    assert float(jnp.abs(st - want_s).max()) < 1e-5
    # the delta rule's step on a state its keys are orthogonal to (zero)
    # writes the same rank one and reads the same: k = B, q = C a head
    kh, qh = (jnp.repeat(x[:, 0], 4, axis=1) for x in (k, q))
    zero = jnp.zeros_like(state)
    o1, s1 = gdn.step(qh, kh, v[:, 0], g[:, 0], beta[:, 0], zero)
    o2, s2 = ssm.step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], zero)
    assert float(jnp.abs(o1 - o2).max()) < 1e-5
    assert float(jnp.abs(s1 - s2).max()) < 1e-6


def test_the_convolution_takes_a_bias():
    u = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 128), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (4, 128))
    bias = jax.random.normal(jax.random.PRNGKey(2), (128,))
    plain, tail = gdn.causal_conv(u, w, None, activation=None)
    with_bias, tail_b = gdn.causal_conv(u, w, None, activation=None,
                                        bias=bias)
    assert float(jnp.abs(with_bias - (plain + bias)).max()) < 1e-6
    assert bool((tail == tail_b).all())
    # one token against the tail, as a decode step runs it
    one, _ = gdn.causal_conv(u[:, 8:], w, gdn.causal_conv(
        u[:, :8], w, None, bias=bias)[1], bias=bias)
    whole, _ = gdn.causal_conv(u, w, None, bias=bias)
    assert float(jnp.abs(one[:, 0] - whole[:, 8]).max()) < 1e-6


def test_the_gated_norm_is_a_norm_a_group_of_the_gated_value():
    y = jax.random.normal(jax.random.PRNGKey(0), (3, 64))
    z = jax.random.normal(jax.random.PRNGKey(1), (3, 64))
    w = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(2), (64,))
    got = ssm.gated_group_norm(y, z, w, 4, 1e-5)
    x = (y * jax.nn.silu(z)).reshape(3, 4, 16)
    want = (x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5)
            ).reshape(3, 64) * w
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(jnp.abs(got - ssm.gated_group_norm(y, z, w, 1, 1e-5)
                         ).max()) > 1e-2


# ---- (b) the step kernel's arm, interpreted ------------------------------

@pytest.mark.parametrize("h,p,grp,n", [(8, 64, 2, 16), (4, 32, 2, 8),
                                       (6, 128, 3, 16)])
def test_kernel_arm_is_the_step_and_writes_idle_rows_through(h, p, grp, n):
    q, k, v, g, beta, state = _draw(5, 5, 1, h, p, grp, n, 1.0)
    q, k, v, g, beta = (x[:, 0] for x in (q, k, v, g, beta))
    live = jnp.asarray([True, False, True, True, False])
    g = jnp.where(live[:, None], g, 0.0)
    beta = jnp.where(live[:, None], beta, 0.0)
    want_o, want_s = ssm.step(q, k, v, g, beta, state)
    got_o, got_s = ssm_decode_step(q, k, v, g, beta, state + 0.0,
                                   interpret=True)
    assert float(jnp.abs(got_o - want_o)[live].max()) < 1e-5
    assert float(jnp.abs(got_s - want_s).max()) < 1e-6
    # an idle row's state comes back bit for bit, whatever its k
    assert bool((got_s[~live] == state[~live]).all())


def test_the_published_shape_walks_two_heads_a_tile():
    assert heads_per_group(128, 64) == 2
    assert heads_per_group(64, 128) == 1 and heads_per_group(30, 192) == 2


@pytest.mark.parametrize("kernel,channel", [(gdn_decode_step, False),
                                            (kda_decode_step, True)])
def test_the_delta_arms_give_the_bits_of_the_plain_kernel(kernel, channel):
    """The arm is static: the delta rule's two names trace the body they
    had. Held to a copy of that body (the kernel as PR 55 left it) run
    through the same `pallas_call`, bit for bit."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    b, h, dk, dv = 3, 4, 16, 64
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    q = gdn.l2norm(jax.random.normal(ks[0], (b, h, dk)))
    k = gdn.l2norm(jax.random.normal(ks[1], (b, h, dk)))
    v = jax.random.normal(ks[2], (b, h, dv))
    g = -jax.random.uniform(ks[3], (b, h, dk) if channel else (b, h))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, h)))
    state = jax.random.normal(ks[5], (b, dk, h * dv))
    got_o, got_s = kernel(q, k, v, g, beta, state + 0.0, interpret=True)

    group = heads_per_group(h, dv)

    def before(qt_ref, kt_ref, at_ref, rows_ref, s_ref, o_ref, s_out_ref):
        width = group * dv
        lane = jax.lax.broadcasted_iota(jnp.int32, (dk, width), 1)
        qt, kt, at = qt_ref[0], kt_ref[0], at_ref[0]

        def expand(cols, h0):
            out = jnp.broadcast_to(cols[:, h0:h0 + 1], (dk, width))
            for j in range(1, group):
                out = jnp.where(lane >= j * dv, jnp.broadcast_to(
                    cols[:, h0 + j:h0 + j + 1], (dk, width)), out)
            return out
        for gi in range(h // group):
            cols = slice(gi * width, (gi + 1) * width)
            vv, bb = rows_ref[0, 0:1, cols], rows_ref[0, 1:2, cols]
            kx = expand(kt, gi * group)
            sd = s_ref[0, :, cols] * expand(at, gi * group)
            u = bb * (vv - jnp.sum(sd * kx, axis=0, keepdims=True))
            new = sd + kx * u
            s_out_ref[0, :, cols] = new
            o_ref[0, :, cols] = jnp.sum(new * expand(qt, gi * group),
                                        axis=0, keepdims=True)

    gg = g if channel else jnp.broadcast_to(g[..., None], q.shape)
    rows = jnp.stack([v.reshape(b, h * dv),
                      jnp.repeat(beta, dv, axis=-1)], axis=1)
    row3 = lambda i: (i, 0, 0)                                # noqa: E731
    cols = pl.BlockSpec((1, dk, h), row3)
    want_o, want_s = pl.pallas_call(
        before, grid=(b,),
        in_specs=[cols, cols, cols, pl.BlockSpec((1, 2, h * dv), row3),
                  pl.BlockSpec((1, dk, h * dv), row3)],
        out_specs=[pl.BlockSpec((1, 1, h * dv), row3),
                   pl.BlockSpec((1, dk, h * dv), row3)],
        out_shape=[jax.ShapeDtypeStruct((b, 1, h * dv), jnp.float32),
                   jax.ShapeDtypeStruct((b, dk, h * dv), jnp.float32)],
        interpret=pltpu.InterpretParams(),
    )(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
      jnp.swapaxes(jnp.exp(gg), 1, 2), rows, state)
    assert bool((got_o.reshape(want_o.shape) == want_o).all())
    assert bool((got_s == want_s).all())


# ---- the tiny decoder ----------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    model = get_model("nemotron-debug", param_dtype=jnp.float32,
                      dtype=jnp.float32, expert_first=2, expert_count=4)
    params = model.init_params(jax.random.PRNGKey(0))
    # norm weights, D and the biases off their initial values
    params = jax.tree_util.tree_map(
        lambda a: a * (1 + 0.3 * jax.random.normal(
            jax.random.PRNGKey(a.size), a.shape, a.dtype))
        if a.ndim == 1 and a.shape[0] >= 8 else a, params)
    return model, params


def _engine(tiny, **kw):
    model, params = tiny
    return LLMEngine(model, params, LLMEngineConfig(**{**dict(
        max_slots=3, max_seq_len=128, prefill_buckets=BUCKETS,
        kv_page_size=8, pipeline_depth=3, max_prefill_batch=2), **kw}))


def _section(cfg, pattern=PATTERN):
    """The reference's model section of a program config."""
    return dict(
        hidden_size=cfg.d_model, num_hidden_layers=len(pattern),
        hybrid_override_pattern=pattern,
        num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, mamba_num_heads=cfg.ssm_n_heads,
        mamba_head_dim=cfg.ssm_head_dim, ssm_state_size=cfg.ssm_state,
        n_groups=cfg.ssm_groups, conv_kernel=cfg.ssm_conv_kernel,
        chunk_size=cfg.ssm_chunk, use_conv_bias=True,
        moe_intermediate_size=cfg.d_expert,
        moe_latent_size=cfg.moe_latent_dim,
        moe_shared_expert_intermediate_size=cfg.d_shared,
        n_routed_experts=cfg.experts_held, num_experts=cfg.experts_held,
        router_width=cfg.n_experts, expert_first=cfg.expert_first,
        n_shared_experts=cfg.n_shared_experts,
        num_experts_per_tok=cfg.experts_per_token,
        norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling, rope_theta=10000,
        layer_norm_epsilon=cfg.norm_eps, vocab_size=cfg.vocab_size)


def test_the_preset_is_the_published_model(tiny):
    cfg = tiny[0].cfg
    assert cfg.layer_types == ("mamba2",) * 4 + ("full_attention", "mamba2")
    assert cfg.ff_types == ("experts",) * 3 + ("none", "experts", "experts")
    assert cfg.pre_norm and not cfg.tie_embeddings and not cfg.out_gate
    assert cfg.rope_theta is None and cfg.qk_norm is False
    assert not cfg.expert_gated and cfg.routed_scaling == 5.0
    assert tiny[0].step_stats == moe.MOE_STATS
    big = get_model("nemotron-3-super-120b").cfg
    assert (big.n_layers, big.d_model, big.vocab_size) == (48, 4096, 131072)
    assert (big.n_heads, big.n_kv_heads, big.head_dim) == (32, 2, 128)
    assert (big.layer_types.count("mamba2"),
            big.layer_types.count("full_attention"),
            big.ff_types.count("experts"), big.ff_types.count("none")) \
        == (40, 8, 40, 8)
    assert (big.ssm_n_heads, big.ssm_head_dim, big.ssm_state, big.ssm_groups,
            big.ssm_conv_kernel, big.ssm_chunk, big.ssm_conv_width) \
        == (128, 64, 128, 8, 4, 128, 10240)
    assert (big.d_expert, big.d_shared, big.moe_latent_dim, big.n_experts,
            big.experts_held, big.experts_per_token, big.n_shared_experts) \
        == (2688, 5376, 1024, 512, 512, 22, 1)
    # the published pattern undone: every E rides with the mixer before
    blocks = HybridConfig.nemotron_blocks(PATTERN)
    assert blocks == (cfg.layer_types, cfg.ff_types)
    for bad in ("EM", "MEE", "M-E"):
        with pytest.raises(ValueError, match="no mixer and follows none"):
            HybridConfig.nemotron_blocks(bad)
    # what the new fields' defaults keep: the three sibling families
    for name in ("hybrid-debug", "lfm2-moe-debug", "solar-debug"):
        sib = get_model(name).cfg
        assert (sib.ff_types, sib.moe_latent_dim, sib.expert_gated,
                sib.d_shared) == (None, None, True, None)
        assert [sib.ff_kind(i) for i in range(sib.n_layers)] \
            == ["dense" if sib.dense_ff(i) else "experts"
                for i in range(sib.n_layers)]
    with pytest.raises(ValueError, match="ff_types must name"):
        HybridConfig.debug(ff_types=("dense", "sparse", "none", "none"))


def test_the_presets_prefill_through_the_flash_kernel_on_a_tpu(monkeypatch):
    assert HybridConfig.nemotron_3_super_120b().attn_impl == "auto"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert HybridConfig.nemotron_3_super_120b().attn_impl == "pallas"
    assert HybridConfig.nemotron_debug().attn_impl == "pallas"
    assert HybridConfig.nemotron_debug(attn_impl="xla").attn_impl == "xla"


def test_the_cache_is_declared_a_block(tiny):
    spec = kv_cache_spec(tiny[0])
    assert [c.entry for c in spec] == [SlotState] * 4 + [PagedKV, SlotState]
    assert [c.by_slot for c in spec] == [True] * 4 + [False, True]
    assert spec[0].shapes == ((16, 128), (3 * 192,))
    assert spec[0].dtypes[0] == jnp.float32
    # the cut the benchmark serves: 4 MiB of state (Solar's very shape)
    # and one row of three inputs of 10 240 a slot a Mamba-2 layer; a
    # pool laid out for 8 KV heads where the layer has 2
    cut = kv_cache_spec(get_model("nemotron-3-super-120b", pattern=PATTERN))
    state, tail = cut[0].shapes
    assert state == (128, 8192) and tail == (3 * 10240,)
    assert 4 * state[0] * state[1] == 4 * 2 ** 20
    assert cut[4].shapes == ((8, 128), (8, 128))
    assert tiny[0].chunk_scan_layers() == (5, 16)


def test_full_forward_agrees_with_the_reference(tiny):
    model, params = tiny
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        1, 256, (1, 45)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got, _ = model.apply({"params": params}, tokens)
    want, records = reference_nemotron.forward(params, tokens[0],
                                               _section(model.cfg))
    assert len(records) == 5                    # the five E layers route
    assert float(jnp.abs(got[0] - want).max()) < 2e-4 * float(want.std())


def test_the_block_lowers_its_scopes(tiny):
    model, params = tiny
    tokens = jnp.zeros((1, 8), jnp.int32)
    text = jax.jit(lambda t: model.apply({"params": params}, t)[0]).lower(
        tokens).as_text(debug_info=True)
    for scope in ("ssm.proj", "ssm.conv", "ssm.scan", "ssm.norm",
                  "moe.route", "moe.latent_in", "moe.latent_out",
                  "moe.shared"):
        assert scope in text, scope


# ---- (e) six blocks are eleven one-sub-layer layers ----------------------

def test_six_blocks_are_eleven_layers_of_one_sub_layer(tiny):
    """The pairing computes the published stack: the same weights run as
    eleven layers of ONE sub-layer behind one norm and one residual add
    (each mixer a block without a feed-forward; each expert layer by
    itself on the stream) give the six-block model's hidden states."""
    from ray_tpu.models.hybrid import HybridBlock
    from ray_tpu.ops import rms_norm
    model, params = tiny
    cfg = model.cfg
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        1, 256, (2, 23)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        _, _, want = model.apply({"params": params}, tokens,
                                 return_hidden=True)
        x = params["token_embed"]["embedding"][tokens]
        block, layers = -1, 0
        for c in PATTERN:
            if c in "M*":
                block += 1
                p = params[f"layer_{block}"]
                mixer_only = {k: v for k, v in p.items()
                              if k not in ("mlp_norm", "moe")}
                x, _ = HybridBlock(cfg, cfg.layer_types[block], "none").apply(
                    {"params": mixer_only}, x)
            else:
                p = params[f"layer_{block}"]
                x = x + ShareMoE(cfg).apply(
                    {"params": p["moe"]},
                    rms_norm(x, p["mlp_norm"], cfg.norm_eps),
                    mutable=["step_stats", "routing"])[0]
            layers += 1
        got = rms_norm(x, params["final_norm"], cfg.norm_eps)
    assert (block + 1, layers) == (6, 11)
    assert float(jnp.abs(got - want).max()) < 1e-5 * float(
        jnp.abs(want).max())


# ---- (c) through the engine, against the reference -----------------------

@pytest.mark.parametrize("prompt_len", [3, 21, 32])
def test_engine_logits_against_the_reference(tiny, prompt_len):
    """Prefill (the chunkwise scan, stopped at the prompt's true length
    inside its bucket) into the slot state and the pages, then decode
    (the one-token form) as the benchmark's check drives the engine: its
    own step programs handing out logits, the experts they chose and the
    first Mamba-2 layer's recurrence, every slot live, the request in a
    slot another has left. Every control of the reference that float32
    can tell fails."""
    model, params = tiny
    eng = _engine(tiny)
    try:
        prompt = np.random.default_rng(prompt_len).integers(1, 256,
                                                            prompt_len)
        answer = eng.generate_sync(prompt, max_new_tokens=6)
        with jax.default_matmul_precision("highest"):
            out = replica_nemotron.serve_check(eng, {
                "model": _section(model.cfg), "prompt": prompt.tolist(),
                "generated": answer, "check": TIGHT,
                # the controls once: each is a forward of its own
                "controls": list(CONTROLS) if prompt_len == 21 else []})
        assert not {"_dispatch_prefill", "_dispatch_decode",
                    "_apply_counted"} & set(vars(eng))
        assert eng.model is model
        assert eng.generate_sync(prompt, max_new_tokens=6) == answer
        stats = eng.get_stats()
    finally:
        eng.shutdown()
    assert out["ok"], out
    assert out["positions"] == prompt_len + 6 and out["new_tokens"] == 7
    assert out["not_followed"] == 0 and out["recurrence_err_rel"] < 1e-5
    assert (out["slots"], out["requests_beside"]) == (3, 6)
    assert out["tokens_as_idle"] and out["tokens_with_logits_as_timed"]
    passed = [n for n, c in out.get("controls", {}).items() if c["ok"]]
    # float32 cannot tell 8-bit weights' error from a limit of 1e-3? it
    # can: every control fails, the bfloat16 state by the recurrence
    assert passed == [] and (prompt_len != 21
                             or len(out["controls"]) == len(CONTROLS))
    # every real row is routed in each of the 5 expert layers to 3
    # experts of the router's 8; the share holds 4 of them
    assert stats["moe_routed_assignments"] == 3 * stats["moe_rows"] > 0
    assert 0 < stats["moe_assignments"] < stats["moe_routed_assignments"]
    # five Mamba-2 layers keep a state row a slot
    assert stats["decode_state_rows_window"] % 5 == 0
    assert 0 < stats["decode_state_rows_live"] \
        <= stats["decode_state_rows_window"]


def test_the_kernel_route_decodes_as_the_plain_step(tiny, monkeypatch):
    """The engine's decode program with `ssm_decode_step` interpreted in
    place of the plain step answers the same tokens."""
    prompt = np.random.default_rng(7).integers(1, 256, 19)
    eng = _engine(tiny)
    try:
        plain = eng.generate_sync(prompt, max_new_tokens=5)
    finally:
        eng.shutdown()
    monkeypatch.setenv("RAY_TPU_PAGED_ATTN_IMPL", "pallas")
    eng = _engine(tiny)
    try:
        kernel = eng.generate_sync(prompt, max_new_tokens=5)
    finally:
        eng.shutdown()
    assert kernel == plain


def test_seven_requests_through_three_slots_answer_as_one_at_a_time(tiny):
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 256, n) for n in (2, 19, 33, 1, 40, 16, 9)]
    eng = _engine(tiny)
    try:
        alone = [eng.generate_sync(p, max_new_tokens=5) for p in prompts]
        rids = [eng.submit(p, max_new_tokens=5) for p in prompts]
        got = [list(eng.stream(r)) for r in rids]
    finally:
        eng.shutdown()
    assert got == alone


def test_an_engine_with_slot_state_refuses_speculation_by_name(tiny):
    model, params = tiny
    with pytest.raises(ValueError, match="cannot be rolled back"):
        LLMEngine(model, params, LLMEngineConfig(
            max_slots=2, max_seq_len=64, prefill_buckets=(16,),
            kv_page_size=8, ngram_speculation=2))


# ---- (d) the share -------------------------------------------------------

def test_eight_ranks_partial_outputs_sum_to_the_uncut_layer():
    """Each of 8 ranks holds one of the router's 8 experts, computes its
    part IN THE LATENT, projects that partial sum up by itself, and adds
    the shared expert; the parts, the shared expert counted once, add up
    to the uncut layer's result, and that is the plain reference's."""
    whole = get_model("nemotron-debug", param_dtype=jnp.float32,
                      dtype=jnp.float32).cfg
    assert whole.experts_held == whole.n_experts == 8
    layer = ShareMoE(whole)
    # inputs wide enough apart for the 0.02-normal router to send some
    # row to every expert
    x = 8.0 * jax.random.normal(jax.random.PRNGKey(1),
                                (2, 48, whole.d_model))
    flat = x.reshape(-1, whole.d_model)
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    assert "experts_gate_kernel" not in params
    assert params["experts_up_kernel"].shape == (8, 32, 32)
    assert params["shared"]["up_proj"]["kernel"].shape == (64, 48)
    with jax.default_matmul_precision("highest"):
        full = layer.apply({"params": params}, x, mutable=["step_stats",
                                                           "routing"])[0]
        scale = float(jnp.abs(full).max())      # float32 rounding of it
        m = _section(whole)
        sh = params["shared"]
        shared = jnp.square(jax.nn.relu(flat @ sh["up_proj"]["kernel"])) \
            @ sh["down_proj"]["kernel"]
        parts = []
        for rank in range(8):
            cfg = get_model("nemotron-debug", param_dtype=jnp.float32,
                            dtype=jnp.float32, expert_first=rank,
                            expert_count=1).cfg
            mine = {k: (v[rank:rank + 1] if k.startswith("experts_") else v)
                    for k, v in params.items()}
            out = ShareMoE(cfg).apply({"params": mine}, x, mutable=[
                "step_stats", "routing"])[0]
            parts.append(out.reshape(-1, whole.d_model) - shared)
            # and each rank's part is the reference's for that share
            want, _ = reference_nemotron.expert_layer(
                flat, mine, dict(m, num_experts=1, expert_first=rank))
            assert float(jnp.abs(out.reshape(want.shape) - want).max()) \
                < 1e-5 * scale
        total = sum(parts) + shared
        want, _ = reference_nemotron.expert_layer(flat, params, m)
    assert float(jnp.abs(total - full.reshape(total.shape)).max()) \
        < 1e-5 * scale
    assert float(jnp.abs(want - full.reshape(want.shape)).max()) \
        < 1e-5 * scale
    # no rank's part is nothing: every expert was chosen by some row
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)


def test_experts_without_a_gate_are_two_matmuls_and_relu_squared():
    g, d, f, e, k = 12, 16, 24, 4, 2
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (g, d))
    w_up = jax.random.normal(ks[1], (e, d, f)) * d ** -0.5
    w_down = jax.random.normal(ks[2], (e, f, d)) * f ** -0.5
    weights, top_idx = moe.route(jax.random.normal(ks[3], (g, e)), k)
    got, stats = moe.moe_dropless(x, weights, top_idx, None, w_up, w_down)
    want = jnp.zeros((g, d))
    for j in range(k):
        up = jnp.einsum("gd,gdf->gf", x, w_up[top_idx[:, j]])
        want = want + weights[:, j:j + 1] * jnp.einsum(
            "gf,gfd->gd", jnp.square(jax.nn.relu(up)),
            w_down[top_idx[:, j]])
    assert float(jnp.abs(got - want).max()) < 1e-4
    assert int(stats[0]) == g * k


# ---- (f) the siblings ----------------------------------------------------

def test_a_block_without_a_feed_forward_has_no_second_norm(tiny):
    _, params = tiny
    assert "mlp_norm" not in params["layer_3"] \
        and "moe" not in params["layer_3"]
    assert "mlp_norm" in params["layer_4"] and "moe" in params["layer_4"]
    # the three older families' blocks are what they were: tests/
    # test_solar.py:test_the_siblings_programs_lower_as_they_did pins
    # their six step programs by hash, Solar's own two among them (and
    # the six of the expert families that share `ShareMoE`, `moe_dropless`)
    older = Hybrid(HybridConfig.debug()).init_params(jax.random.PRNGKey(0))
    assert {"attn_norm", "mlp_norm", "mlp"} <= set(older["layer_0"])
