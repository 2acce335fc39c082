"""The third hybrid family (models/hybrid.py, Solar-Open2-250B): the
delta rule with a decay a key channel in its three forms and its step
kernel, the tiny decoder against the benchmark's plain reference and
through LLMEngine's slot state and pages, the expert share summed over
its eight ranks, and the sibling families' programs unmoved."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import reference_solar, replica_solar
from ray_tpu.models import HybridConfig, get_model
from ray_tpu.models.latent_moe import ShareMoE
from ray_tpu.ops import gated_deltanet as gdn
from ray_tpu.ops import moe
from ray_tpu.ops.attention import PagedKV, SlotState, kv_cache_spec
from ray_tpu.ops.pallas.gdn_decode import kda_decode_step
from ray_tpu.serve.llm.engine import LLMEngine, LLMEngineConfig

BUCKETS = (16, 32, 64)
CONTROLS = reference_solar.CONTROLS
# float32 model against the float32 reference: rounding of two orders of
# summation only
TIGHT = {"logit_tol_rel": 1e-3, "logit_mean_tol_rel": 1e-3,
         "logit_decode_mean_tol_rel": 1e-3, "argmax_tol_rel": 1e-3,
         "tie_margin_rel": 1e-4, "recurrence_tol_rel": 1e-5,
         "busy_new_tokens": 7}
# the largest rate the initialisers draw before the low-rank pair's own
# term: A = 16 and a step of 0.1 (models/hybrid.py)
TOP_RATE = 1.6


def _draw(seed, b, s, h, dk, dv, rate, constant=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = gdn.l2norm(jax.nn.silu(jax.random.normal(ks[0], (b, s, h, dk)))) \
        * dk ** -0.5
    k = gdn.l2norm(jax.nn.silu(jax.random.normal(ks[1], (b, s, h, dk))))
    v = jax.random.normal(ks[2], (b, s, h, dv))
    g = -rate * (jnp.ones((b, s, h, dk)) if constant
                 else jax.random.uniform(ks[3], (b, s, h, dk)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, h)))
    state = jax.random.normal(ks[5], (b, dk, h * dv))
    return q, k, v, g, beta, state


# ---- (a) the operator's three forms with a decay a channel ---------------

@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("rate,constant", [(0.1, False), (TOP_RATE, True),
                                           (20.0, False)])
def test_chunk_scan_is_the_recurrence_with_a_rate_a_channel(chunk, rate,
                                                            constant):
    q, k, v, g, beta, state = _draw(0, 2, 150, 3, 16, 8, rate, constant)
    want_o, want_s = gdn.recurrent(q, k, v, g, beta, state)
    got_o, got_s = gdn.chunk_scan(q, k, v, g, beta, state, chunk=chunk)
    assert np.isfinite(np.asarray(got_o)).all()
    # float32 rounding of two orders of summation
    assert float(jnp.abs(got_o - want_o).max()) < 1e-5
    assert float(jnp.abs(got_s - want_s).max()) < 5e-5


def test_the_factored_form_overflows_where_sub_chunks_do_not():
    """At the initialisers' largest rate exp(-G) passes float32 inside
    one 64-token chunk; the sub-chunked form never raises a positive
    exponent."""
    _q, k, _v, g, _beta, _ = _draw(1, 1, 64, 1, 16, 8, TOP_RATE, True)
    gc = jnp.cumsum(g[0, :, 0], axis=0)                       # (64, d_k)
    assert float(-gc[-1, 0]) > np.log(np.finfo(np.float32).max)
    factored = (k[0, :, 0] * jnp.exp(gc)) @ (k[0, :, 0] * jnp.exp(-gc)).T
    assert not np.isfinite(np.asarray(jnp.tril(factored))).all()
    q, k, v, g, beta, state = _draw(1, 1, 64, 1, 16, 8, TOP_RATE, True)
    o, s = gdn.chunk_scan(q, k, v, g, beta, state, chunk=64)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(
        np.asarray(s)).all()


def test_step_is_one_token_of_the_recurrence():
    q, k, v, g, beta, state = _draw(2, 2, 5, 3, 16, 8, 1.0)
    want_o, want_s = gdn.recurrent(q, k, v, g, beta, state)
    outs = []
    for t in range(5):
        o, state = gdn.step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t],
                            state)
        outs.append(o)
    assert float(jnp.abs(jnp.stack(outs, 1) - want_o).max()) < 1e-6
    assert float(jnp.abs(state - want_s).max()) < 1e-6


def test_frozen_positions_leave_state_and_tail_alone():
    q, k, v, g, beta, state = _draw(3, 2, 40, 3, 16, 8, 1.0)
    real = jnp.arange(40)[None, :] < jnp.asarray([23, 40])[:, None]
    gf, bf = gdn.freeze(g, beta, real)
    assert gf.shape == g.shape and bf.shape == beta.shape
    _, s_pad = gdn.chunk_scan(q, k, v, gf, bf, state, chunk=16)
    _, s_cut = gdn.chunk_scan(q[:1, :23], k[:1, :23], v[:1, :23],
                              g[:1, :23], beta[:1, :23], state[:1],
                              chunk=16)
    assert float(jnp.abs(s_pad[0] - s_cut[0]).max()) < 1e-5


def test_gates_draw_a_rate_a_channel():
    h, dk = 3, 16
    a = jax.random.normal(jax.random.PRNGKey(0), (2, 5, h, dk))
    b = jax.random.normal(jax.random.PRNGKey(1), (2, 5, h))
    a_log = jnp.log(jnp.asarray([0.5, 4.0, 16.0]))
    dt_bias = jax.random.normal(jax.random.PRNGKey(2), (h, dk))
    g, beta = gdn.gates(a, b, a_log, dt_bias, True)
    assert g.shape == (2, 5, h, dk) and beta.shape == (2, 5, h)
    want = -jnp.exp(a_log)[:, None] * jax.nn.softplus(a + dt_bias)
    assert float(jnp.abs(g - want).max()) < 1e-6
    assert float(beta.max()) <= 2.0 and float(g.max()) <= 0.0


# ---- (e) the scalar decay is the sibling's function ----------------------

def test_a_scalar_decay_is_the_channel_decay_with_equal_entries():
    q, k, v, g, beta, state = _draw(4, 2, 37, 3, 16, 8, 1.0)
    scalar = g[..., 0]
    spread = jnp.broadcast_to(scalar[..., None], g.shape)
    for form in (gdn.recurrent, lambda *a: gdn.chunk_scan(*a, chunk=16)):
        o1, s1 = form(q, k, v, scalar, beta, state)
        o2, s2 = form(q, k, v, spread, beta, state)
        assert float(jnp.abs(o1 - o2).max()) < 1e-6
        assert float(jnp.abs(s1 - s2).max()) < 1e-5
    o1, s1 = gdn.step(q[:, 0], k[:, 0], v[:, 0], scalar[:, 0], beta[:, 0],
                      state)
    o2, s2 = gdn.step(q[:, 0], k[:, 0], v[:, 0], spread[:, 0], beta[:, 0],
                      state)
    assert bool((o1 == o2).all()) and bool((s1 == s2).all())


# sha256 of the lowered text of the engine's prefill (2 x 16) and decode
# (5 rows, window 2 pages) programs over the sibling families' debug
# shapes. Pinned on the commit before this family (PR 50's) and held
# through PR 52-54: with the scalar decay, `out_gate` False,
# `attn_head_dim` None and no share nothing of them moved. **All six
# re-pinned on PR 55's commit**: all three families run
# `ops/gated_deltanet.py:causal_conv`, and that PR changed the function
# and the tail's pool shape for all of them at once (the tail a row a
# slot, the one-token form on (rows, channels)); its values are held to
# the parent's form in tests/test_gated_deltanet.py. From here on the
# numbers again say: shared code moved under a sibling.
SIBLING_PROGRAMS = {
    ("hybrid-debug", "decode"):
        "33d96d657ea706985e051b16db4fa5546064160a0ed997b895faea7f8faec624",
    ("hybrid-debug", "prefill"):
        "5c70a9d4fa25753cb3d704367a68055cd3ec144fba5447f27c8181a9a38823b7",
    ("lfm2-moe-debug", "decode"):
        "0e1e6adc751245f8141da81cd06b1b1d9c0e8cb3d49ba7267cf9ec1e20944433",
    ("lfm2-moe-debug", "prefill"):
        "1e421ed1d85b60e6ce54d6e70bb5be60692f1261a2e0c6e3803aa0463a14d202",
    # this family's own: the chunk kernel (PR 53) moved neither, since
    # off the TPU `models/hybrid.py:_scan` keeps the plain chunkwise
    # form and the kernel never touched the decode program. What the
    # TPU's prefill programs hold is tests/test_tpu_compile.py's
    ("solar-debug", "decode"):
        "db2f127bde46d05b2a15cbb211338f38f60c72dfe68342f2f5d27dab47cf37f4",
    ("solar-debug", "prefill"):
        "5abf049b655f93d89e344d6c3e140f883e8c7a3c7831ff37409dc595756c1d68",
    # the three expert families OUTSIDE models/hybrid.py (Sarvam's,
    # OLMoE's, Xing's debug shapes), pinned on PR 55's commit when PR 56
    # made `ops/moe.py:moe_dropless`'s gate optional and gave
    # `models/latent_moe.py:ShareMoE` three arms that are off by default
    # (a latent pair, experts without a gate, a shared expert of its own
    # width). Off the TPU `grouped_matmul` lowers to `ragged_dot` where
    # the chip runs `gmm`; nothing else of the path reads the backend
    ("latent-moe-debug", "decode"):
        "e0da4a83a4a47f6942a88f27a4629c83f1d58a3bdc0f4c6bcae786a8032d98ff",
    ("latent-moe-debug", "prefill"):
        "de64cdbd11f2dbd5086ece487dd51183f0cbda623a82bb2e1af30d6b06bebe5b",
    ("mixtral-debug", "decode"):
        "a754379d612472be9d9ee51306f13027c944b60351fc3aac1c2c60f75480d699",
    ("mixtral-debug", "prefill"):
        "00ee98786191bb634a8a07af55794475366bc842eceb892aa5e3188a892b7adf",
    ("xing-debug", "decode"):
        "6d301c58ffd02288e10cab14f709cf459dd58651698244d64f21a05900138b53",
    ("xing-debug", "prefill"):
        "7d951a00fb9cb86e500ee3b20fa6be2c5cca58ae244f2a3340aa45acbfcc9bb9",
}


@pytest.mark.parametrize("name,program", sorted(SIBLING_PROGRAMS))
def test_the_siblings_programs_lower_as_they_did(name, program):
    model = get_model(name)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = LLMEngine(model, params, LLMEngineConfig(
        max_slots=4, max_seq_len=64, prefill_buckets=(16, 32),
        kv_page_size=8))
    try:
        if program == "decode":
            s = 5
            text = jax.jit(eng._decode_paged_impl,
                           static_argnames=("window_pages",)).lower(
                params, eng._pools, eng._pages.rows(), eng._state.lengths,
                jnp.zeros((s,), jnp.int32), jnp.ones((s,), bool),
                jnp.zeros((s,), jnp.float32), jnp.ones((s,), jnp.float32),
                jax.random.PRNGKey(0), window_pages=2).as_text()
        else:
            g = 2
            text = jax.jit(eng._prefill_paged_impl,
                           static_argnames=("pad_len",)).lower(
                params, eng._pools, eng._pages.rows(), eng._state.lengths,
                jnp.zeros((g, 16), jnp.int32), jnp.zeros((g,), jnp.int32),
                jnp.full((g,), 9, jnp.int32), jnp.zeros((g,), jnp.float32),
                jnp.ones((g,), jnp.float32), jax.random.PRNGKey(0),
                pad_len=16, n_real=jnp.int32(2)).as_text()
    finally:
        eng.shutdown()
    assert hashlib.sha256(text.encode()).hexdigest() == \
        SIBLING_PROGRAMS[name, program]


# ---- (b) the step kernel, interpreted ------------------------------------

@pytest.mark.parametrize("h,dk,dv", [(4, 16, 32), (2, 8, 128), (3, 16, 64)])
def test_kernel_is_the_step_and_writes_idle_rows_through(h, dk, dv):
    q, k, v, g, beta, state = _draw(5, 5, 1, h, dk, dv, 1.0)
    q, k, v, g, beta = (x[:, 0] for x in (q, k, v, g, beta))
    live = jnp.asarray([True, False, True, True, False])
    g = jnp.where(live[:, None, None], g, 0.0)
    beta = jnp.where(live[:, None], beta, 0.0)
    want_o, want_s = gdn.step(q, jnp.where(live[:, None, None], k, 0.0), v,
                              g, beta, state)
    got_o, got_s = kda_decode_step(q, k, v, g, beta, state + 0.0,
                                   interpret=True)
    assert float(jnp.abs(got_o - want_o)[live].max()) < 1e-6
    assert float(jnp.abs(got_s - want_s).max()) < 1e-6
    # an idle row's state comes back bit for bit, whatever its k
    assert bool((got_s[~live] == state[~live]).all())


# ---- the tiny decoder ----------------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    model = get_model("solar-debug", param_dtype=jnp.float32,
                      dtype=jnp.float32, expert_first=2, expert_count=4)
    params = model.init_params(jax.random.PRNGKey(0))
    # norm weights off their initial ones
    params = jax.tree_util.tree_map(
        lambda a: a * (1 + 0.3 * jax.random.normal(
            jax.random.PRNGKey(a.size), a.shape, a.dtype))
        if a.ndim == 1 and a.shape[0] > 8 else a, params)
    return model, params


def _engine(tiny, **kw):
    model, params = tiny
    return LLMEngine(model, params, LLMEngineConfig(**{**dict(
        max_slots=3, max_seq_len=128, prefill_buckets=BUCKETS,
        kv_page_size=8, pipeline_depth=3, max_prefill_batch=2), **kw}))


def _section(cfg):
    """The reference's model section of a program config."""
    n = cfg.n_layers
    m = dict(
        hidden_size=cfg.d_model, num_hidden_layers=n,
        num_attention_heads=cfg.n_heads, num_key_value_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, moe_intermediate_size=cfg.d_expert,
        n_routed_experts=cfg.experts_held, num_experts=cfg.experts_held,
        router_width=cfg.n_experts, expert_first=cfg.expert_first,
        n_shared_experts=cfg.n_shared_experts,
        num_experts_per_tok=cfg.experts_per_token,
        norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling,
        rms_norm_eps=cfg.norm_eps, vocab_size=cfg.vocab_size,
        use_gqa_gate=cfg.out_gate, kda_rank=cfg.kda_rank,
        kda_allow_neg_eigval=cfg.linear_allow_neg_eigval,
        gqa_layers=[i for i, k in enumerate(cfg.layer_types)
                    if k == "full_attention"],
        linear_attn_config={
            "num_heads": cfg.linear_n_heads, "head_dim": cfg.linear_key_dim,
            "short_conv_kernel_size": cfg.linear_conv_kernel})
    m["layer_types"] = reference_solar.layer_types(m)
    return m


def test_the_preset_is_the_published_model(tiny):
    cfg = tiny[0].cfg
    assert cfg.layer_types == ("full_attention", "kda", "kda", "kda")
    assert _section(cfg)["layer_types"] == list(cfg.layer_types)
    assert cfg.pre_norm and cfg.out_gate and not cfg.tie_embeddings
    assert cfg.rope_theta is None and cfg.qk_norm is False
    assert not any(cfg.dense_ff(i) for i in range(4))
    assert tiny[0].step_stats == moe.MOE_STATS
    big = get_model("solar-open2-250b").cfg
    assert (big.n_layers, big.d_model, big.vocab_size) == (48, 4096, 196608)
    assert (big.n_heads, big.n_kv_heads, big.head_dim) == (64, 8, 128)
    assert [i for i, k in enumerate(big.layer_types)
            if k == "full_attention"] == list(range(0, 48, 4))
    assert set(big.layer_types) == {"full_attention", "kda"}
    assert (big.linear_n_heads, big.linear_key_dim, big.linear_value_dim,
            big.linear_conv_kernel, big.kda_rank) == (64, 128, 128, 4, 128)
    assert (big.d_expert, big.n_experts, big.experts_held,
            big.experts_per_token, big.n_shared_experts) \
        == (1280, 320, 320, 8, 1)
    # what the new fields' defaults keep: the two sibling families
    for name in ("hybrid-debug", "lfm2-moe-debug"):
        sib = get_model(name).cfg
        assert (sib.out_gate, sib.attn_head_dim, sib.n_shared_experts,
                sib.expert_first, sib.expert_count) == (False, None, 0, 0,
                                                        None)
        assert sib.experts_held == sib.n_experts
        assert sib.head_dim == sib.d_model // sib.n_heads
    with pytest.raises(ValueError, match="not among the router's"):
        get_model("solar-debug", expert_first=6, expert_count=4)


def test_the_presets_prefill_through_the_flash_kernel_on_a_tpu(monkeypatch):
    """The family's own choice, so that the registry's presets, the
    documented path and the benchmark's cell build the same programs:
    XLA's plain attention in front of delta-rule layers hung the chip at
    2 x 1 024 tokens (docs/SERVING.md). A caller's own `attn_impl`
    still wins; off the chip the rule is every family's."""
    assert HybridConfig.solar_open2_250b().attn_impl == "auto"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert HybridConfig.solar_open2_250b().attn_impl == "pallas"
    assert HybridConfig.solar_debug().attn_impl == "pallas"
    assert HybridConfig.solar_debug(attn_impl="xla").attn_impl == "xla"
    assert HybridConfig.olmo_hybrid_7b().attn_impl == "auto"


def test_the_cache_is_declared_a_layer(tiny):
    spec = kv_cache_spec(tiny[0])
    assert [c.entry for c in spec] == [PagedKV] + [SlotState] * 3
    assert [c.by_slot for c in spec] == [False, True, True, True]
    assert spec[1].shapes == ((16, 64), (3 * 192,))
    assert spec[1].dtypes[0] == jnp.float32
    # the cut the benchmark serves: 4 MiB of state and one row of three
    # inputs of 3 x 8 192 a slot a delta-rule layer (the tail never lies
    # K - 1 rows deep), 4 096 B of K and V a token
    cut = kv_cache_spec(get_model("solar-open2-250b", n_layers=4))
    state, tail = cut[1].shapes
    assert state == (128, 8192) and tail == (3 * 24576,)
    assert 4 * state[0] * state[1] == 4 * 2 ** 20
    assert cut[0].shapes == ((8, 128), (8, 128))


def test_full_forward_agrees_with_the_reference(tiny):
    model, params = tiny
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        1, 256, (1, 45)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        got, _ = model.apply({"params": params}, tokens)
    want, records = reference_solar.forward(params, tokens[0],
                                            _section(model.cfg))
    assert len(records) == 4                    # every layer routes
    assert float(jnp.abs(got[0] - want).max()) < 2e-4 * float(want.std())


def test_the_block_lowers_its_scopes(tiny):
    model, params = tiny
    tokens = jnp.zeros((1, 8), jnp.int32)
    text = jax.jit(lambda t: model.apply({"params": params}, t)[0]).lower(
        tokens).as_text(debug_info=True)
    for scope in ("kda.proj", "kda.conv", "kda.gates", "kda.scan",
                  "kda.gate_out", "attn.out_gate", "moe.route",
                  "moe.shared"):
        assert scope in text, scope


# ---- (c) through the engine, against the reference -----------------------

@pytest.mark.parametrize("prompt_len", [3, 21, 32])
def test_engine_logits_against_the_reference(tiny, prompt_len):
    """Prefill (the chunkwise form, stopped at the prompt's true length
    inside its bucket) into the slot state and the pages, then decode
    (the one-token form) as the benchmark's check drives the engine: its
    own step programs handing out logits and the experts they chose,
    every slot live, the request in a slot another has left. Every
    control of the reference that float32 can tell fails."""
    model, params = tiny
    eng = _engine(tiny)
    try:
        prompt = np.random.default_rng(prompt_len).integers(1, 256,
                                                            prompt_len)
        answer = eng.generate_sync(prompt, max_new_tokens=6)
        with jax.default_matmul_precision("highest"):
            out = replica_solar.serve_check(eng, {
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
    assert out["not_followed"] == 0
    assert (out["slots"], out["requests_beside"]) == (3, 6)
    assert out["tokens_as_idle"] and out["tokens_with_logits_as_timed"]
    passed = [n for n, c in out.get("controls", {}).items() if c["ok"]]
    assert passed == [] and (prompt_len != 21
                             or len(out["controls"]) == len(CONTROLS))
    # every real row is routed in each of the 4 layers to 2 experts of
    # the router's 8; the share holds 4 of them
    assert stats["moe_routed_assignments"] == 2 * stats["moe_rows"] > 0
    assert 0 < stats["moe_assignments"] < stats["moe_routed_assignments"]
    # three delta-rule layers keep a state row a slot
    assert stats["decode_state_rows_window"] % 3 == 0
    assert 0 < stats["decode_state_rows_live"] \
        <= stats["decode_state_rows_window"]


def test_the_kernel_route_decodes_as_the_plain_step(tiny, monkeypatch):
    """The engine's decode program with `kda_decode_step` interpreted in
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


def test_prefill_counts_the_chunks_its_rows_fill(tiny):
    """`prefill_chunks_window` / `prefill_chunks_live` by hand: chunks of
    16 in three delta-rule layers. Prompts of 40 and 50 tokens share a
    64-wide call of two rows (2 x 4 chunks; 3 + 4 hold a token), a
    prompt of 5 runs alone in 16 (1; 1)."""
    eng = _engine(tiny)
    assert tiny[0].chunk_scan_layers() == (3, 16)
    rids = []

    def submit_all():       # on the loop thread: one admission pass
        for n in (40, 50, 5):
            rids.append(eng.submit(np.arange(1, n + 1), max_new_tokens=2))
    try:
        eng._run_on_loop(submit_all)
        for rid in rids:
            assert len(list(eng.stream(rid))) == 2
        st = eng.get_stats()
    finally:
        eng.shutdown()
    assert st["prefill_shapes"] == {"64x2": 1, "16x1": 1}
    assert st["prefill_chunks_window"] == 3 * (2 * 4 + 1)
    assert st["prefill_chunks_live"] == 3 * (3 + 4 + 1)


# ---- (d) the share -------------------------------------------------------

def test_eight_ranks_partial_outputs_sum_to_the_uncut_layer():
    """Each of 8 ranks holds one of the router's 8 experts and computes
    its part and the shared expert; the parts, the shared expert counted
    once, add up to the uncut layer's result, and that is the plain
    reference's."""
    whole = get_model("solar-debug", param_dtype=jnp.float32,
                      dtype=jnp.float32).cfg
    assert whole.experts_held == whole.n_experts == 8
    layer = ShareMoE(whole)
    # inputs wide enough apart for the 0.02-normal router to send some
    # row to every expert
    x = 8.0 * jax.random.normal(jax.random.PRNGKey(1),
                                (2, 48, whole.d_model))
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    with jax.default_matmul_precision("highest"):
        full = layer.apply({"params": params}, x, mutable=["step_stats",
                                                           "routing"])[0]
        scale = float(jnp.abs(full).max())      # float32 rounding of it
        m = _section(whole)
        shared = reference_solar.swiglu_mlp(
            x.reshape(-1, whole.d_model), params["shared"], m)
        parts = []
        for rank in range(8):
            cfg = get_model("solar-debug", param_dtype=jnp.float32,
                            dtype=jnp.float32, expert_first=rank,
                            expert_count=1).cfg
            mine = {k: (v[rank:rank + 1] if k.startswith("experts_") else v)
                    for k, v in params.items()}
            out = ShareMoE(cfg).apply({"params": mine}, x, mutable=[
                "step_stats", "routing"])[0]
            parts.append(out.reshape(-1, whole.d_model) - shared)
            # and each rank's part is the reference's for that share
            want, _ = reference_solar.expert_layer(
                x.reshape(-1, whole.d_model), mine,
                dict(m, num_experts=1, expert_first=rank))
            assert float(jnp.abs(out.reshape(want.shape) - want).max()) \
                < 1e-5 * scale
        total = sum(parts) + shared
        want, _ = reference_solar.expert_layer(
            x.reshape(-1, whole.d_model), params, m)
    assert float(jnp.abs(total - full.reshape(total.shape)).max()) \
        < 1e-5 * scale
    assert float(jnp.abs(want - full.reshape(want.shape)).max()) \
        < 1e-5 * scale
    # no rank's part is nothing: every expert was chosen by some row
    assert all(float(jnp.abs(p).max()) > 0 for p in parts)
