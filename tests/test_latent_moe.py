"""The latent-attention expert-share family (models/latent_moe.py:
sarvam-105b) at a small size on the CPU, seeded random weights, float32
where exactness is claimed: the system against the plain reference kept
with the benchmark (benchmarks/harness/reference_sarvam.py), the two
forms of latent attention against each other, the shares of an expert
layer against the uncut layer, the routing convention and YaRN against
hand-computed values, the Pallas kernel against the gather route, the
engine's latent pool (pages, prefix pages, the sliced vocabulary), and
the other families' pools and decode programs against what they were.
"""
import collections
import dataclasses
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import reference_sarvam
from ray_tpu.models import LatentMoE, LatentMoEConfig, get_model
from ray_tpu.models.latent_moe import LatentAttention, ShareMoE
from ray_tpu.ops import route, yarn_frequencies, yarn_softmax_scale
from ray_tpu.ops.attention import (PagedKV, PagedLatent, kv_cache_spec,
                                   latent_cached_attention)
from ray_tpu.ops.pallas.latent_attention import latent_decode_attention
from ray_tpu.serve.llm.engine import LLMEngine, LLMEngineConfig


def _section(c: LatentMoEConfig) -> dict:
    """The reference's model section of a program config."""
    return {"hidden_size": c.d_model, "num_hidden_layers": c.n_layers,
            "num_attention_heads": c.n_heads,
            "qk_nope_head_dim": c.qk_nope_dim,
            "qk_rope_head_dim": c.qk_rope_dim, "v_head_dim": c.v_head_dim,
            "kv_lora_rank": c.kv_lora_rank,
            "num_experts": c.experts_held, "router_width": c.n_experts,
            "expert_first": c.expert_first,
            "num_experts_per_tok": c.experts_per_token,
            "routed_scaling_factor": c.routed_scaling,
            "rope_theta": c.rope_theta, "rms_norm_eps": c.norm_eps,
            "rope_scaling": {
                "factor": c.rope_factor, "beta_fast": c.rope_beta_fast,
                "beta_slow": c.rope_beta_slow,
                "mscale_all_dim": c.rope_mscale_all_dim,
                "original_max_position_embeddings":
                    c.rope_original_max_len}}


def _model(**kw):
    cfg = LatentMoEConfig.debug(dtype=jnp.float32, **kw)
    model = LatentMoE(cfg)
    return model, model.init_params(jax.random.PRNGKey(3))


@pytest.fixture(scope="module")
def share():
    """The debug shape holding experts 2..5 of a router 8 wide."""
    return _model(expert_first=2, expert_count=4)


def _engine(model, params, **kw):
    return LLMEngine(model, params, LLMEngineConfig(**{**dict(
        max_slots=4, max_seq_len=64, prefill_buckets=(16, 32, 64),
        kv_page_size=8), **kw}))


# (a) the system against the plain reference ------------------------------
def test_full_forward_agrees_with_the_reference(share):
    model, params = share
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        1, 256, (1, 24)), jnp.int32)
    got, _ = model.apply({"params": params}, tokens)
    want = reference_sarvam.forward_logits(params, tokens[0],
                                           _section(model.cfg))
    np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-4)


def test_prefill_then_decode_through_the_engine_agrees_with_the_reference(
        share):
    """Prefill (expanded form) and 8 decode steps (absorbed form) through
    LLMEngine's paged latent cache: the log-probability the engine
    reports for every token it emitted is the reference's full forward's,
    and the greedy tokens are its arg-maxima."""
    model, params = share
    eng = _engine(model, params, logprobs=True)
    try:
        prompt = np.random.default_rng(1).integers(1, 256, 13)
        rid = eng.submit(prompt, max_new_tokens=8)
        out = list(eng.stream_detailed(rid))
        assert eng.get_stats()["kv_bytes_per_token"] == \
            model.cfg.n_layers * model.cfg.cache_width * 4
    finally:
        eng.shutdown()
    toks = [t for t, _lp in out]
    seq = np.concatenate([prompt, toks])[:-1]
    ref = reference_sarvam.forward_logits(params, jnp.asarray(seq),
                                          _section(model.cfg))
    rows = ref[len(prompt) - 1:]                     # the 8 sampled rows
    assert toks == np.asarray(rows.argmax(-1)).tolist()
    want = jax.nn.log_softmax(rows, -1)[np.arange(8), np.asarray(toks)]
    np.testing.assert_allclose([lp for _t, lp in out], want, rtol=1e-3,
                               atol=1e-3)


def test_rows_check_decomposes_the_check_at_the_rehearsal_size(tmp_path):
    """tools/rows_check.py on the sarvam configuration's `rehearse` size
    (8 slots and the scratch row): the engine's tokens are `model.apply`'s
    at the engine's row count, and on the CPU the 1-row programs' too
    (the benchmark's own and the tool's), so all five comparisons read
    the same gap and pass."""
    from tools import rows_check
    out = tmp_path / "rows.json"
    assert rows_check.main([
        "--config", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "benchmarks", "configs", "sarvam-105b-serve-ep4-l6.json"),
        "--seed", "5", "--rows", "9", "--prompt-len", "40",
        "--new-tokens", "6", "--rehearse", "--out", str(out)]) == 0
    got = json.loads(out.read_text())
    assert len(got["tokens"]["engine"]) == 6 and got["engine_repeatable"]
    assert got["engine_is_rows_n"] and got["rows_n_is_rows_1"]
    assert got["same_experts_pair_share_rows_n_vs_1"] == 1.0
    judged = [got[k] for k in got if "_following_" in k]
    assert len(judged) == 5 and all(j["ok"] for j in judged)
    assert len({j["argmax_gap_rel"] for j in judged}) == 1


# (b) absorbed form = expanded form on one layer --------------------------
def test_absorbed_form_is_the_expanded_form():
    cfg = LatentMoEConfig.debug(dtype=jnp.float32)
    layer = LatentAttention(cfg)
    b, s, ps = 2, 11, 4
    x = jnp.asarray(np.random.default_rng(2).standard_normal(
        (b, s, cfg.d_model)), jnp.float32)
    cos, sin = yarn_frequencies(cfg.qk_rope_dim, 64, cfg.rope_theta,
                                factor=cfg.rope_factor,
                                original_max_len=cfg.rope_original_max_len)
    params = layer.init(jax.random.PRNGKey(0), x, cos, sin)
    expanded, _ = layer.apply(params, x, cos, sin)
    n_pages = 3
    entry = PagedLatent(
        jnp.zeros(((b * n_pages + 1) * ps, cfg.cache_width), jnp.float32),
        jnp.arange(b * n_pages, dtype=jnp.int32).reshape(b, n_pages),
        jnp.zeros((b,), jnp.int32), ps)
    steps = []
    for t in range(s):                   # one token at a time, absorbed
        out, entry = layer.apply(params, x[:, t:t + 1], cos, sin, entry,
                                 jnp.full((b, 1), t, jnp.int32))
        steps.append(out)
    np.testing.assert_allclose(jnp.concatenate(steps, 1), expanded,
                               rtol=1e-4, atol=1e-5)
    assert entry.lengths.tolist() == [s, s]


@pytest.mark.parametrize("form", ["prefill", "decode"])
def test_the_pinned_query_product_is_the_unpinned_one(form, monkeypatch):
    """`q_proj`'s product is pinned flat by an `optimization_barrier` (so
    that the TPU compiler does not re-lay the kernel out in HBM a layer a
    step: tests/test_tpu_compile.py): the barrier is the identity, in a
    whole prefill and in a decode step against the pool."""
    cfg = LatentMoEConfig.debug(dtype=jnp.float32)
    layer = LatentAttention(cfg)
    b, ps, n_pages = 2, 4, 3
    s = 11 if form == "prefill" else 1
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((b, s, cfg.d_model)), jnp.float32)
    cos, sin = yarn_frequencies(cfg.qk_rope_dim, 64, cfg.rope_theta,
                                factor=cfg.rope_factor,
                                original_max_len=cfg.rope_original_max_len)
    params = layer.init(jax.random.PRNGKey(0), x, cos, sin)
    start = 0 if form == "prefill" else 7
    entry = PagedLatent(
        jnp.asarray(rng.standard_normal(
            ((b * n_pages + 1) * ps, cfg.cache_width)), jnp.float32),
        jnp.arange(b * n_pages, dtype=jnp.int32).reshape(b, n_pages),
        jnp.full((b,), start, jnp.int32), ps, fresh=form == "prefill")
    positions = start + jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32),
                                         (b, s))

    def call():
        out, new = layer.apply(params, x, cos, sin, entry, positions)
        return out, new.flat
    # (a new lambda a trace: make_jaxpr keeps what it traced of `call`)
    pinned = call()
    assert "optimization_barrier" in str(jax.make_jaxpr(lambda: call())())
    monkeypatch.setattr(jax.lax, "optimization_barrier", lambda q: q)
    assert "optimization_barrier" not in str(
        jax.make_jaxpr(lambda: call())())
    for got, want in zip(pinned, call()):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


# (c) the shares add up ---------------------------------------------------
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Four 2-expert shares of one expert layer of a router 8 wide, the
    shared expert counted once, sum to what the plain reference gives
    for the uncut layer: the tie between the chip's share and the
    model."""
    whole = LatentMoEConfig.debug(dtype=jnp.float32)
    h = jnp.asarray(np.random.default_rng(4).standard_normal(
        (1, 19, whole.d_model)), jnp.float32)
    params = ShareMoE(whole).init(jax.random.PRNGKey(5), h)["params"]
    m = _section(whole)
    weights, _ = reference_sarvam.routing(h[0], params, m)
    uncut = (reference_sarvam.experts(h[0], weights, params, m)
             + reference_sarvam.swiglu_mlp(h[0], params["shared"], m))
    shared = reference_sarvam.swiglu_mlp(h[0], params["shared"], m)
    total, ran = shared, 0
    for rank in range(4):
        part = dataclasses.replace(whole, expert_first=2 * rank,
                                   expert_count=2)
        held = {k: (v[2 * rank:2 * rank + 2] if k.startswith("experts_")
                    else v) for k, v in params.items()}
        out, sown = ShareMoE(part).apply({"params": held}, h,
                                         mutable=["step_stats"])
        stats = dict(zip(LatentMoE(part).step_stats,
                         np.asarray(sown["step_stats"]["moe"][0])))
        assert stats["moe_routed_assignments"] == 19 * 2
        ran += stats["moe_assignments"]
        total = total + (out[0] - shared)
    assert ran == 19 * 2             # every routed pair ran on one share
    np.testing.assert_allclose(total, uncut, rtol=1e-4, atol=1e-5)


# (d) the sigmoid convention by hand --------------------------------------
def test_sigmoid_routing_selects_by_biased_scores_and_weighs_by_scores():
    logits = jnp.asarray([[2.0, 0.0, -1.0, 1.0]])
    bias = jnp.asarray([-1.0, 0.0, 1.0, 0.0])
    s = 1.0 / (1.0 + np.exp(-np.asarray([2.0, 0.0, -1.0, 1.0])))
    # s + b = [-0.119, 0.5, 1.269, 0.731]: experts 2 and 3 are selected,
    # though expert 0 has the largest score
    weights, idx = route(logits, 2, "sigmoid_bias", True, select_bias=bias,
                         scale=2.5)
    assert idx.tolist() == [[2, 3]]
    np.testing.assert_allclose(
        weights[0], 2.5 * s[[2, 3]] / s[[2, 3]].sum(), rtol=1e-6)
    plain, idx = route(logits, 2, "sigmoid_bias")     # no bias, no norm
    assert idx.tolist() == [[0, 3]]
    np.testing.assert_allclose(plain[0], s[[0, 3]], rtol=1e-6)


# (e) YaRN by hand --------------------------------------------------------
def test_yarn_frequencies_and_scale_against_hand_computed_values():
    assert yarn_softmax_scale(192, 40.0, 1.0) == pytest.approx(
        0.13523, abs=5e-6)                  # 192^-1/2 x (0.1 ln 40 + 1)^2
    assert yarn_softmax_scale(192, 40.0, 0.0) == pytest.approx(192 ** -0.5)
    cos, sin = yarn_frequencies(64, 8, 10000.0, factor=40.0,
                                original_max_len=4096)
    inv = np.arctan2(np.asarray(sin[1]), np.asarray(cos[1]))   # position 1
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    # correction dimensions 10 (32 turns over 4 096 positions) and 23 (1)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-5)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40.0, rtol=1e-5)
    ramp = (16 - 10) / 13.0
    assert inv[16] == pytest.approx(
        plain[16] * (1 - ramp) + plain[16] / 40.0 * ramp, rel=1e-5)
    np.testing.assert_allclose(np.asarray(cos[0]), 1.0)   # no magnitude


# (f) the kernel against the gather route ---------------------------------
@pytest.mark.parametrize("pages_per_block", [1, 2, None])
def test_latent_kernel_interpreted_matches_the_gather_route(
        monkeypatch, pages_per_block):
    """Ragged lengths, an empty slot, a row whose first page is another
    row's (a shared prefix page), a replayed query position."""
    rng = np.random.default_rng(6)
    rows, h, w, d_v, ps, n_table = 5, 4, 128, 96, 8, 4
    flat = jnp.asarray(rng.standard_normal((12 * ps, w)), jnp.float32)
    table = jnp.asarray([[0, 1, 2, 3], [4, 5, 11, 11], [11, 11, 11, 11],
                         [0, 6, 7, 11], [8, 9, 10, 11]], jnp.int32)
    lengths = jnp.asarray([29, 9, 0, 17, 24], jnp.int32)
    qpos = jnp.asarray([28, 8, 0, 16, 20], jnp.int32)
    q = jnp.asarray(rng.standard_normal((rows, h, w)), jnp.float32)
    got = latent_decode_attention(
        q, flat, table, lengths, ps, d_v=d_v, scale=0.2, qpos=qpos,
        interpret=True, pages_per_block=pages_per_block)
    # the gather route, on an entry that already holds every token: the
    # token written is the one the pool has at the query's position
    monkeypatch.setenv("RAY_TPU_PAGED_ATTN_IMPL", "gather")
    entry = PagedLatent(flat, table, lengths, ps)
    at = entry.flat_rows(qpos[:, None])
    want, _ = latent_cached_attention(
        q[:, None], flat[at], entry, qpos[:, None], 0.2, d_v)
    live = np.asarray(lengths) > 0
    np.testing.assert_allclose(np.asarray(got)[live],
                               np.asarray(want[:, 0])[live],
                               rtol=1e-4, atol=1e-5)
    assert not np.asarray(got)[~live].any()      # an empty slot: zeros


def test_latent_decode_runs_the_kernel_when_asked(monkeypatch, share):
    """RAY_TPU_PAGED_ATTN_IMPL=pallas sends the engine's decode steps
    through the interpreted kernel; the tokens are the gather route's."""
    model, params = share
    prompt = np.random.default_rng(7).integers(1, 256, 10)

    def generate():
        eng = _engine(model, params)
        try:
            return eng.generate_sync(prompt, max_new_tokens=5)
        finally:
            eng.shutdown()
    want = generate()
    monkeypatch.setenv("RAY_TPU_PAGED_ATTN_IMPL", "pallas")
    assert generate() == want


# (g) prefix pages on a latent pool ---------------------------------------
def test_prefix_registration_and_page_copy_on_a_latent_pool(share):
    model, params = share
    rng = np.random.default_rng(8)
    prefix, suffix = rng.integers(1, 256, 12), rng.integers(1, 256, 5)
    eng = _engine(model, params, max_prefixes=1)
    try:
        assert all(len(layer) == 1 and layer[0].shape[1:]
                   == (model.cfg.cache_width,) for layer in eng._pools)
        want = eng.generate_sync(np.concatenate([prefix, suffix]),
                                 max_new_tokens=6)
        pid = eng.register_prefix(prefix)   # 12 tokens: a page and a half
        got = eng.generate_sync(suffix, max_new_tokens=6, prefix_id=pid)
        assert got == want
        assert eng.get_stats()["prefix_tokens_saved"] == 12
        # the allocator's copy_page on the pool's own arrays: page 1 onto 3
        before = [np.asarray(layer[0]) for layer in eng._pools]
        pools = eng._pages.copy_page(eng._pools, jnp.int32(1), jnp.int32(3))
        for old, (new,) in zip(before, pools):
            np.testing.assert_array_equal(np.asarray(new)[24:32],
                                          old[8:16])
            np.testing.assert_array_equal(np.asarray(new)[:24], old[:24])
    finally:
        eng.shutdown()


# (h) a sliced vocabulary -------------------------------------------------
def test_a_sliced_vocabulary_stays_inside_the_slice():
    """The model is built with the slice's size: ids sampled at a
    temperature and their log-probabilities are over the slice."""
    model, params = _model(vocab_size=64)
    assert params["lm_head"]["kernel"].shape[1] == 64
    assert params["token_embed"]["embedding"].shape[0] == 64
    eng = _engine(model, params, logprobs=True)
    try:
        rid = eng.submit(np.random.default_rng(9).integers(1, 64, 9),
                         max_new_tokens=16, temperature=1.5)
        out = list(eng.stream_detailed(rid))
    finally:
        eng.shutdown()
    assert len(out) == 16
    assert all(0 <= t < 64 for t, _lp in out)
    # no log-probability mass outside the slice: each is at least the
    # uniform bound's neighbourhood and none is positive
    assert all(np.isfinite(lp) and lp <= 0.0 for _t, lp in out)


# (i) the other families are what they were -------------------------------
# sha256 of the lowered text of the engine's decode program (4 slots,
# window 2 pages). "llama-wide" has heads of 128 and lowers, letter for
# letter, what the commit before PR 40 lowered: heads that fill the 128
# lanes keep the path they had. The debug models' heads are 16 wide:
# since PR 40 their pool rows hold eight heads side by side
# (ops/attention.py:packed_kv_shape), which is a pad and three reshapes
# a layer more than the programs PR 31 pinned (860 and 875 operations);
# they are pinned as they lower now. PR 49 moved the sampler's draw
# under its two `cond`s (13 operations more in the text: the draw stands
# in both branches of the inner one) and nothing else: with the one-path
# sampler of tests/test_engine_sampler.py in `_sample_tokens`' place all
# three, and the two of tests/test_xing.py, lower to the texts pinned
# before it.
PARENT_DECODE_TEXT = {
    "llama-wide":
        "61f3e15ccdcdd190bcbad8e0f28842fd979e002e5bab4e101813a45373f5ac6d",
    "llama-debug":
        "39100b47f48095ff7e90353673d2e12d677af9bcea3291223a198eeb3a425d3d",
    "gpt2-debug":
        "736f715b5403d8f231791479cd29191b20bc4eea9743145b2cde6af4c7f14471",
}


def _decode_text(name: str):
    from ray_tpu.models import Llama, LlamaConfig
    from ray_tpu.ops.attention import packed_kv_shape
    model = Llama(LlamaConfig(
        vocab_size=256, d_model=256, n_layers=2, n_heads=2, n_kv_heads=1,
        d_ff=128, max_seq_len=128)) if name == "llama-wide" \
        else get_model(name)
    params = model.init_params(jax.random.PRNGKey(0))
    eng = _engine(model, params, prefill_buckets=(16, 32))
    try:
        c = model.cfg
        kv = packed_kv_shape(c.n_kv_heads, c.head_dim)
        assert kv == ((c.n_kv_heads, c.head_dim) if c.head_dim == 128
                      else (1, 128))
        assert kv_cache_spec(model) == [
            (PagedKV, (kv, kv), (c.dtype, c.dtype), False)] * c.n_layers
        assert all(len(layer) == 2 and layer[0].shape == layer[1].shape
                   == (33 * 8, *kv) for layer in eng._pools)
        s = 5
        return jax.jit(eng._decode_paged_impl,
                       static_argnames=("window_pages",)).lower(
            params, eng._pools, eng._pages.rows(), eng._state.lengths,
            jnp.zeros((s,), jnp.int32), jnp.ones((s,), bool),
            jnp.zeros((s,), jnp.float32), jnp.ones((s,), jnp.float32),
            jax.random.PRNGKey(0), window_pages=2).as_text()
    finally:
        eng.shutdown()


@pytest.mark.parametrize("name", sorted(PARENT_DECODE_TEXT))
def test_dense_families_lower_the_decode_program_they_had(name):
    text = _decode_text(name)
    assert hashlib.sha256(text.encode()).hexdigest() == \
        PARENT_DECODE_TEXT[name]


def test_mixtral_lowers_the_decode_program_it_had_plus_one_counter():
    """The sixth counter (`moe_routed_assignments` = rows x k) is one
    multiply by a constant and one more operand of the counters' stack
    in each of the two expert layers; nothing else of the program
    moved (1 209 operations on the parent commit), until PR 40 packed
    the pool rows of its 16-wide heads: seven operations a layer more
    (two constants and a pad for K and for V, the reshapes), and PR 49
    put the sampler's draw under its two `cond`s: 13 more, two
    constants and two broadcasts among them."""
    ops = collections.Counter(re.findall(r"(?:stablehlo|chlo)\.\w+",
                                         _decode_text("mixtral-debug")))
    assert sum(ops.values()) == 1209 + 6 + 14 + 13
    assert (ops["stablehlo.multiply"], ops["stablehlo.constant"],
            ops["stablehlo.broadcast_in_dim"]) == (43, 215 + 4 + 2, 331 + 2)


def test_get_model_builds_the_published_and_the_debug_shape():
    big = get_model("sarvam-105b").cfg
    assert (big.d_model, big.n_layers, big.n_heads, big.latent_width,
            big.cache_width, big.experts_held) == (4096, 32, 64, 576, 640,
                                                   128)
    assert big.softmax_scale == pytest.approx(0.13523, abs=5e-6)
    assert kv_cache_spec(get_model("latent-moe-debug")) == [
        (PagedLatent, ((128,),), (jnp.bfloat16,), False)] * 3
    with pytest.raises(ValueError, match="are not among"):
        LatentMoEConfig.debug(expert_first=6, expert_count=4)
