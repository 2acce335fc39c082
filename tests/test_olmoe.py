"""OLMoE on the normal path, at small widths on the CPU with seeded
random weights: the system (models/mixtral.py, ops/moe.py, the engine's
paged cache) against the benchmark's float32 reference
(benchmarks/harness/reference_olmoe.py, which imports nothing of the
system), logits and not tokens; dropless dispatch; both routing
conventions; padding rows; the counters."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.harness import reference_olmoe, replica_olmoe
from ray_tpu.models import Mixtral, MixtralConfig, get_model
from ray_tpu.ops.moe import (moe_dispatch_combine, moe_dropless, route)

SIZES = {"top2of8": (8, 2), "top8of16": (16, 8)}


def _file_cfg(n_experts, k):
    """A configuration file's keys at toy widths."""
    return {"hidden_size": 64, "num_hidden_layers": 2,
            "num_attention_heads": 4, "num_key_value_heads": 4,
            "head_dim": 16, "intermediate_size": 32, "vocab_size": 512,
            "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
            "max_position_embeddings": 256, "tie_word_embeddings": False,
            "num_experts": n_experts, "num_experts_per_tok": k,
            "norm_topk_prob": False}


def _build(size, dtype, seed=3):
    cfg = _file_cfg(*SIZES[size])
    model = Mixtral(replica_olmoe.mixtral_config(
        cfg, param_dtype=dtype, dtype=dtype))
    params = model.init(jax.random.PRNGKey(seed),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, model, params


def _forward_with_choices(model, params, tokens):
    (logits, _), sown = model.apply({"params": params}, tokens[None],
                                    mutable=["routing"])
    n = model.cfg.n_layers
    return (np.asarray(logits[0], np.float32),
            [sown["routing"][f"layer_{i}"]["moe"]["top_idx"][0][0]
             for i in range(n)])


def test_the_preset_holds_the_published_values():
    cfg = get_model("olmoe-1b-7b").cfg
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.d_ff) == (2048, 16, 16, 16, 128, 1024)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.vocab_size,
            cfg.max_seq_len) == (64, 8, 50304, 4096)
    assert (cfg.routing, cfg.norm_topk_prob, cfg.qk_norm,
            cfg.capacity_factor) == ("softmax_topk", False, True, None)
    assert get_model("olmoe-1b-7b", param_dtype=jnp.bfloat16,
                     n_layers=8).cfg.n_layers == 8


# (a) float32 system against the float32 reference, full forward. Both
# compute the same function in the same precision; what is left is the
# order of float32 sums (sorted grouped matmul against a dense masked
# sum, fused norms), a few 1e-6 on logits of order 1: 1e-4 is far under
# anything a dropped assignment, a wrong weight or a missing norm gives
# (each is > 1e-2 here, see the cases below).
@pytest.mark.parametrize("size", list(SIZES))
def test_float32_forward_matches_the_reference(size):
    cfg, model, params = _build(size, jnp.float32)
    tokens = jnp.asarray(np.random.default_rng(0).integers(1, 512, 48))
    got, chose = _forward_with_choices(model, params, tokens)
    m = replica_olmoe.model_section(cfg)
    ref, rec = reference_olmoe.forward(params, tokens, m)
    assert np.abs(got - np.asarray(ref)).max() < 1e-4
    for layer, r in zip(chose, rec):       # the same experts, everywhere
        picked = np.zeros((48, cfg["num_experts"]), bool)
        picked[np.arange(48)[:, None], np.asarray(layer)] = True
        assert (picked == np.asarray(r["chosen"])).all()
    # what the tolerance is there to catch
    for breakage in ({"norm_topk_prob": True},):
        worse, _ = reference_olmoe.forward(params, tokens,
                                           dict(m, **breakage))
        assert np.abs(got - np.asarray(worse)).max() > 1e-2


# (b) bf16 system against the float32 reference with the near-tie
# treatment of the chip check: where the system chose other experts and
# every swapped expert is within tie_margin of the reference's own k-th
# probability, the reference follows; nothing may be outside the margin.
@pytest.mark.parametrize("size", list(SIZES))
def test_bf16_forward_matches_the_reference_up_to_near_ties(size):
    cfg, model, params = _build(size, jnp.bfloat16)
    tokens = jnp.asarray(np.random.default_rng(1).integers(1, 512, 96))
    got, chose = _forward_with_choices(model, params, tokens)
    m = replica_olmoe.model_section(cfg)
    ref, rec = reference_olmoe.forward(params, tokens, m, follow=chose,
                                       tie_margin=0.1)
    ref = np.asarray(ref)
    assert not any(np.asarray(r["not_followed"]).any() for r in rec)
    # bf16 activations on a 64-wide toy: a few percent of the logits'
    # standard deviation
    assert np.abs(got - ref).max() / ref.std() < 0.1


# (c) prefill into pages, then decode through LLMEngine's paged cache,
# against the reference's full forward: logits, not tokens.
@pytest.fixture(scope="module")
def engine():
    from ray_tpu.serve.llm.engine import LLMEngine, LLMEngineConfig
    cfg, model, params = _build("top8of16", jnp.float32)
    eng = LLMEngine(model, params, LLMEngineConfig(
        max_slots=4, max_seq_len=128, kv_page_size=16, kv_pool_tokens=512,
        prefill_buckets=(32, 64), max_prefill_batch=4, eos_token_id=None))
    yield cfg, eng
    eng.shutdown()


def test_engine_paged_cache_matches_the_reference(engine):
    from benchmarks.harness import modelcfg
    cfg, eng = engine
    prompt = np.random.default_rng(2).integers(1, 512, 40).tolist()
    gen = eng.generate_sync(prompt, max_new_tokens=6)
    assert len(gen) == 6
    out = replica_olmoe.serve_check(eng, {
        "model": modelcfg.model_section(cfg), "prompt": prompt,
        "generated": gen,
        "check": {"logit_tol_rel": 1e-3, "argmax_tol_rel": 1e-3,
                  "tie_margin_rel": 1e-3}})
    assert out["ok"], out
    assert out["positions"] == 45 and out["not_followed"] == 0
    assert out["same_experts_pair_share"] == 1.0


# (d) every row chooses the same experts: the dropless path loses
# nothing; the capacity path, kept for ep-sharded training, does.
@pytest.mark.parametrize("path", ["dropless", "capacity"])
def test_all_rows_on_the_same_experts(path):
    rng = np.random.default_rng(0)
    g, d, f, e, k = 32, 16, 8, 8, 2
    x = jnp.asarray(rng.standard_normal((g, d)), jnp.float32)
    wg, wu = (jnp.asarray(rng.standard_normal((e, d, f)), jnp.float32)
              for _ in range(2))
    wd = jnp.asarray(rng.standard_normal((e, f, d)), jnp.float32)
    logits = jnp.tile(jnp.asarray([[0., 3., 0., 0., 2., 0., 0., 0.]]),
                      (g, 1))                  # everyone picks 1 and 4
    weights, idx = route(logits, k, "softmax_topk")
    want = sum(weights[:, j:j + 1] * (
        (jax.nn.silu(x @ wg[int(idx[0, j])]) * (x @ wu[int(idx[0, j])]))
        @ wd[int(idx[0, j])]) for j in range(k))
    if path == "dropless":
        got, stats = moe_dropless(x, weights, idx, wg, wu, wd)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        assert stats.tolist() == [g * k, g, 0, g, 2, g * k]
    else:
        def fn(b):
            return jnp.einsum("ecf,efd->ecd", jax.nn.silu(
                jnp.einsum("ecd,edf->ecf", b, wg)) * jnp.einsum(
                    "ecd,edf->ecf", b, wu), wd)
        got, _ = moe_dispatch_combine(x, logits, fn, k=k,
                                      capacity_factor=1.25,
                                      routing="softmax_topk")
        cap = int(g * k * 1.25 / e)                      # 10 rows an expert
        kept = np.abs(np.asarray(got)).sum(-1) > 0
        assert kept[:cap].all() and not kept[cap:].any()
        np.testing.assert_allclose(got[:cap], want[:cap], rtol=1e-4,
                                   atol=1e-4)


# (e) the routing conventions against weights computed by hand
@pytest.mark.parametrize("routing,norm,want", [
    ("topk_softmax", False, [0.7310586, 0.2689414]),    # softmax([2, 1])
    ("softmax_topk", False, [0.6439143, 0.2368828]),    # of softmax(all 4)
    ("softmax_topk", True, [0.7310586, 0.2689414]),
])
def test_routing_conventions(routing, norm, want):
    weights, idx = route(jnp.asarray([[1.0, 2.0, 0.0, -1.0]]), 2, routing,
                         norm)
    assert idx.tolist() == [[1, 0]]
    np.testing.assert_allclose(weights[0], want, rtol=1e-6)
    assert weights.dtype == jnp.float32


def test_unknown_routing_is_refused():
    with pytest.raises(ValueError):
        MixtralConfig.debug(routing="best_effort")


# (f) a real row's logits do not depend on what the other rows of the
# step hold: another prompt beside it, other tokens in its own bucket
# padding, with the rows marked as padding or not.
def test_real_rows_do_not_depend_on_other_rows():
    _cfg, model, params = _build("top8of16", jnp.float32)
    rng = np.random.default_rng(5)
    row = rng.integers(1, 512, 24)

    def logits_of(other_row, tail, mark):
        tokens = np.zeros((2, 32), np.int64)
        tokens[0, :24], tokens[0, 24:] = row, tail
        tokens[1] = other_row
        mask = np.zeros((2, 32), bool)
        mask[0, :24] = True
        out, _ = model.apply({"params": params}, jnp.asarray(tokens),
                             row_mask=jnp.asarray(mask) if mark else None)
        return np.asarray(out[0, :24])

    base = logits_of(np.zeros(32), np.zeros(8), True)
    for other, tail, mark in ((rng.integers(1, 512, 32), np.zeros(8), True),
                              (np.full(32, 7), rng.integers(1, 512, 8),
                               True),
                              (rng.integers(1, 512, 32),
                               rng.integers(1, 512, 8), False)):
        np.testing.assert_array_equal(base, logits_of(other, tail, mark))


# (g) the counters, through the engine
def test_engine_counts_what_the_expert_layers_did(engine):
    cfg, eng = engine
    s0 = eng.get_stats()
    prompts = [np.random.default_rng(i).integers(1, 512, n).tolist()
               for i, n in enumerate((20, 33, 50))]
    for pr in prompts:
        eng.generate_sync(pr, max_new_tokens=5)
    s1 = eng.get_stats()
    d = {k: s1[k] - s0[k] for k in s1 if k.startswith("moe_")
         or k in ("decode_steps", "prefill_tokens_padded",
                  "prefill_tokens_real", "decode_tokens_emitted")}
    layers, k = cfg["num_hidden_layers"], cfg["num_experts_per_tok"]
    assert d["moe_assignments"] == d["moe_rows"] * k
    # every prompt token and every emitted decode row went through every
    # layer; steps that ran ahead of a finished request ran no real row
    assert d["moe_rows"] >= layers * (d["prefill_tokens_real"]
                                      + d["decode_tokens_emitted"])
    assert d["moe_rows"] + d["moe_pad_rows"] == layers * (
        d["prefill_tokens_padded"] + d["decode_steps"] * eng._n_slots)
    assert d["moe_pad_rows"] > 0
    assert d["moe_expert_load_max"] * cfg["num_experts"] \
        >= d["moe_assignments"]                        # max >= mean
    assert 0 < d["moe_experts_touched"] <= cfg["num_experts"] * layers * (
        d["decode_steps"] + 3)
