"""The float32 reference against the engine at a tiny size on the CPU:
greedy tokens through the real engine, then the check the chip runs."""
import numpy as np
import pytest


@pytest.fixture(scope="module")
def tiny():
    import jax
    import jax.numpy as jnp
    from benchmarks.harness import modelcfg
    from ray_tpu.models import Llama
    from ray_tpu.serve.llm.engine import LLMEngine, LLMEngineConfig
    cfg = {"hidden_size": 64, "num_hidden_layers": 2,
           "num_attention_heads": 4, "num_key_value_heads": 2,
           "head_dim": 16, "intermediate_size": 128, "vocab_size": 512,
           "rope_theta": 1e6, "rms_norm_eps": 1e-5,
           "max_position_embeddings": 256, "tie_word_embeddings": False}
    model = Llama(modelcfg.llama_config(cfg, param_dtype=jnp.float32))
    params = model.init(jax.random.PRNGKey(3),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    eng = LLMEngine(model, params, LLMEngineConfig(
        max_slots=4, max_seq_len=128, kv_page_size=16, kv_pool_tokens=512,
        prefill_buckets=(32, 64), eos_token_id=None))
    yield cfg, model, params, eng
    eng.shutdown()


def test_engine_agrees_with_the_reference(tiny):
    from benchmarks.harness import checks, modelcfg
    cfg, _model, _params, eng = tiny
    prompt = np.random.default_rng(0).integers(1, 512, 40).tolist()
    gen = eng.generate_sync(prompt, max_new_tokens=6)
    assert len(gen) == 6
    out = checks.serve_check(eng, {
        "model": modelcfg.model_section(cfg), "prompt": prompt,
        "generated": gen,
        "check": {"logit_tol_rel": 0.1, "argmax_tol_rel": 0.1}})
    assert out["ok"], out
    # a wrong token would sit far below the reference's largest logit
    bad = list(gen)
    bad[2] = (bad[2] + 1) % 512
    worse = checks.serve_check(eng, {
        "model": modelcfg.model_section(cfg), "prompt": prompt,
        "generated": bad,
        "check": {"logit_tol_rel": 0.1, "argmax_tol_rel": 0.1}})
    assert worse["argmax_gap_rel"] > out["argmax_gap_rel"]


def test_reference_loss_matches_the_trainers_loss(tiny):
    import jax.numpy as jnp
    from benchmarks.harness import modelcfg, reference
    from ray_tpu.train.spmd import next_token_loss
    cfg, model, params, _eng = tiny
    toks = np.random.default_rng(1).integers(0, 512, (1, 65)).astype(np.int32)
    want, _ = next_token_loss(model.apply, params,
                              {"tokens": jnp.asarray(toks)})
    got = reference.sequence_loss(params, jnp.asarray(toks[0]),
                                  modelcfg.model_section(cfg))
    assert abs(float(want) - float(got)) < 0.02
