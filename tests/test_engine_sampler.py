"""The engine's sampler against the one-path sampler it replaced.

`LLMEngine._sample_tokens` makes only the passes over the (N, V) logits
that the rows of a call ask for: the arg-max always, the draw under a
`cond` on "some temperature is above 0", the nucleus sort under a second
one on "some top_p is below 1". The function it replaced made the divide,
the noise and a second arg-max for every row of every call and dropped
them for a greedy row. It is kept here as the plain reference: for the
same key every row's token, and its log-probability where the engine
reports them, has to come out the same, bit for bit, in every mix of rows.
"""
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.serve.llm import LLMEngine

V = 384


def _adjusted(cfg, logits, allow=None, bias=None):
    """The logits as both samplers see them after bias, mask and top-k:
    the front of `_sample_tokens`, which PR 49 left where it was."""
    if bias is not None:
        logits = logits + bias.astype(logits.dtype)
    if allow is not None:
        logits = jnp.where(allow, logits, -jnp.inf)
    if cfg.top_k and cfg.top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -cfg.top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    return logits


def reference_sample_tokens(cfg, logits, temps, top_ps, rng_key, allow=None,
                            bias=None):
    """`_sample_tokens` as it stood before the branches: one path, every
    pass made for every row."""
    raw_logp = (jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
                if cfg.logprobs else None)
    logits = _adjusted(cfg, logits, allow, bias)
    greedy = jnp.argmax(logits, axis=-1)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]

    def nucleus(scaled):
        n, _v = scaled.shape
        sort_idx = jnp.argsort(-scaled, axis=-1)
        sorted_probs = jax.nn.softmax(
            jnp.take_along_axis(scaled, sort_idx, axis=-1), axis=-1)
        cum = jnp.cumsum(sorted_probs, axis=-1)
        keep_sorted = (cum - sorted_probs) < top_ps[:, None]
        keep = jnp.zeros_like(keep_sorted).at[
            jnp.arange(n)[:, None], sort_idx].set(keep_sorted)
        use_top_p = (top_ps < 1.0)[:, None]
        return jnp.where(use_top_p & ~keep, -jnp.inf, scaled)

    scaled = jax.lax.cond(jnp.any(top_ps < 1.0), nucleus,
                          lambda s: s, scaled)
    sampled = jax.random.categorical(rng_key, scaled, axis=-1)
    toks = jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)
    if raw_logp is None:
        logps = jnp.zeros(toks.shape, jnp.float32)
    else:
        logps = jnp.take_along_axis(raw_logp, toks[:, None], axis=-1)[:, 0]
    return toks, logps


# name -> (rows, temperatures, top_ps, cfg.top_k, allow, bias, cfg.logprobs);
# a temperature / top_p list is cycled over the rows
CASES = {
    "all_greedy": (9, [0.0], [1.0], 0, False, False, False),
    "all_sampled": (9, [0.7, 1.0, 1.3], [1.0], 0, False, False, False),
    "mixed": (9, [0.0, 0.8, 0.0, 1.2], [1.0], 0, False, False, False),
    "some_top_p": (9, [0.9, 0.0, 0.6], [1.0, 0.5, 0.9, 0.2], 0, False,
                   False, False),
    "greedy_rows_with_top_p": (9, [0.0], [0.9], 0, False, False, False),
    "top_k": (9, [0.0, 1.0, 0.7], [1.0], 8, False, False, False),
    "top_k_and_top_p": (9, [1.0, 0.0], [0.8, 1.0, 1.0], 8, False, False,
                        False),
    "allow": (9, [0.0, 0.9, 1.1], [1.0, 1.0, 0.7], 0, True, False, False),
    "bias": (9, [0.0, 0.9, 1.1], [1.0], 0, False, True, False),
    "allow_and_bias": (9, [1.0, 0.0], [0.6, 1.0], 0, True, True, False),
    "one_row_greedy": (1, [0.0], [1.0], 0, False, False, False),
    "one_row_sampled": (1, [0.8], [1.0], 0, False, False, False),
    "one_row_top_p": (1, [0.8], [0.4], 0, False, False, False),
    "logprobs_greedy": (9, [0.0], [1.0], 0, False, False, True),
    "logprobs_mixed": (9, [0.0, 0.8, 1.2], [1.0, 0.5], 0, False, True,
                       True),
}


def _cycled(values, n):
    return np.resize(np.asarray(values, np.float32), n)


@pytest.mark.parametrize("case", sorted(CASES))
def test_sampler_draws_what_the_one_path_sampler_drew(case):
    n, temps, top_ps, top_k, with_allow, with_bias, logprobs = CASES[case]
    cfg = types.SimpleNamespace(top_k=top_k, logprobs=logprobs)
    stub = types.SimpleNamespace(_jnp=jnp, _jax=jax, cfg=cfg)
    rng = np.random.default_rng(sum(map(ord, case)))
    # a peaked distribution: a sampled row departs from its arg-max
    # often, and not always
    logits = jnp.asarray(rng.standard_normal((n, V)) * 3.0, jnp.float32)
    temps, top_ps = _cycled(temps, n), _cycled(top_ps, n)
    kw = {}
    if with_allow:
        allow = rng.random((n, V)) < 0.3
        allow[:, 0] = True                  # never an empty row
        kw["allow"] = jnp.asarray(allow)
    if with_bias:
        kw["bias"] = jnp.asarray(rng.standard_normal((n, V)), jnp.float32)
    new = jax.jit(lambda *a, **k: LLMEngine._sample_tokens(stub, *a, **k))
    old = jax.jit(lambda *a, **k: reference_sample_tokens(cfg, *a, **k))
    top = np.asarray(jnp.argmax(_adjusted(cfg, logits, **kw), -1))
    departed = 0
    for seed in range(6):
        key = jax.random.PRNGKey(seed)
        toks, logps = new(logits, temps, top_ps, key, **kw)
        want, want_lp = map(np.asarray, old(logits, temps, top_ps, key, **kw))
        assert toks.dtype == jnp.int32 and toks.shape == (n,)
        np.testing.assert_array_equal(np.asarray(toks), want)
        np.testing.assert_array_equal(np.asarray(logps), want_lp)
        if logprobs:
            assert np.all(want_lp < 0)
        # a greedy row takes no notice of the key
        np.testing.assert_array_equal(want[temps == 0], top[temps == 0])
        departed += int((want != top).sum())
    if (temps > 0).any():
        assert departed > 0, "no sampled row ever left its arg-max"
