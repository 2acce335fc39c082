"""Pallas paged-decode-attention kernel: parity with the XLA gather
path (interpret mode on CPU; tests_tpu re-runs the engine on-chip).

The kernel (ops/pallas/paged_attention.py) copies a row's live pages
out of the pool block by block (page table in SMEM) — these tests pin
numerical parity against paged_cached_attention's gather path and
against `_attend_cached` over the gathered pages: both head layouts of
the serve cells, empty rows, lengths and windows that are no multiple
of a block, replay positions, scrambled tables with trash entries, the
block chooser, and the engine end-to-end with the kernel forced on.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import (PagedKV, _attend_cached,
                                   paged_cached_attention)
from ray_tpu.ops.pallas.paged_attention import (VMEM_BUDGET_BYTES,
                                                choose_pages_per_block,
                                                paged_decode_attention,
                                                vmem_bytes)


def _build_pool(rng, S, P, ps, hkv, d, lengths):
    n_pages = S * P
    k_flat = jnp.zeros(((n_pages + 1) * ps, hkv, d), jnp.float32)
    v_flat = jnp.zeros(((n_pages + 1) * ps, hkv, d), jnp.float32)
    perm = rng.permutation(n_pages)       # scrambled physical pages
    table = perm.reshape(S, P).astype(np.int32)
    for s in range(S):
        for pos in range(lengths[s]):
            fr = table[s, pos // ps] * ps + pos % ps
            k_flat = k_flat.at[fr].set(rng.randn(hkv, d))
            v_flat = v_flat.at[fr].set(rng.randn(hkv, d))
    return k_flat, v_flat, jnp.asarray(table)


def gather_reference(q, k_flat, v_flat, table, lengths, ps,
                     monkeypatch):
    """Reference output via the XLA gather path: replay the last
    token's kv through the public op at positions = lengths-1 (the
    engine's decode shape). Shared by the CPU and on-chip suites —
    the flat-row formula comes from PagedKV.flat_rows, not a copy."""
    monkeypatch.setenv("RAY_TPU_PAGED_ATTN_IMPL", "gather")
    try:
        cache = PagedKV(k_flat, v_flat, table, lengths - 1, ps)
        rows = cache.flat_rows((lengths - 1)[:, None])[:, 0]
        ref, _ = jax.jit(paged_cached_attention)(
            q[:, None], k_flat[rows][:, None], v_flat[rows][:, None],
            cache, (lengths - 1)[:, None])
    finally:
        monkeypatch.delenv("RAY_TPU_PAGED_ATTN_IMPL")
    return ref[:, 0]


@pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2)])
def test_kernel_matches_gather_path(hq, hkv, monkeypatch):
    S, P, ps, d = 3, 4, 8, 16
    rng = np.random.RandomState(0)
    lengths = np.asarray([5, 1, 29], np.int32)  # incl. multi-page
    k_flat, v_flat, table = _build_pool(rng, S, P, ps, hkv, d, lengths)
    q = jnp.asarray(rng.randn(S, hq, d), jnp.float32)
    new_lengths = jnp.asarray(lengths)

    out = jax.jit(lambda *a: paged_decode_attention(
        *a, page_size=ps, interpret=True))(
        q, k_flat, v_flat, table, new_lengths)

    ref = gather_reference(q, k_flat, v_flat, table, new_lengths, ps,
                           monkeypatch)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_kernel_replay_at_earlier_position_is_causal():
    """A replay query at position < lengths-1 (speculative-decode
    verification shape) must not see future keys: qpos bounds the
    attention window exactly like the gather path's causal mask."""
    S, P, ps, hq, hkv, d = 2, 3, 8, 4, 2, 16
    rng = np.random.RandomState(1)
    lengths = np.asarray([20, 11], np.int32)
    k_flat, v_flat, table = _build_pool(rng, S, P, ps, hkv, d, lengths)
    q = jnp.asarray(rng.randn(S, hq, d), jnp.float32)
    qpos = jnp.asarray([7, 3], jnp.int32)   # mid-sequence replays

    out = paged_decode_attention(
        q, k_flat, v_flat, table, jnp.asarray(lengths),
        page_size=ps, qpos=qpos, interpret=True)
    # truncating each sequence to qpos+1 must give identical output
    trunc = paged_decode_attention(
        q, k_flat, v_flat, table, qpos + 1,
        page_size=ps, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(trunc),
                               rtol=2e-5, atol=2e-5)


# name: (S, P, page, Hq, Hkv, D, lengths, qpos, pages a block); a
# length of 0 is an empty slot, and the last row of "empty_rows" is the
# scratch row (nothing but trash in its table); qpos None = lengths - 1
KERNEL_CASES = {
    "empty_rows": (4, 4, 8, 8, 2, 16, [0, 19, 0, 0], None, 2),
    "ragged_len_block_3": (3, 8, 8, 4, 2, 16, [61, 9, 33], None, 3),
    "window_no_multiple_of_block": (2, 5, 8, 4, 4, 16, [39, 40], None, 2),
    "replay_qpos_before_end": (3, 4, 8, 8, 4, 16, [30, 17, 8],
                               [11, 16, 0], 2),
    "rep1_16_kv_heads": (3, 4, 16, 16, 16, 128, [64, 3, 50], None, None),
    "rep4_8_kv_heads": (3, 4, 16, 32, 8, 128, [17, 64, 1], None, None),
    "page_16": (2, 6, 16, 8, 8, 32, [95, 16], None, 4),
    "page_64": (2, 3, 64, 8, 8, 32, [130, 65], None, None),
    "one_page_a_block": (2, 4, 8, 4, 2, 16, [32, 25], None, 1),
}


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_kernel_cases_match_attend_cached(case):
    """Every pool row is random (dead pages, the trash page and the
    tails of partial pages hold finite garbage), the table is shuffled
    with trash entries behind a row's own pages; against
    `_attend_cached` over the gathered pages at the parent's
    tolerance. A row with no key gives zeros."""
    S, P, ps, hq, hkv, d, lengths, qpos, n_blk = KERNEL_CASES[case]
    rng = np.random.RandomState(sorted(KERNEL_CASES).index(case))
    n_pages = S * P + 1
    trash = n_pages - 1
    k_flat = jnp.asarray(rng.randn(n_pages * ps, hkv, d), jnp.float32)
    v_flat = jnp.asarray(rng.randn(n_pages * ps, hkv, d), jnp.float32)
    lengths = np.asarray(lengths, np.int32)
    qpos = lengths - 1 if qpos is None else np.asarray(qpos, np.int32)
    table = np.full((S, P), trash, np.int32)
    perm = rng.permutation(n_pages - 1)
    for s in range(S):
        held = -(-lengths[s] // ps)
        table[s, :held] = perm[s * P:s * P + held]
    q = jnp.asarray(rng.randn(S, hq, d), jnp.float32)

    out = jax.jit(lambda *a: paged_decode_attention(
        *a, page_size=ps, qpos=jnp.asarray(qpos), interpret=True,
        pages_per_block=n_blk))(
        q, k_flat, v_flat, jnp.asarray(table), jnp.asarray(lengths))

    idx = (table[:, :, None] * ps + np.arange(ps)[None, None, :]).reshape(
        S, P * ps)
    ref = _attend_cached(q[:, None], k_flat[idx], v_flat[idx],
                         jnp.asarray(qpos)[:, None], jnp.asarray(lengths),
                         d ** -0.5)[:, 0]
    live = np.minimum(lengths, qpos + 1) > 0
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(ref)[live],
                               rtol=2e-5, atol=2e-5)
    assert not np.asarray(out)[~live].any()


# (window pages, page, Hq, Hkv, D, dtype) -> pages a block
CHOSEN = {
    "mistral_w16": ((16, 64, 32, 8, 128, jnp.bfloat16), 8),
    "mistral_full": ((130, 64, 32, 8, 128, jnp.bfloat16), 8),
    "olmoe_w16": ((16, 64, 16, 16, 128, jnp.bfloat16), 4),
    "page_16": ((128, 16, 32, 8, 128, jnp.bfloat16), 32),
    "window_2": ((2, 64, 32, 8, 128, jnp.bfloat16), 2),
    "window_1": ((1, 64, 32, 8, 128, jnp.bfloat16), 1),
    "float32_many_heads": ((64, 64, 64, 64, 128, jnp.float32), 1),
}


@pytest.mark.parametrize("name", sorted(CHOSEN))
def test_choose_pages_per_block(name):
    """A power of two within the window whose VMEM plan fits (or one
    page, the least there is); the two serve cells get the blocks the
    microbenchmark found within 2 % of the best (PERF.md, PR 29)."""
    (w, ps, hq, hkv, d, dtype), want = CHOSEN[name]
    n = choose_pages_per_block(w, ps, hq, hkv, d, dtype)
    assert n == want
    assert 1 <= n <= w and n & (n - 1) == 0
    assert n == 1 or vmem_bytes(n, ps, hq, hkv, d, jnp.dtype(
        dtype).itemsize) <= VMEM_BUDGET_BYTES


@pytest.mark.slow
def test_engine_tokens_identical_with_kernel_forced(monkeypatch):
    """Greedy generation with the kernel forced on (interpret mode)
    matches the gather path token-for-token through the real engine."""
    from ray_tpu.models import Llama, LlamaConfig
    from ray_tpu.serve.llm import LLMEngine, LLMEngineConfig
    cfg = LlamaConfig(vocab_size=128, d_model=32, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_ff=64, max_seq_len=64, remat=False,
                      dtype=jnp.float32)
    model = Llama(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    prompts = [np.arange(2, 8) % 128, np.arange(3, 20) % 128]

    def run(impl):
        monkeypatch.setenv("RAY_TPU_PAGED_ATTN_IMPL", impl)
        eng = LLMEngine(model, params, LLMEngineConfig(
            max_slots=2, max_seq_len=64, prefill_buckets=(8, 32),
            kv_page_size=8, max_prefill_batch=1))
        try:
            return [eng.generate_sync(p, max_new_tokens=6)
                    for p in prompts]
        finally:
            eng.shutdown()

    assert run("pallas") == run("gather")
