"""Attention on TPU.

Default path: `jax.nn.dot_product_attention`, which XLA lowers to an MXU-
friendly fused kernel (and to TPU flash attention where supported). A Pallas
flash-attention kernel (ray_tpu/ops/pallas/flash_attention.py) can be
selected with impl="pallas" for long sequences.

Replaces the reference's torch scaled_dot_product_attention / flash-attn
dependency in its model code (e.g. rllib models and train examples).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..util import knobs

# What training attention leaves for its backward, by name
# (jax.ad_checkpoint.checkpoint_name): q, k and v as the attention takes
# them, its output, and the flash kernel's row logsumexp. A rematted
# block whose policy is save_only_these_names(...) keeps them and does
# not run the attention (nor the projections and the rotation in front
# of it) a second time; under any other policy, and outside
# jax.checkpoint, a name is an identity that lowers to nothing. The
# flash kernel names its own residuals (pallas/flash_attention.py:
# _flash_vjp_fwd); the XLA and dpa routes name the first four here and
# recompute their softmax.
ATTN_RESIDUALS = ("attn_q", "attn_k", "attn_v", "attn_out", "attn_lse")
_ATTN_OUT = ATTN_RESIDUALS[3]


def causal_attention_mask(seq_len: int, dtype=jnp.bool_) -> jax.Array:
    return jnp.tril(jnp.ones((seq_len, seq_len), dtype=dtype))


def _resolve_impl(impl: str, q: jax.Array, k: jax.Array, causal: bool,
                  segment_ids) -> str:
    """"auto" = the Pallas flash kernel on TPU whenever the shape suits it
    (self-attention, long enough to tile); XLA otherwise — notably cached
    decode (Sq != Sk under causal), segment masking, and CPU, where
    interpret-mode Pallas would crawl. RAY_TPU_ATTN_IMPL overrides the
    auto choice (benchmark A/B knob)."""
    if impl == "auto":
        impl = knobs.get_str("RAY_TPU_ATTN_IMPL")
    if impl != "auto":
        return impl
    if jax.default_backend() != "tpu":
        return "xla"
    if segment_ids is not None:
        return "xla"
    if causal and q.shape[1] != k.shape[1]:
        return "xla"
    # Forward + backward on one v5e, 16 query / 4 KV heads of 128, bf16,
    # causal, device time (tools/flash_microbench.py; PERF.md, PR 27):
    # at 4 x 2 048 xla 16.7 ms, dpa 13.1, pallas 3.75 (19.6 with PR 26's
    # 128 x 128 tiles); at 2 x 4 096 xla 32.3, dpa 25.4, pallas 5.70
    # (37.2). Below 2 048 one point since the kernel was re-tiled, at
    # 2 x 1 000 with 8 heads of 64: xla 0.16 ms, pallas 0.30; the
    # threshold is the old one (ROADMAP A1 (c) sweeps 256-1 024).
    if q.shape[1] < 2048:
        return "xla"
    return "pallas"


def cached_attention(q: jax.Array, k: jax.Array, v: jax.Array, cache,
                     positions: jax.Array,
                     scale: Optional[float] = None,
                     impl: str = "auto"):
    """Decode/continuation attention against a per-sequence KV cache.

    q/k/v: (B, S, H{q,kv}, D) for the NEW tokens; cache = (ck, cv,
    lengths) with ck/cv (B, L, Hkv, D) and lengths (B,). Writes k/v at
    `positions` (B, S), attends causally over the written prefix, and
    returns (out (B, S, Hq, D), new_cache). Shared by every decoder in
    the zoo (llama.py, gpt2.py): the (ck, cv, lengths) entry is the
    model's own cache (Model.empty_cache).

    A PagedKV cache entry — what the serving engine passes — routes to
    paged_cached_attention: same semantics over a shared page pool.
    `impl` (the model's
    cfg.attn_impl) governs the fresh-prefill fast path's attention
    router so a pinned implementation holds on every code path."""
    if isinstance(cache, PagedKV):
        return paged_cached_attention(q, k, v, cache, positions,
                                      scale=scale, impl=impl)
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    if scale is None:
        scale = d ** -0.5
    ck, cv, lengths = cache
    idx = jnp.arange(b)
    ck = ck.at[idx[:, None], positions].set(k.astype(ck.dtype))
    cv = cv.at[idx[:, None], positions].set(v.astype(cv.dtype))
    new_lengths = jnp.maximum(lengths, positions[:, -1] + 1)
    out = _attend_cached(q, ck, cv, positions, new_lengths, scale)
    return out, (ck, cv, new_lengths)


def _attend_cached(q, ck, cv, positions, new_lengths, scale):
    """Shared attention tail of cached_attention and of the page pool's
    gather path: length-valid mask + causal mask + GQA repeat +
    softmax(QK)V. ONE implementation, so the engine's pages can never
    drift numerically from the model's own cache (their
    token-identical contract is tested in tests/test_paged_kv.py)."""
    hq = q.shape[2]
    L = ck.shape[1]
    valid = jnp.arange(L)[None, :] < new_lengths[:, None]
    logits_mask = jnp.where(valid, 0.0, jnp.finfo(jnp.float32).min)
    hkv = ck.shape[2]
    rep = hq // hkv
    kk = jnp.repeat(ck, rep, axis=2) if rep > 1 else ck
    vv = jnp.repeat(cv, rep, axis=2) if rep > 1 else cv
    att = jnp.einsum("bqhd,bkhd->bhqk", q, kk,
                     preferred_element_type=jnp.float32) * scale
    att = att + logits_mask[:, None, None, :]
    pos_k = jnp.arange(L)[None, None, None, :]
    pos_q = positions[:, None, :, None]
    att = jnp.where(pos_k <= pos_q, att, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(att, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, vv)


@jax.tree_util.register_pytree_node_class
class PagedKV:
    """Per-layer paged KV cache entry (vLLM-style, TPU-first).

    k_flat/v_flat: (N_flat, Hkv, D) — the shared page pool, flattened to
      token rows; N_flat = (n_pages [+ trash]) * page_size. Every
      sequence in the batch reads/writes the SAME pool. Heads narrower
      than 128 lanes lie packed, (N_flat, rows, 128): `packed_kv_shape`
      (paged_cached_attention tells the two apart by the last axis).
    page_table: (B, P) int32 — page ids backing each sequence, in order;
      logical position p of row b lives at flat row
      page_table[b, p // page_size] * page_size + p % page_size.
      Unallocated entries point at a trash page: writes there are
      discarded by construction, reads are masked by `lengths`.
    lengths: (B,) int32 — tokens currently valid per sequence.
    page_size and `fresh` are STATIC pytree metadata. fresh=True marks
    a PURE PREFILL call (every sequence starts at length 0): attention
    then runs straight over the new tokens' k/v — no page gather at
    all, and the multi_head_attention router can pick the flash kernel
    for long prompts — while KV still scatters into the pages.
    """

    def __init__(self, k_flat, v_flat, page_table, lengths,
                 page_size: int, fresh: bool = False):
        self.k_flat = k_flat
        self.v_flat = v_flat
        self.page_table = page_table
        self.lengths = lengths
        self.page_size = page_size
        self.fresh = fresh

    def flat_rows(self, positions):
        """Flat pool row index for each (sequence, logical position) in
        `positions` (B, S) — the single definition of the page-indexing
        formula (debug/introspection/tests)."""
        ps = self.page_size
        return (jnp.take_along_axis(self.page_table, positions // ps,
                                    axis=1) * ps + positions % ps)

    @property
    def arrays(self):
        """The pool's arrays of this layer, in the order of the
        constructor's leading arguments (what the engine keeps)."""
        return (self.k_flat, self.v_flat)

    def tree_flatten(self):
        return ((self.k_flat, self.v_flat, self.page_table,
                 self.lengths), (self.page_size, self.fresh))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


@jax.tree_util.register_pytree_node_class
class PagedLatent:
    """Per-layer paged cache entry of a latent-attention layer: ONE
    array `flat` (N_flat, W), a token's compressed key/value (the normed
    latent, then the rotated rope key shared by all heads). Pages, page
    table, lengths, `page_size` and `fresh` are PagedKV's, so the
    engine's allocator and windows do not know the difference."""

    flat_rows = PagedKV.flat_rows

    def __init__(self, flat, page_table, lengths, page_size: int,
                 fresh: bool = False):
        self.flat = flat
        self.page_table = page_table
        self.lengths = lengths
        self.page_size = page_size
        self.fresh = fresh

    @property
    def arrays(self):
        return (self.flat,)

    def write(self, latent: jax.Array, positions: jax.Array
              ) -> "PagedLatent":
        """Scatter the new tokens' latents (B, S, W) into their pages;
        the entry that results is never `fresh`."""
        b, s, w = latent.shape
        flat = self.flat.at[self.flat_rows(positions).reshape(-1)].set(
            latent.astype(self.flat.dtype).reshape(b * s, w))
        return PagedLatent(
            flat, self.page_table,
            jnp.maximum(self.lengths, positions[:, -1] + 1),
            self.page_size)

    def tree_flatten(self):
        return ((self.flat, self.page_table, self.lengths),
                (self.page_size, self.fresh))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, *aux)


@jax.tree_util.register_pytree_node_class
class SlotState:
    """Per-layer cache entry of a layer whose memory is a fixed-size
    recurrent state a sequence (a linear-attention layer) and not
    something a token: its pool arrays are indexed by SLOT,
    `(n_slots, *trailing)`, and know nothing of pages.

    arrays: the layer's pool arrays (for ops/gated_deltanet.py: the
      float32 state and the convolution's last K - 1 inputs, side by
      side in ONE row a slot: `causal_conv`).
    slots: (B,) int32, the pool row of each sequence of the call, or
      None where the call's rows ARE the pool's rows (a decode step
      over every slot).
    n_new: (B,) int32, how many of the call's S new positions of each
      sequence are real: a prompt's true length inside its bucket, a
      chunk's true tokens, 1 or 0 for a decoding or an idle row. The
      layer freezes its state past them.
    restart: (B,) bool or None, the sequences that begin with this
      call (a prompt's first chunk): they start from the zero state,
      whatever the slot's previous occupant left.
    `fresh` is STATIC: every sequence begins with this call and nothing
    is read (a whole prefill).
    """

    def __init__(self, *fields, fresh: bool = False):
        *arrays, slots, n_new, restart = fields
        self.arrays = tuple(arrays)
        self.slots = slots
        self.n_new = n_new
        self.restart = restart
        self.fresh = fresh

    def read(self):
        """The sequences' states as the call starts."""
        if self.fresh:
            return tuple(jnp.zeros((self.n_new.shape[0], *a.shape[1:]),
                                   a.dtype) for a in self.arrays)
        rows = self.arrays if self.slots is None else tuple(
            a[self.slots] for a in self.arrays)
        if self.restart is None:
            return rows
        return tuple(jnp.where(
            self.restart.reshape(-1, *(1,) * (r.ndim - 1)), 0, r)
            for r in rows)

    def write(self, *new) -> "SlotState":
        """The entry after the call: each sequence's arrays put back in
        its slot (padding rows of a group all aim at the scratch slot;
        which of them lands there does not matter)."""
        if self.slots is None:
            arrays = tuple(n.astype(a.dtype)
                           for a, n in zip(self.arrays, new))
        else:
            arrays = tuple(a.at[self.slots].set(n.astype(a.dtype))
                           for a, n in zip(self.arrays, new))
        return SlotState(*arrays, self.slots, self.n_new, None)

    def tree_flatten(self):
        return ((*self.arrays, self.slots, self.n_new, self.restart),
                (self.fresh,))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children, fresh=aux[0])


class LayerCache(NamedTuple):
    """What ONE layer caches: its entry class, the trailing shapes and
    dtypes of its pool arrays, and what indexes the pool: a token
    (through the page table: `(n_flat, *shape)`) or, `by_slot`, a
    sequence's slot (`(n_slots, *shape)`)."""
    entry: type
    shapes: tuple
    dtypes: tuple
    by_slot: bool = False


def packed_kv_shape(n_kv_heads: int, head_dim: int) -> "tuple[int, int]":
    """A token's K (or V) as a page pool holds it. Heads of 128 lanes or
    more lie a head a row, `(n_kv_heads, head_dim)`. Narrower heads
    (`head_dim` dividing 128) are PACKED: 128 // head_dim of them side
    by side in one 128-lane row, in head order, the last row filled up
    with zero heads: `(ceil(n_kv_heads / pack), 128)`. On the chip a
    row of fewer than 128 lanes is padded to them in HBM and the
    decode kernel cannot copy out of it, so an unpacked pool of narrow
    heads costs its padding and a padded copy of itself a call; a
    packed pool is held as it is counted and the kernel's view of it is
    the pool (ops/pallas/paged_attention.py)."""
    pack = 128 // head_dim if head_dim < 128 and 128 % head_dim == 0 else 1
    return (-(-n_kv_heads // pack), pack * head_dim)


def kv_cache_spec(model) -> "list[LayerCache]":
    """What a model caches, one `LayerCache` a layer. A model says so
    itself (`paged_cache_spec()`); every other decoder of the zoo caches
    K and V of `packed_kv_shape(n_kv_heads, head_dim)` a token a layer.
    Three kinds so far: `PagedKV` and `PagedLatent`, indexed by token
    through the page table, and `SlotState`, a fixed-size state a
    sequence, indexed by slot (models/hybrid.py: a linear-attention
    layer's recurrent state and convolution tail, a short-convolution
    layer's last inputs). The serving engine builds its pools from this
    list; a model with a `by_slot` layer is refused prefix caching and
    speculation there by name (a prefix would be a state snapshot, a
    rejected proposal a rollback), and `get_stats()` reports
    `state_bytes_per_slot`, `decode_state_rows_window` and
    `decode_state_rows_live` for it."""
    own = getattr(model, "paged_cache_spec", None)
    if own is not None:
        return own()
    c = model.cfg
    kv = packed_kv_shape(c.n_kv_heads, c.head_dim)
    return [LayerCache(PagedKV, (kv, kv), (c.dtype, c.dtype))] * c.n_layers


def uneven_head_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          scale: float, impl: str = "auto") -> jax.Array:
    """Causal self-attention whose value heads are narrower than its
    query/key heads (a latent-attention layer's expanded form: q, k
    (B, S, H, dq), v (B, S, H, dv)); returns (B, S, H, dv). The einsum
    route takes the widths as they are; a kernel route (flash, dpa)
    wants one width, so q, k and v are padded with zeros to dq rounded
    up to the 128 lanes (the scores and the first dv columns of the
    result are unchanged) and the result is cut back."""
    if _resolve_impl(impl, q, k, True, None) == "xla":
        return multi_head_attention(q, k, v, causal=True, impl="xla",
                                    scale=scale)
    d = -(-q.shape[-1] // 128) * 128

    def pad(x):
        return jnp.pad(x, ((0, 0),) * 3 + ((0, d - x.shape[-1]),))
    return multi_head_attention(pad(q), pad(k), pad(v), causal=True,
                                impl=impl, scale=scale)[..., :v.shape[-1]]


def latent_cached_attention(q: jax.Array, latent: jax.Array,
                            cache: PagedLatent, positions: jax.Array,
                            scale: float, d_v: int):
    """Absorbed-form latent attention against the page pool: multi-query
    attention of q (B, S, H, W) over one W-wide key a token whose first
    `d_v` columns are also the value. Writes the new tokens' `latent`
    (B, S, W) at `positions` (B, S) first, attends causally over the
    written prefix and returns (out (B, S, H, d_v), new entry).

    Single-token steps on the TPU (or under RAY_TPU_PAGED_ATTN_IMPL=
    pallas) run the Pallas kernel over the pool's live pages
    (ops/pallas/latent_attention.py); everything else gathers the
    sequence's pages and goes through `_attend_cached`, the same tail
    PagedKV's gather route uses."""
    b, s, _h, _w = q.shape
    new = cache.write(latent, positions)
    impl = knobs.get_str("RAY_TPU_PAGED_ATTN_IMPL")
    if s == 1 and impl != "gather" and (
            impl == "pallas" or jax.default_backend() == "tpu"):
        from .pallas.latent_attention import (  # noqa: PLC0415
            latent_decode_attention)
        out = latent_decode_attention(
            q[:, 0], new.flat, new.page_table, new.lengths,
            new.page_size, d_v=d_v, qpos=positions[:, 0], scale=scale)
        return out[:, None], new
    ps = new.page_size
    L = new.page_table.shape[1] * ps
    gather_idx = (new.page_table[:, :, None] * ps
                  + jnp.arange(ps)[None, None, :]).reshape(b, L)
    ck = new.flat[gather_idx][:, :, None, :]              # (B, L, 1, W)
    out = _attend_cached(q, ck, ck[..., :d_v], positions, new.lengths,
                         scale)
    return out, new


def paged_cached_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                           cache: "PagedKV", positions: jax.Array,
                           scale: Optional[float] = None,
                           impl: str = "auto"):
    """cached_attention semantics over a PagedKV pool.

    Static shapes throughout (gather width = P * page_size), so the
    decode step still compiles exactly once; the page indirection is one
    take + one scatter per layer. Storage win vs the slot cache: the
    pool is sized to the real token budget, not B * max_seq_len.
    """
    b, s, hq, d = q.shape
    if scale is None:
        scale = d ** -0.5
    k_flat, v_flat = cache.k_flat, cache.v_flat
    page_table, lengths = cache.page_table, cache.lengths
    page_size = cache.page_size
    n_pages_per_seq = page_table.shape[1]
    L = n_pages_per_seq * page_size

    # a pool row holds `pack` heads side by side (packed_kv_shape: 1
    # unless heads are narrower than 128 lanes), and the pool may be
    # laid out for more KV heads than the layer has (a last packed row
    # filled up; a model whose head count fills no whole 8-row tile
    # declares it rounded up, so that the decode kernel's view of the
    # pool is a bitcast and not a copy of it a call): the extra heads
    # are zeros at the end
    pool_row = k_flat.shape[1:]
    pack = pool_row[1] // d
    hkv, extra = k.shape[2], pool_row[0] * pack - k.shape[2]
    k_new, v_new = k, v
    if extra:
        pad = ((0, 0), (0, 0), (0, extra), (0, 0))
        k_new, v_new = jnp.pad(k, pad), jnp.pad(v, pad)

    # scatter the new tokens' k/v into their flat pool rows
    flat_pos = cache.flat_rows(positions)                     # (B, S)
    k_flat = k_flat.at[flat_pos.reshape(-1)].set(
        k_new.astype(k_flat.dtype).reshape(b * s, *pool_row))
    v_flat = v_flat.at[flat_pos.reshape(-1)].set(
        v_new.astype(v_flat.dtype).reshape(b * s, *pool_row))
    new_lengths = jnp.maximum(lengths, positions[:, -1] + 1)

    if cache.fresh \
            and knobs.get_str("RAY_TPU_PAGED_ATTN_IMPL") != "gather":
        # pure prefill (all sequences start empty): no prior context to
        # gather — attend directly over the new tokens via the model's
        # configured attention impl (flash-eligible for long prompts on
        # TPU). Padding-tail keys only influence discarded query
        # outputs (causal mask), same as the gather path's semantics.
        # RAY_TPU_PAGED_ATTN_IMPL=gather forces the pool-gather
        # reference path here too (A/B-debugging contract).
        out = multi_head_attention(q, k.astype(q.dtype),
                                   v.astype(q.dtype), causal=True,
                                   impl=impl, scale=scale)
        return out, PagedKV(k_flat, v_flat, page_table, new_lengths,
                            page_size)

    # Single-token decode fast path: the Pallas kernel copies each
    # row's live pages out of the pool itself (page table in SMEM) —
    # no (B, L, Hkv, D) contiguous gather temp, and work follows the
    # pages a row holds, not the window. RAY_TPU_PAGED_ATTN_IMPL:
    # auto|gather|pallas.
    impl = knobs.get_str("RAY_TPU_PAGED_ATTN_IMPL")
    if s == 1 and impl != "gather" and (
            impl == "pallas" or jax.default_backend() == "tpu"):
        from .pallas.paged_attention import (  # noqa: PLC0415
            paged_decode_attention)
        q1 = q[:, 0]
        if extra:
            # query heads for the pool's zero heads: they attend zeros
            # and are cut off again
            q1 = jnp.pad(q1, ((0, 0), (0, extra * (hq // hkv)), (0, 0)))
        out = paged_decode_attention(
            q1, k_flat, v_flat, page_table, new_lengths,
            page_size, qpos=positions[:, 0], scale=scale)
        if extra:
            out = out[:, :hq]
        return out[:, None], PagedKV(
            k_flat, v_flat, page_table, new_lengths, page_size)

    # gather each sequence's contiguous KV view from its pages
    gather_idx = (page_table[:, :, None] * page_size
                  + jnp.arange(page_size)[None, None, :]
                  ).reshape(b, L)                             # (B, L)
    ck = k_flat[gather_idx].reshape(b, L, -1, d)[:, :, :hkv]  # (B,L,Hkv,D)
    cv = v_flat[gather_idx].reshape(b, L, -1, d)[:, :, :hkv]
    out = _attend_cached(q, ck, cv, positions, new_lengths, scale)
    return out, PagedKV(k_flat, v_flat, page_table, new_lengths,
                        page_size)


@functools.partial(jax.jit,
                   static_argnames=("mesh", "spec", "causal", "scale"))
def _sharded_flash(q, k, v, *, mesh, spec, causal, scale):
    """The flash kernel inside a sharded train step: per device on its
    share of batch and heads (GQA groups stay whole: q and kv heads split
    over tp at the same boundaries). Jitted so that a model's layers
    share one trace of the kernels, their backward and the shard_map
    around them (two thirds of the training cell's 8 s of tracing were
    here, once a layer); the compiled program is the same."""
    from .pallas.flash_attention import flash_attention  # noqa: PLC0415
    flash = functools.partial(flash_attention, causal=causal, scale=scale)
    return jax.shard_map(flash, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def attention_residuals(q: jax.Array, k: jax.Array, *,
                        causal: bool = True, segment_ids=None,
                        impl: str = "auto") -> Tuple[str, ...]:
    """The names of ATTN_RESIDUALS that this call of multi_head_attention
    gives values to: all five on the flash kernel's route, no logsumexp
    on the others."""
    if _resolve_impl(impl, q, k, causal, segment_ids) == "pallas":
        return ATTN_RESIDUALS
    return ATTN_RESIDUALS[:-1]


def multi_head_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                         *, causal: bool = True,
                         segment_ids: Optional[jax.Array] = None,
                         impl: str = "auto",
                         scale: Optional[float] = None) -> jax.Array:
    """q: (B, Sq, Hq, D); k/v: (B, Sk, Hkv, D) with Hq % Hkv == 0 (GQA).

    Returns (B, Sq, Hq, D). Operands and result carry ATTN_RESIDUALS'
    names for a remat policy to keep.
    """
    impl = _resolve_impl(impl, q, k, causal, segment_ids)
    if impl == "pallas":
        # no fallback: a kernel Mosaic refuses fails the caller's compile
        from ..parallel.sharding import attention_shard_spec  # noqa: PLC0415
        from .pallas.flash_attention import flash_attention  # noqa: PLC0415
        sharded = attention_shard_spec(k.shape)
        if sharded is None:
            return flash_attention(q, k, v, causal=causal, scale=scale)
        mesh, spec = sharded
        return _sharded_flash(q, k, v, mesh=mesh, spec=spec, causal=causal,
                              scale=scale)
    q, k, v = (checkpoint_name(x, name)
               for x, name in zip((q, k, v), ATTN_RESIDUALS))
    if impl == "dpa":
        # jax.nn.dot_product_attention: XLA's own fused attention,
        # which on TPU can lower to the compiler's flash kernel —
        # A/B against "xla" (hand einsum) + "pallas" via flash-ab.
        # Same no-silent-fallback rule as explicit pallas: unsupported
        # arguments must error, not contaminate A/B numbers.
        if segment_ids is not None or q.shape[1] != k.shape[1]:
            raise ValueError(
                "impl='dpa' supports only self-attention without "
                "segment_ids; use impl='xla' for packed/cached shapes")
        return checkpoint_name(jax.nn.dot_product_attention(
            q, k, v, is_causal=causal, scale=scale), _ATTN_OUT)

    b, sq, hq, d = q.shape
    _, sk, hkv, _ = k.shape
    if scale is None:
        scale = d ** -0.5
    if hq != hkv:
        # grouped-query: repeat kv heads
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    mask = None
    if causal:
        mask = causal_attention_mask(sq)[None, None, :, :]
        if sk != sq:  # decode with KV cache: offset the causal structure
            mask = jnp.tril(jnp.ones((sq, sk), dtype=jnp.bool_),
                            k=sk - sq)[None, None, :, :]
    if segment_ids is not None:
        seg_mask = (segment_ids[:, None, :, None]
                    == segment_ids[:, None, None, :])
        mask = seg_mask if mask is None else jnp.logical_and(mask, seg_mask)
    if mask is not None:
        logits = jnp.where(mask, logits, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return checkpoint_name(jnp.einsum("bhqk,bkhd->bqhd", probs, v),
                           _ATTN_OUT)
