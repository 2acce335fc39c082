"""Latent-attention sparse-expert decoders (the `sarvam_mla` family:
sarvam-105b; `xing4_0`: Xing4.0-29B-A4B), TPU-first.

A block is latent attention followed by a dense SwiGLU MLP (the leading
`first_dense` layers) or by an expert layer: routed SwiGLU experts beside
a shared one. Its residual path is the plain sum (`hc_mult` None) or
`hc_mult` streams of `d_model` mixed around each of the two sub-layers
by manifold-constrained hyper-connections (ops/hyper_connections.py has
the equations): the hidden state is then (B, S, hc_mult * d_model), the
embedding repeated into every stream on the way in and the streams
summed before the final norm.

Latent attention, token at position p, h = RMSNorm(x):
  q = W_q h, heads of [nope | rope]; each head's query takes a learned
  RMSNorm (one weight shared by the heads), then its rope part is
  rotated. With `q_lora_rank` the query is low-rank instead,
  q = W_qb RMSNorm(W_qa h), and takes no norm a head.
  [c | k_r] = W_dkv h; c takes a learned RMSNorm, k_r (ONE rope
  key for all heads) is rotated. What is cached is [c | k_r], a token's
  latent. Keys and values are [k_nope_h | v_h] = W_ukv,h c.
Two forms of the same mathematics:
  * expanded (the plain forward, and prefill: `cache.fresh`): k_h =
    [k_nope_h | k_r], causal attention over the new tokens through
    `ops.attention.uneven_head_attention`; the latents are written to
    the pool;
  * absorbed (decode against the pool, PagedLatent): q~_h = W_uk,h^T
    q_nope_h, scores (q~_h . c + q_rope_h . k_r) x scale, o~_h = sum p c,
    o_h = W_uv,h o~_h: multi-query attention over the latents, which the
    Pallas kernel of ops/pallas/latent_attention.py reads where they lie.
RoPE is YaRN's (`ops.rotary.yarn_frequencies`), the scale carries its
m^2 (`yarn_softmax_scale`).

The expert layer routes by sigmoid scores with a selection bias
(`ops.moe.route`, "sigmoid_bias"): the router scores all `n_experts`,
the layer HOLDS `expert_count` of them from `expert_first` (a chip's
share of an expert-parallel deployment; default all) and computes
`sum over selected experts held here of w_e E_e(h) + E_shared(h)`, the
weights normalised over all selected wherever they live. On one chip
the share runs without its exchange; nothing stands in for the absent
experts (ops/moe.py, "A share").

The pool row is the latent padded with zeros to whole 128-lane tiles
(`cache_width`): Mosaic refuses a DMA slice that is no multiple of the
lanes, and the pool's rows are padded so in HBM whatever their nominal
width.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import (apply_rotary, rms_norm, swiglu, yarn_frequencies,
                   yarn_softmax_scale)
from ..ops import hyper_connections as hc
from ..ops.attention import (LayerCache, PagedLatent,
                             latent_cached_attention, uneven_head_attention)
from ..ops.activations import relu2
from ..ops.moe import MOE_STATS, moe_dropless, route
from .llama import _LMHead, head_logits


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    vocab_size: int = 262144
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 64
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    # None: a full-rank query with a learned norm a head (sarvam's); a
    # rank: q = W_qb RMSNorm(W_qa h), no norm a head
    q_lora_rank: Optional[int] = None
    d_ff: int = 16384               # the dense layers' SwiGLU
    first_dense: int = 1            # leading layers with a dense MLP
    d_expert: int = 2048            # one expert's (and the shared) width
    n_experts: int = 128            # the router's width
    experts_per_token: int = 8
    n_shared_experts: int = 1
    routed_scaling: float = 2.5
    norm_topk_prob: bool = True
    route_norm_eps: float = 0.0     # added to the sum that normalises
    # the share of each expert layer held here: experts expert_first ..
    # expert_first + expert_count - 1; None holds all n_experts
    expert_first: int = 0
    expert_count: Optional[int] = None
    max_seq_len: int = 4096         # rows of the rope tables
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_original_max_len: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 1.0
    # residual streams (ops/hyper_connections.py); None: the plain sum
    hc_mult: Optional[int] = None
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: Tuple[float, float] = (-30.0, 30.0)
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    # storage dtype of embeddings and matmul kernels; norm weights, the
    # router and its bias stay float32
    param_dtype: Any = jnp.float32
    attn_impl: str = "auto"

    def __post_init__(self):
        held = self.experts_held
        if not (0 <= self.expert_first
                and self.expert_first + held <= self.n_experts):
            raise ValueError(
                f"experts {self.expert_first}..{self.expert_first + held} "
                f"are not among the router's {self.n_experts}")
        if self.experts_per_token > self.n_experts:
            raise ValueError("experts_per_token exceeds n_experts")
        if self.qk_rope_dim % 2:
            raise ValueError("qk_rope_dim must be even (RoPE pairs)")

    @property
    def experts_held(self) -> int:
        return (self.n_experts if self.expert_count is None
                else self.expert_count)

    def dense_ff(self, i: int) -> bool:
        """Layer i's feed-forward is the dense SwiGLU, not experts."""
        return i < self.first_dense

    @property
    def q_head_dim(self) -> int:
        return self.qk_nope_dim + self.qk_rope_dim

    @property
    def latent_width(self) -> int:
        """What a token caches a layer: latent + rope key."""
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def cache_width(self) -> int:
        """A pool row: `latent_width` in whole 128-lane tiles."""
        return -(-self.latent_width // 128) * 128

    @property
    def hc_params(self) -> Optional[hc.HCParams]:
        if self.hc_mult is None:
            return None
        return hc.HCParams(self.hc_mult, self.hc_sinkhorn_iters,
                           self.hc_eps, self.norm_eps,
                           tuple(self.hc_res_clamp))

    @property
    def softmax_scale(self) -> float:
        return yarn_softmax_scale(self.q_head_dim, self.rope_factor,
                                  self.rope_mscale_all_dim)

    @staticmethod
    def sarvam_105b(**kw) -> "LatentMoEConfig":
        """sarvam-105b as published (config.json, model_type sarvam_mla):
        every default above."""
        return LatentMoEConfig(**kw)

    @staticmethod
    def xing4_29b_a4b(**kw) -> "LatentMoEConfig":
        """Xing4.0-29B-A4B as published (config.json, model_type
        xing4_0): 40 layers, the first 2 dense; four residual streams, a
        low-rank query, 64 experts of 1 024, 4 a token. `max_seq_len`
        stays the rope tables' rows (the published 262 144 positions
        change no frequency); the multi-token-prediction module
        (`num_nextn_predict_layers` 1) is a training objective and a
        draft head, and is not built (docs/SERVING.md)."""
        return LatentMoEConfig(**{**dict(
            vocab_size=131072, d_model=3584, n_layers=40, n_heads=32,
            q_lora_rank=768, d_ff=9216, first_dense=2, d_expert=1024,
            n_experts=64, experts_per_token=4, routed_scaling=2.0,
            rope_factor=64.0, hc_mult=4, hc_sinkhorn_iters=20,
            hc_eps=1e-6, hc_res_clamp=(-30.0, 30.0)), **kw})

    @staticmethod
    def debug(**kw) -> "LatentMoEConfig":
        return LatentMoEConfig(**{**dict(
            vocab_size=256, d_model=64, n_layers=3, n_heads=4,
            qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16, kv_lora_rank=32,
            d_ff=128, d_expert=32, n_experts=8, experts_per_token=2,
            max_seq_len=128, rope_original_max_len=32), **kw})

    @staticmethod
    def xing_debug(**kw) -> "LatentMoEConfig":
        """The debug shape with the `xing4_0` mechanisms: four residual
        streams, a low-rank query, every expert held."""
        return LatentMoEConfig.debug(**{**dict(
            hc_mult=4, q_lora_rank=24, routed_scaling=2.0,
            rope_factor=64.0), **kw})


# spreads of the seeded mapping biases (LatentMoEBlock._mapping)
HC_GATE_SPREAD = 0.5
HC_RES_SPREAD = 1.5


def _dense(cfg: LatentMoEConfig, features: int, name: str):
    return nn.Dense(features, use_bias=False, name=name, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype)


class LatentAttention(nn.Module):
    cfg: LatentMoEConfig

    @nn.compact
    def __call__(self, x, cos, sin, cache=None, positions=None):
        cfg = self.cfg
        b, s, _ = x.shape
        h, dn, dr, dv, r = (cfg.n_heads, cfg.qk_nope_dim, cfg.qk_rope_dim,
                            cfg.v_head_dim, cfg.kv_lora_rank)
        w_ukv = self.param("kv_up_kernel", nn.initializers.lecun_normal(),
                           (r, h * (dn + dv)), cfg.param_dtype
                           ).astype(cfg.dtype).reshape(r, h, dn + dv)
        with jax.named_scope("mla.project"):
            # the product is pinned flat before it is cut into heads: left
            # free, the TPU compiler folds the cut into heads of 192 (no
            # whole number of 128 lanes) into the product's result layout
            # and copies the whole kernel column-major in HBM to match,
            # 100 MB a layer a step (tests/test_tpu_compile.py:
            # test_latent_attention_copies_no_parameter_in_hbm)
            if cfg.q_lora_rank is None:
                q = jax.lax.optimization_barrier(
                    _dense(cfg, h * cfg.q_head_dim, "q_proj")(x))
                q = rms_norm(q.reshape(b, s, h, cfg.q_head_dim),
                             self.param("q_norm", nn.initializers.ones,
                                        (cfg.q_head_dim,)), cfg.norm_eps)
            else:
                with jax.named_scope("mla.q_lora"):
                    q = rms_norm(
                        _dense(cfg, cfg.q_lora_rank, "q_a_proj")(x),
                        self.param("q_a_norm", nn.initializers.ones,
                                   (cfg.q_lora_rank,)), cfg.norm_eps)
                    q = jax.lax.optimization_barrier(
                        _dense(cfg, h * cfg.q_head_dim, "q_b_proj")(q)
                    ).reshape(b, s, h, cfg.q_head_dim)
            q_nope = q[..., :dn]
            q_rope = apply_rotary(q[..., dn:], cos, sin, positions)
            down = _dense(cfg, cfg.latent_width, "kv_down_proj")(x)
            c = rms_norm(down[..., :r],
                         self.param("kv_norm", nn.initializers.ones, (r,)),
                         cfg.norm_eps)
            k_rope = apply_rotary(down[..., None, r:], cos, sin, positions)
            latent = jnp.concatenate([c, k_rope[:, :, 0]], axis=-1)
        scale = cfg.softmax_scale
        if cache is None or cache.fresh:
            with jax.named_scope("mla.expand"):
                kv = jnp.einsum("bsc,chd->bshd", c, w_ukv)
                k = jnp.concatenate(
                    [kv[..., :dn],
                     jnp.broadcast_to(k_rope, (b, s, h, dr))], axis=-1)
                q = jnp.concatenate([q_nope, q_rope], axis=-1)
            with jax.named_scope("mla.attend"):
                out = uneven_head_attention(q, k, kv[..., dn:], scale,
                                            cfg.attn_impl)
            new_cache = None if cache is None else cache.write(
                self._pool_row(latent), positions)
        else:
            with jax.named_scope("mla.absorb"):
                q_abs = jnp.einsum("bshd,chd->bshc", q_nope,
                                   w_ukv[..., :dn])
                q = self._pool_row(
                    jnp.concatenate([q_abs, q_rope], axis=-1))
            with jax.named_scope("mla.attend"):
                o_lat, new_cache = latent_cached_attention(
                    q, self._pool_row(latent), cache, positions, scale,
                    d_v=r)
            with jax.named_scope("mla.expand"):
                out = jnp.einsum("bshc,chd->bshd", o_lat, w_ukv[..., dn:])
        out = _dense(cfg, cfg.d_model, "o_proj")(out.reshape(b, s, h * dv))
        return out, new_cache

    def _pool_row(self, x):
        """Zero-pad the last axis from `latent_width` to `cache_width`."""
        pad = self.cfg.cache_width - self.cfg.latent_width
        return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, pad),))


class _SwiGLU(nn.Module):
    cfg: LatentMoEConfig
    width: int

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        gate = _dense(cfg, self.width, "gate_proj")(x)
        up = _dense(cfg, self.width, "up_proj")(x)
        return _dense(cfg, cfg.d_model, "down_proj")(swiglu(gate, up))


class _Relu2MLP(nn.Module):
    """A feed-forward without a gate: down(relu(up x)^2)."""
    cfg: Any
    width: int

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        return _dense(cfg, cfg.d_model, "down_proj")(
            relu2(_dense(cfg, self.width, "up_proj")(x)))


class ShareMoE(nn.Module):
    """Sigmoid-routed SwiGLU experts, of which this layer holds a share,
    beside a shared expert every token passes through (none where
    `n_shared_experts` is 0). `cfg` is any config with the fields read
    here (models/hybrid.py's has them too). Three further arms where
    `cfg` has the field (models/hybrid.py's `nemotron_h`):
    `moe_latent_dim`, the routed experts computed in a latent, l =
    W_dn x BEFORE the dispatch (the gather moves latent-wide rows) and
    W_up behind the combine (which is linear: a share's partial sum is
    projected on its own), router and shared expert at full width;
    `expert_gated` False, every expert (the shared one too) two matmuls
    and relu^2; `d_shared`, the shared expert's own width.
    `stats_tail`: zeros behind
    the layer's `step_stats` vector, for a model whose vector carries
    further counters behind ops/moe.py's (the engine sums whole
    vectors)."""
    cfg: Any
    stats_tail: int = 0

    @nn.compact
    def __call__(self, x, row_mask=None):
        cfg = self.cfg
        b, s, d = x.shape
        held, f = cfg.experts_held, cfg.d_expert
        latent = getattr(cfg, "moe_latent_dim", None)
        gated = getattr(cfg, "expert_gated", True)
        d_in = latent or d
        router_w = self.param("router_kernel", nn.initializers.normal(0.02),
                              (d, cfg.n_experts))
        # drawn non-zero (a tenth of the spread of the scores that normed
        # activations give through a 0.02-normal router), so that
        # selection by s + b and weighting by s can be told apart
        router_b = self.param("router_bias", nn.initializers.normal(0.025),
                              (cfg.n_experts,))
        init = nn.initializers.lecun_normal(batch_axis=(0,))
        w_gate = self.param("experts_gate_kernel", init, (held, d_in, f),
                            cfg.param_dtype) if gated else None
        w_up = self.param("experts_up_kernel", init, (held, d_in, f),
                          cfg.param_dtype)
        w_down = self.param("experts_down_kernel", init, (held, f, d_in),
                            cfg.param_dtype)
        tokens = x.reshape(b * s, d).astype(cfg.dtype)
        with jax.named_scope("moe.route"):
            logits = jnp.einsum(
                "gd,de->ge", tokens.astype(jnp.float32),
                router_w.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
            weights, top_idx = route(
                logits, cfg.experts_per_token, "sigmoid_bias",
                cfg.norm_topk_prob, select_bias=router_b,
                scale=cfg.routed_scaling, norm_eps=cfg.route_norm_eps)
        routed_in = tokens
        if latent:
            with jax.named_scope("moe.latent_in"):
                routed_in = _dense(cfg, latent, "latent_down_proj")(tokens)
        out, stats = moe_dropless(
            routed_in, weights, top_idx, w_gate, w_up, w_down,
            None if row_mask is None else row_mask.reshape(b * s),
            first=cfg.expert_first, count=held)
        if latent:
            with jax.named_scope("moe.latent_out"):
                out = _dense(cfg, d, "latent_up_proj")(out)
        if self.stats_tail:
            stats = jnp.pad(stats, (0, self.stats_tail))
        self.sow("step_stats", "moe", stats)
        # which experts each position chose, for a reference check
        self.sow("routing", "top_idx",
                 top_idx.reshape(b, s, cfg.experts_per_token))
        if cfg.n_shared_experts:
            with jax.named_scope("moe.shared"):
                width = getattr(cfg, "d_shared", None) \
                    or cfg.n_shared_experts * f
                out = out + (_SwiGLU if gated else _Relu2MLP)(
                    cfg, width, name="shared")(tokens)
        return out.reshape(b, s, d).astype(cfg.dtype)


class LatentMoEBlock(nn.Module):
    cfg: LatentMoEConfig
    dense: bool

    @nn.compact
    def __call__(self, x, cos, sin, cache=None, positions=None,
                 row_mask=None):
        cfg = self.cfg
        attn_norm_w = self.param("attn_norm", nn.initializers.ones,
                                 (cfg.d_model,))
        mlp_norm_w = self.param("mlp_norm", nn.initializers.ones,
                                (cfg.d_model,))
        if cfg.hc_mult is not None:
            return self._streams(x, cos, sin, cache, positions, row_mask,
                                 attn_norm_w, mlp_norm_w)
        h, new_cache = LatentAttention(cfg, name="attention")(
            rms_norm(x, attn_norm_w, cfg.norm_eps), cos, sin, cache,
            positions)
        x = x + h
        h = rms_norm(x, mlp_norm_w, cfg.norm_eps)
        if self.dense:
            x = x + _SwiGLU(cfg, cfg.d_ff, name="mlp")(h)
        else:
            x = x + ShareMoE(cfg, name="moe")(h, row_mask)
        return x, new_cache

    def _mapping(self, name: str):
        """One sub-layer's (phi, b, a). phi is drawn so that m is about
        unit normal on a normed stream; b and a so that no mapping is
        constant or saturated under seeded weights: a = 1 on every arm,
        b normal with spread HC_GATE_SPREAD on the two sigmoid arms
        (gates between ~0.1 and ~0.9 of their range) and HC_RES_SPREAD
        on Hres's logits (entries a few e-foldings apart: 20 Sinkhorn
        iterations converge on all but a few tokens in a thousand, 2 do
        not)."""
        cfg = self.cfg
        n = cfg.hc_mult
        width = n * cfg.d_model
        spread = jnp.concatenate([jnp.full((2 * n,), HC_GATE_SPREAD),
                                  jnp.full((n * n,), HC_RES_SPREAD)])
        return (
            self.param(f"hc_{name}_phi",
                       nn.initializers.normal(width ** -0.5),
                       (hc.n_maps(n), width), cfg.param_dtype),
            self.param(f"hc_{name}_b", lambda k, shape: spread
                       * jax.random.normal(k, shape), (hc.n_maps(n),)),
            self.param(f"hc_{name}_a", nn.initializers.ones, (3,)))

    def _streams(self, x, cos, sin, cache, positions, row_mask,
                 attn_norm_w, mlp_norm_w):
        """The block over `hc_mult` residual streams, x (B, S, n * d):
        each sub-layer reads h = sum_i Hpre[i] X[i], takes its own
        pre-norm, and writes X'[i] = sum_j Hres[i, j] X[j] + Hpost[i] y."""
        cfg, hp = self.cfg, self.cfg.hc_params
        h, maps = hc.read(x, *self._mapping("attn"), hp)
        y, new_cache = LatentAttention(cfg, name="attention")(
            rms_norm(h, attn_norm_w, cfg.norm_eps), cos, sin, cache,
            positions)
        x = hc.write(x, y, maps, hp.n)
        counted = hc.counters(maps, row_mask)
        h, maps = hc.read(x, *self._mapping("mlp"), hp)
        h = rms_norm(h, mlp_norm_w, cfg.norm_eps)
        if self.dense:
            y = _SwiGLU(cfg, cfg.d_ff, name="mlp")(h)
        else:
            y = ShareMoE(cfg, len(hc.HC_STATS), name="moe")(h, row_mask)
        x = hc.write(x, y, maps, hp.n)
        counted = counted + hc.counters(maps, row_mask)
        self.sow("step_stats", "hc",
                 jnp.pad(counted, (len(MOE_STATS), 0)))
        return x, new_cache


class LatentMoE(nn.Module):
    """tokens (B, S) -> (logits, cache): the calling convention of Llama
    and Mixtral, so that the serve engine is family agnostic. `cache` is
    None (the plain forward, expanded form) or one PagedLatent a layer
    (`paged_cache_spec`). Under `mutable=["step_stats"]` every expert
    layer leaves the int32 vector `step_stats` names (ops/moe.py:
    MOE_STATS), counted over the rows `row_mask` (B, S) marks as real;
    with residual streams every block also leaves its two sub-layers'
    ops/hyper_connections.py:HC_STATS behind them in the same vector.
    `return_hidden`: models/llama.py."""
    cfg: LatentMoEConfig

    @property
    def step_stats(self):
        return MOE_STATS + (hc.HC_STATS if self.cfg.hc_mult else ())

    @nn.compact
    def __call__(self, tokens, cache=None, positions=None, row_mask=None,
                 return_hidden: bool = False):
        cfg = self.cfg
        x = nn.Embed(cfg.vocab_size, cfg.d_model, name="token_embed",
                     dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                     embedding_init=nn.initializers.normal(0.02))(tokens)
        if cfg.hc_mult is not None:
            x = jnp.tile(x, (1, 1, cfg.hc_mult))
        cos, sin = yarn_frequencies(
            cfg.qk_rope_dim, cfg.max_seq_len, cfg.rope_theta,
            factor=cfg.rope_factor,
            original_max_len=cfg.rope_original_max_len,
            beta_fast=cfg.rope_beta_fast, beta_slow=cfg.rope_beta_slow)
        new_cache = []
        for i in range(cfg.n_layers):
            x, c = LatentMoEBlock(cfg, cfg.dense_ff(i),
                                  name=f"layer_{i}")(
                x, cos, sin, None if cache is None else cache[i],
                positions, row_mask)
            new_cache.append(c)
        if cfg.hc_mult is not None:
            x = x.reshape(*x.shape[:-1], cfg.hc_mult, cfg.d_model).astype(
                jnp.float32).sum(-2).astype(cfg.dtype)
        x = rms_norm(x, self.param("final_norm", nn.initializers.ones,
                                   (cfg.d_model,)), cfg.norm_eps)
        logits = _LMHead(cfg.vocab_size, cfg.param_dtype,
                         name="lm_head")(x)
        out = (logits, new_cache if cache is not None else None)
        return out + (x,) if return_hidden else out

    def head(self, params, hidden):
        """models/llama.py:Llama.head."""
        return head_logits(hidden, kernel=params["lm_head"]["kernel"])

    def init_params(self, rng, batch=1, seq=8):
        return self.init(rng, jnp.zeros((batch, seq), jnp.int32))["params"]

    def paged_cache_spec(self):
        """One latent row a token a layer
        (ops/attention.py:kv_cache_spec)."""
        cfg = self.cfg
        return [LayerCache(PagedLatent, ((cfg.cache_width,),),
                           (cfg.dtype,))] * cfg.n_layers
