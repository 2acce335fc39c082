"""Hybrid decoders: a mixer a layer, chosen by `layer_types` (the
`olmo_hybrid` family: Olmo-Hybrid-7B), TPU-first.

A block is a mixer and a SwiGLU MLP around the residual stream. The
mixer of layer i is what `layer_types[i]` names:

  * "full_attention": `LlamaAttention` (models/llama.py) with its
    `qk_norm` arm (RMSNorm over the whole projected q and k) and no
    rotation (the family's `rope_theta` is null: its full layers see
    no positions);
  * "linear_attention": `GatedDeltaNet`, the layer of
    ops/gated_deltanet.py. With x the block's input, H heads of key
    width d_k and value width d_v:
        [q~ | k~ | v] = SiLU(conv_K(W_qkv x))     depthwise, causal
        q = l2norm(q~) d_k^-1/2, k = l2norm(k~)   over each head's d_k
        beta = sigmoid(w_b x) (x 2 if allow_neg_eigval)
        alpha = exp(-exp(A_log) softplus(w_a x + dt_bias))
        S <- alpha S (I - beta k k^T) + beta v k^T,  o = S q
        y = W_o [RMSNorm_dv(o_h) * SiLU(W_g x)_h]
    prefill and the plain forward in the chunkwise form, a decode step
    against the engine's per-slot state in the one-token form (the
    Pallas kernel of ops/pallas/gdn_decode.py on the TPU). Projections,
    convolution and output in the activations' dtype, the state and
    everything that touches it in float32.

Each sub-layer's OUTPUT is normalised, as the OLMo 2 and 3 family
does: h = x + Norm(Mixer(x)), y = h + Norm(MLP(h)).

What a layer caches it says itself (`paged_cache_spec`): a full layer
pages K and V a token, a linear layer keeps a state and the
convolution's last K - 1 inputs a SLOT (ops/attention.py:SlotState).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import rms_norm
from ..ops import gated_deltanet as gdn
from ..ops.attention import LayerCache, PagedKV, SlotState
from ..util import knobs
from .llama import LlamaAttention, LlamaMLP, _LMHead, _proj

LINEAR, FULL = "linear_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class HybridConfig:
    vocab_size: int = 100352
    d_model: int = 3840
    n_layers: int = 32
    # one entry a layer; None repeats (linear, linear, linear, full)
    layer_types: Optional[Tuple[str, ...]] = None
    n_heads: int = 30               # the full layers'
    n_kv_heads: int = 30
    d_ff: int = 11008
    linear_n_heads: int = 30
    linear_key_dim: int = 96        # d_k, a head
    linear_value_dim: int = 192     # d_v, a head
    linear_conv_kernel: int = 4
    linear_allow_neg_eigval: bool = True
    linear_chunk: int = 64          # tokens a chunk of the chunkwise form
    max_seq_len: int = 65536
    norm_eps: float = 1e-6
    qk_norm: bool = True
    dtype: Any = jnp.bfloat16
    # storage dtype of embeddings and matmul kernels; norm weights,
    # A_log and dt_bias stay float32
    param_dtype: Any = jnp.float32
    attn_impl: str = "auto"
    quant: Optional[str] = None             # LlamaAttention reads it

    def __post_init__(self):
        if self.layer_types is None:
            object.__setattr__(self, "layer_types", tuple(
                FULL if i % 4 == 3 else LINEAR
                for i in range(self.n_layers)))
        else:
            object.__setattr__(self, "layer_types",
                               tuple(self.layer_types))
        bad = set(self.layer_types) - {LINEAR, FULL}
        if bad or len(self.layer_types) != self.n_layers:
            raise ValueError(
                f"layer_types must name {self.n_layers} layers as "
                f"{LINEAR!r} or {FULL!r}; got {self.layer_types}")
        if self.d_model % self.n_heads or self.n_heads % self.n_kv_heads:
            raise ValueError("d_model / n_heads / n_kv_heads do not divide")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def kv_pool_heads(self) -> int:
        """KV heads the page pool is laid out for: the count rounded up
        to whole 8-row tiles (ops/attention.py:paged_cached_attention)."""
        return -(-self.n_kv_heads // 8) * 8

    @property
    def conv_width(self) -> int:
        """Columns of the fused q | k | v projection."""
        return self.linear_n_heads * (2 * self.linear_key_dim
                                      + self.linear_value_dim)

    @staticmethod
    def olmo_hybrid_7b(**kw) -> "HybridConfig":
        return HybridConfig(**kw)

    @staticmethod
    def debug(**kw) -> "HybridConfig":
        return HybridConfig(**{**dict(
            vocab_size=256, d_model=64, n_layers=4, n_heads=4,
            n_kv_heads=4, d_ff=128, linear_n_heads=4, linear_key_dim=8,
            linear_value_dim=16, linear_chunk=8, max_seq_len=256), **kw})


def _uniform(bound: float):
    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)
    return init


def _a_log_init(key, shape, dtype=jnp.float32):
    """log A, A uniform in (0, 16): the published kernels' draw."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-3, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """softplus^-1 of a step drawn log-uniform in (0.001, 0.1)."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, jnp.log(1e-3),
                                    jnp.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


class GatedDeltaNet(nn.Module):
    cfg: HybridConfig

    @nn.compact
    def __call__(self, x, cache: Optional[SlotState] = None):
        cfg = self.cfg
        h, dk, dv = (cfg.linear_n_heads, cfg.linear_key_dim,
                     cfg.linear_value_dim)
        b, s, _ = x.shape
        with jax.named_scope("gdn.project"):
            u = _proj(cfg, cfg.conv_width, "qkv_proj")(x)
            gate = _proj(cfg, h * dv, "g_proj")(x)
            a = _proj(cfg, h, "a_proj")(x)
            bb = _proj(cfg, h, "b_proj")(x)
            g, beta = gdn.gates(
                a, bb, self.param("A_log", _a_log_init, (h,)),
                self.param("dt_bias", _dt_bias_init, (h,)),
                cfg.linear_allow_neg_eigval)
        conv_w = self.param(
            "conv_kernel", _uniform(cfg.linear_conv_kernel ** -0.5),
            (cfg.linear_conv_kernel, cfg.conv_width), cfg.param_dtype)
        state = tail = n_new = None
        if cache is not None:
            state, tail = cache.read()
            n_new = cache.n_new
            g, beta = gdn.freeze(
                g, beta, jnp.arange(s)[None, :] < n_new[:, None])
        with jax.named_scope("gdn.conv"):
            qkv, tail = gdn.causal_conv(u, conv_w, tail, n_new)
            q, k, v = jnp.split(qkv, [h * dk, 2 * h * dk], axis=-1)
            q = gdn.l2norm(q.reshape(b, s, h, dk)) * dk ** -0.5
            k = gdn.l2norm(k.reshape(b, s, h, dk))
            v = v.reshape(b, s, h, dv)
        if cache is not None and s == 1:
            with jax.named_scope("gdn.step"):
                o, state = self._step(q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                                      beta[:, 0], state)
                o = o[:, None]
        else:
            with jax.named_scope("gdn.scan"):
                o, state = gdn.chunk_scan(q, k, v, g, beta, state,
                                          chunk=cfg.linear_chunk)
        with jax.named_scope("gdn.gate_out"):
            o = rms_norm(o, self.param("o_norm", nn.initializers.ones,
                                       (dv,)), cfg.norm_eps)
            o = (o.reshape(b, s, h * dv)
                 * jax.nn.silu(gate.astype(jnp.float32))).astype(cfg.dtype)
            y = _proj(cfg, cfg.d_model, "o_proj")(o)
        return y, (None if cache is None else cache.write(state, tail))

    @staticmethod
    def _step(q, k, v, g, beta, state):
        """The one-token form: the fused kernel on the TPU (or under
        RAY_TPU_PAGED_ATTN_IMPL=pallas, interpreted on the CPU), three
        passes in plain XLA elsewhere (and under =gather)."""
        impl = knobs.get_str("RAY_TPU_PAGED_ATTN_IMPL")
        if impl != "gather" and (impl == "pallas"
                                 or jax.default_backend() == "tpu"):
            from ..ops.pallas.gdn_decode import (  # noqa: PLC0415
                gdn_decode_step)
            return gdn_decode_step(q, k, v, g, beta, state)
        return gdn.step(q, k, v, g, beta, state)


class HybridBlock(nn.Module):
    cfg: HybridConfig
    kind: str

    @nn.compact
    def __call__(self, x, cache=None, positions=None):
        cfg = self.cfg
        mixer_w = self.param("attn_norm", nn.initializers.ones,
                             (cfg.d_model,))
        mlp_w = self.param("mlp_norm", nn.initializers.ones,
                           (cfg.d_model,))
        if self.kind == FULL:
            # cos = sin = None: no rotation
            h, new_cache = LlamaAttention(cfg, name="attention")(
                x, None, None, cache, positions)
        else:
            h, new_cache = GatedDeltaNet(cfg, name="linear_attention")(
                x, cache)
        x = x + rms_norm(h, mixer_w, cfg.norm_eps)
        x = x + rms_norm(LlamaMLP(cfg, name="mlp")(x), mlp_w, cfg.norm_eps)
        return x, new_cache


class Hybrid(nn.Module):
    """tokens (B, S) -> (logits, cache): Llama's calling convention, so
    that the serve engine is family agnostic. `cache` is None (the plain
    forward: every layer starts from nothing) or one entry a layer as
    `paged_cache_spec` says."""
    cfg: HybridConfig

    @nn.compact
    def __call__(self, tokens, cache=None, positions=None):
        cfg = self.cfg
        x = nn.Embed(cfg.vocab_size, cfg.d_model, name="token_embed",
                     dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                     embedding_init=nn.initializers.normal(0.02))(tokens)
        new_cache = []
        for i, kind in enumerate(cfg.layer_types):
            x, c = HybridBlock(cfg, kind, name=f"layer_{i}")(
                x, None if cache is None else cache[i], positions)
            new_cache.append(c)
        x = rms_norm(x, self.param("final_norm", nn.initializers.ones,
                                   (cfg.d_model,)), cfg.norm_eps)
        logits = _LMHead(cfg.vocab_size, cfg.param_dtype,
                         name="lm_head")(x)
        return logits, (new_cache if cache is not None else None)

    def init_params(self, rng, batch=1, seq=8):
        return self.init(rng, jnp.zeros((batch, seq), jnp.int32))["params"]

    def paged_cache_spec(self):
        """A full layer pages K and V of (kv_pool_heads, head_dim) a
        token; a linear layer keeps, a slot, the float32 state
        (d_k, H x d_v) and the convolution's last K - 1 inputs
        (ops/attention.py:kv_cache_spec)."""
        cfg = self.cfg
        kv = (cfg.kv_pool_heads, cfg.head_dim)
        full = LayerCache(PagedKV, (kv, kv), (cfg.dtype, cfg.dtype))
        linear = LayerCache(
            SlotState,
            ((cfg.linear_key_dim,
              cfg.linear_n_heads * cfg.linear_value_dim),
             (cfg.linear_conv_kernel - 1, cfg.conv_width)),
            (jnp.float32, cfg.dtype), by_slot=True)
        return [full if kind == FULL else linear
                for kind in cfg.layer_types]
