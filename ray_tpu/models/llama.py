"""Llama-3-style decoder-only transformer, TPU-first.

Built from scratch on flax.linen + ray_tpu.ops (not a port of any torch
implementation; the reference trains Llama via HF torch models inside
TorchTrainer — e.g. python/ray/train/examples and doc/source/train llm
examples). Design notes:
  * GQA attention, RoPE, RMSNorm, SwiGLU — all bf16 compute, fp32 norms.
  * Pure-functional KV cache (pytree in/out) so the serve engine can jit
    prefill/decode separately with static shapes.
  * Optional `remat` applies jax.checkpoint per block (HBM <-> FLOPs trade);
    `remat_policy` says what a block keeps: by default the attention
    half's residuals by name, so that the backward reruns the MLP only.
  * Module names line up with ray_tpu.parallel.sharding DEFAULT_RULES, so
    tp/fsdp PartitionSpecs attach without model surgery.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops import (rms_norm, apply_rotary, rope_frequencies,
                   cached_attention,
                   multi_head_attention, swiglu)
from ..ops.attention import ATTN_RESIDUALS, attention_residuals

# the residual stream after attention, x + o_proj(out): with it kept the
# backward reruns neither o_proj nor the ring that sums it over `tp`
_ATTN_RESID = "attn_resid"
# LlamaConfig.remat_policy -> jax.checkpoint's policy
_REMAT_POLICIES = {
    "attention": jax.checkpoint_policies.save_only_these_names(
        *ATTN_RESIDUALS, _ATTN_RESID),
    "full": None,
    "dots": jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
}


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    d_model: int = 2048
    n_layers: int = 16
    n_heads: int = 16
    n_kv_heads: int = 8
    d_ff: int = 5632
    max_seq_len: int = 2048
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    remat: bool = False
    # What a rematted block keeps for its backward beside its input:
    # "attention" (the default): the attention half's residuals, by name
    #   (_REMAT_POLICIES: q, k and v after the rotation, the attention's
    #   output and the flash kernel's logsumexp, the residual stream
    #   after o_proj; 118 MB a layer a chip at Mistral-7B's widths, 2
    #   rows x 4 096 and tp=2). The backward reruns the norms and the
    #   MLP only, whose gate / up products (117 MB each at that shape)
    #   are too large to keep: the most speed that fits where "dots"
    #   does not.
    # "full": nothing; the whole forward runs again (~1.33x FLOPs). For
    #   a caller at the memory limit.
    # "dots": every matmul output (jax.checkpoint_policies.
    #   dots_with_no_batch_dims_saveable); only elementwise ops rerun.
    #   Where micro-batches are small enough (grad accumulation on one
    #   chip) for the MLP's products to fit.
    remat_policy: str = "attention"
    dtype: Any = jnp.bfloat16
    # Storage dtype of the big parameter tensors (embeddings + matmul
    # kernels). fp32 default; bf16 halves parameter HBM — the knob that
    # fits >=1B-param training on one 16 GB chip (norm weights stay
    # fp32 regardless: they're tiny and fp32 norms are load-bearing).
    param_dtype: Any = jnp.float32
    attn_impl: str = "auto"         # "auto" | "xla" | "dpa" | "pallas"
    # None = fp weights; "int8" = weight-only quantized projections
    # (ops/quant.py QuantDense; params from quantize_llama_params).
    # Serving-only: int8 kernels are not trained.
    quant: Optional[str] = None
    # True: RMSNorm over the whole projected q vector and over the whole
    # projected k vector, before the rotation (OLMoE's q_norm / k_norm).
    # "head": over each head's width, one weight shared by the heads
    # (models/hybrid.py's short-convolution family).
    qk_norm: "bool | str" = False
    # True: the attention's result is gated elementwise, before o_proj,
    # by the sigmoid of a projection of the block's input of its own
    # (models/hybrid.py's `solar_open2` family)
    out_gate: bool = False

    def __post_init__(self):
        if self.d_model % self.n_heads:
            raise ValueError(
                f"d_model={self.d_model} must be divisible by "
                f"n_heads={self.n_heads}")
        if (self.d_model // self.n_heads) % 2:
            raise ValueError(
                f"head_dim={self.d_model // self.n_heads} must be even "
                f"(RoPE rotates dimension pairs)")
        if self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"n_heads={self.n_heads} must be divisible by "
                f"n_kv_heads={self.n_kv_heads} (GQA groups)")
        if self.quant not in (None, "int8"):
            raise ValueError(f"quant={self.quant!r}; valid: None, "
                             f"'int8'")
        if self.remat_policy not in _REMAT_POLICIES:
            raise ValueError(f"remat_policy={self.remat_policy!r}; "
                             f"valid: {sorted(_REMAT_POLICIES)}")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    # ---- presets (sizes follow the public Llama-3 family; kwargs
    # override any preset default, e.g. max_seq_len / remat) ----
    @staticmethod
    def llama3_8b(**kw) -> "LlamaConfig":
        return LlamaConfig(**{**dict(
            vocab_size=128256, d_model=4096, n_layers=32, n_heads=32,
            n_kv_heads=8, d_ff=14336, max_seq_len=8192, remat=True),
            **kw})

    @staticmethod
    def llama3_1b(**kw) -> "LlamaConfig":
        return LlamaConfig(**{**dict(
            vocab_size=128256, d_model=2048, n_layers=16, n_heads=32,
            n_kv_heads=8, d_ff=8192, max_seq_len=8192), **kw})

    @staticmethod
    def debug(**kw) -> "LlamaConfig":
        return LlamaConfig(vocab_size=256, d_model=64, n_layers=2,
                           n_heads=4, n_kv_heads=2, d_ff=128,
                           max_seq_len=128, **kw)


def _proj(cfg: LlamaConfig, features: int, name: str):
    """Projection layer: nn.Dense, or QuantDense under quant='int8'
    (same param-tree position; kernel -> kernel_q/scale)."""
    if cfg.quant == "int8":
        from ..ops.quant import QuantDense  # noqa: PLC0415
        return QuantDense(features, name=name, dtype=cfg.dtype)
    return nn.Dense(features, use_bias=False, name=name,
                    dtype=cfg.dtype, param_dtype=cfg.param_dtype)


class _Kernel(nn.Module):
    """The kernel of `_proj`'s nn.Dense of the same name, at the same
    place of the parameter tree, for a caller that multiplies itself."""
    features: int
    param_dtype: Any

    @nn.compact
    def __call__(self, x):
        return self.param("kernel", nn.linear.default_kernel_init,
                          (x.shape[-1], self.features), self.param_dtype)


def _tp_route(cfg: LlamaConfig, shape, matmuls: int):
    """The mesh for parallel/collective_matmul.py where a sharded train
    step runs these projections of (B, S, D) activations overlapped with
    their collective, else None: `_proj`'s plain layers (serve, one
    device, `tp` of 1)."""
    if cfg.quant is not None:
        return None
    from ..parallel.sharding import tp_matmul_route  # noqa: PLC0415
    return tp_matmul_route(shape, matmuls)


def _column_proj(cfg: LlamaConfig, x, chunks: bool = False,
                 **features: int):
    """x through the column-parallel projections `name=features` that
    share it, in order. On the overlapped route x comes sharded over the
    sequence and is gathered once for all of them; `chunks` lets each
    product be the tuple of row chunks the gather delivers, for
    elementwise ops on the way to `_row_proj`."""
    mesh = _tp_route(cfg, x.shape, len(features))
    if mesh is None:
        return [_proj(cfg, f, name)(x) for name, f in features.items()]
    from ..parallel.collective_matmul import allgather_matmul  # noqa: PLC0415
    kernels = [_Kernel(f, cfg.param_dtype, name=name)(x).astype(cfg.dtype)
               for name, f in features.items()]
    return allgather_matmul(x.astype(cfg.dtype), kernels, mesh,
                            chunks=chunks)


def _row_proj(cfg: LlamaConfig, x, features: int, name: str):
    """x (or the chunks `_column_proj` made of it) through the
    row-parallel projection `name`. On the overlapped route the sum over
    `tp` comes back sharded over the sequence."""
    parts = x if isinstance(x, tuple) else (x,)
    b, _, f = parts[0].shape
    mesh = _tp_route(cfg, (b, sum(p.shape[1] for p in parts), f), 1)
    if mesh is None:
        return _proj(cfg, features, name)(x)
    from ..parallel.collective_matmul import matmul_reducescatter  # noqa: PLC0415
    kernel = _Kernel(features, cfg.param_dtype, name=name)(parts[0])
    return matmul_reducescatter(
        jax.tree.map(lambda p: p.astype(cfg.dtype), x),
        kernel.astype(cfg.dtype), mesh)


class LlamaAttention(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, cos, sin, cache=None, positions=None):
        cfg = self.cfg
        hd = cfg.head_dim
        q, k, v = _column_proj(cfg, x, q_proj=cfg.n_heads * hd,
                               k_proj=cfg.n_kv_heads * hd,
                               v_proj=cfg.n_kv_heads * hd)
        b, s, _ = x.shape
        if cfg.qk_norm is True:
            q = rms_norm(q, self.param("q_norm", nn.initializers.ones,
                                       (cfg.n_heads * hd,)), cfg.norm_eps)
            k = rms_norm(k, self.param("k_norm", nn.initializers.ones,
                                       (cfg.n_kv_heads * hd,)),
                         cfg.norm_eps)
        q = q.reshape(b, s, cfg.n_heads, hd)
        k = k.reshape(b, s, cfg.n_kv_heads, hd)
        v = v.reshape(b, s, cfg.n_kv_heads, hd)
        if cfg.qk_norm == "head":
            q = rms_norm(q, self.param("q_norm", nn.initializers.ones,
                                       (hd,)), cfg.norm_eps)
            k = rms_norm(k, self.param("k_norm", nn.initializers.ones,
                                       (hd,)), cfg.norm_eps)
        if cos is not None:     # None: a model without positions in
            #                     its attention (models/hybrid.py)
            q = apply_rotary(q, cos, sin, positions)
            k = apply_rotary(k, cos, sin, positions)

        new_cache = None
        if cache is None:
            out = multi_head_attention(q, k, v, causal=True,
                                       impl=cfg.attn_impl)
        else:
            # Decode: write new k/v at `positions`, attend over prefix
            # (shared zoo-wide cached path, ops/attention.py).
            out, new_cache = cached_attention(q, k, v, cache, positions,
                                              impl=cfg.attn_impl)

        out = out.reshape(b, s, cfg.n_heads * hd)
        if cfg.out_gate:
            with jax.named_scope("attn.out_gate"):
                gate = _proj(cfg, cfg.n_heads * hd, "gate_proj")(x)
                out = (out * jax.nn.sigmoid(gate.astype(jnp.float32))
                       ).astype(cfg.dtype)
        out = _row_proj(cfg, out, cfg.d_model, "o_proj")
        return out, new_cache


class LlamaMLP(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        gate, up = _column_proj(cfg, x, chunks=True, gate_proj=cfg.d_ff,
                                up_proj=cfg.d_ff)
        return _row_proj(cfg, jax.tree.map(swiglu, gate, up), cfg.d_model,
                         "down_proj")


class LlamaBlock(nn.Module):
    cfg: LlamaConfig

    @nn.compact
    def __call__(self, x, cos, sin, cache=None, positions=None):
        cfg = self.cfg
        attn_norm_w = self.param("attn_norm", nn.initializers.ones,
                                 (cfg.d_model,))
        mlp_norm_w = self.param("mlp_norm", nn.initializers.ones,
                                (cfg.d_model,))
        h, new_cache = LlamaAttention(cfg, name="attention")(
            rms_norm(x, attn_norm_w, cfg.norm_eps), cos, sin, cache,
            positions)
        x = checkpoint_name(x + h, _ATTN_RESID)
        x = x + LlamaMLP(cfg, name="mlp")(
            rms_norm(x, mlp_norm_w, cfg.norm_eps))
        return x, new_cache


def head_logits(x, kernel=None, embedding=None):
    """Final hidden states x (B, S', d_model) through the head: the
    untied `kernel` (d_model, V), or the `embedding` (V, d_model) of a
    tied one. bf16 operands + fp32 accumulation: fp32-quality logits at
    bf16 MXU speed (casting both sides to fp32 would force slow fp32
    passes on the biggest matmul in the model)."""
    if embedding is not None:
        return jnp.einsum("bsd,vd->bsv", x, embedding.astype(x.dtype),
                          preferred_element_type=jnp.float32)
    return jnp.einsum("bsd,dv->bsv", x, kernel.astype(x.dtype),
                      preferred_element_type=jnp.float32)


class _LMHead(nn.Module):
    """Untied head, kernel stored at params['lm_head']['kernel'] (same
    tree as nn.Dense) in `param_dtype`. Matmul runs bf16-in/fp32-
    accumulate — MXU native — instead of nn.Dense(dtype=fp32)'s
    full-fp32 pass."""
    vocab_size: int
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", nn.initializers.lecun_normal(),
                            (x.shape[-1], self.vocab_size),
                            self.param_dtype)
        return head_logits(x, kernel=kernel)


class Llama(nn.Module):
    cfg: LlamaConfig

    def head(self, params, hidden):
        """The head alone on this model's parameter tree, over any rows
        (B, S', d_model) of the final hidden states `return_hidden`
        hands out (the serve engine's prefill: the rows it samples
        from, not every position). Every served family has one."""
        if self.cfg.tie_embeddings:
            return head_logits(
                hidden, embedding=params["token_embed"]["embedding"])
        return head_logits(hidden, kernel=params["lm_head"]["kernel"])

    @nn.compact
    def __call__(self, tokens, cache=None, positions=None,
                 return_hidden: bool = False):
        """tokens: (B, S) int32. cache: optional list of per-layer
        (k, v, lengths). Returns (logits, new_cache), and under
        `return_hidden` the final hidden states (B, S, d_model) the
        head multiplied as a third (`head` applies it to any of their
        rows)."""
        cfg = self.cfg
        embed = nn.Embed(cfg.vocab_size, cfg.d_model, name="token_embed",
                         dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         embedding_init=nn.initializers.normal(0.02))
        from ..parallel.sharding import (constrain_activations,  # noqa: PLC0415
                                         count_saved_residuals)
        # Pin the residual stream to batch/sequence sharding right at the
        # embed: the (vocab, d) table is (tp, fsdp)-sharded, and without
        # the pin XLA carries the table's d-sharding into the hiddens and
        # the backward re-shards them with a full rematerialization.
        x = constrain_activations(embed(tokens))
        cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len,
                                    cfg.rope_theta)
        new_cache = []
        # remat trades recompute for HBM on the train path only; the decode
        # path (cache is not None) never checkpoints. Param paths stay
        # "layer_{i}/..." under both classes, so one weight pytree serves
        # train and serve.
        if cfg.remat and cache is None:
            block_cls = nn.remat(LlamaBlock,
                                 policy=_REMAT_POLICIES[cfg.remat_policy])
            if cfg.remat_policy == "attention":
                # a block's q and k have x's rows, which is all that
                # the attention's route is chosen from
                count_saved_residuals(cfg.n_layers * (1 + len(
                    attention_residuals(x, x, impl=cfg.attn_impl))))
        else:
            block_cls = LlamaBlock
        for i in range(cfg.n_layers):
            block = block_cls(cfg, name=f"layer_{i}")
            x, c = block(x, cos, sin,
                         None if cache is None else cache[i], positions)
            new_cache.append(c)
        final_w = self.param("final_norm", nn.initializers.ones,
                             (cfg.d_model,))
        # the head multiplies whole sequences, whatever the blocks kept
        x = constrain_activations(rms_norm(x, final_w, cfg.norm_eps),
                                  gathered=True)
        if cfg.tie_embeddings:
            logits = head_logits(x, embedding=embed.embedding)
        else:
            logits = _LMHead(cfg.vocab_size, cfg.param_dtype,
                             name="lm_head")(x)
        out = (logits, new_cache if cache is not None else None)
        return out + (x,) if return_hidden else out

    # ---- convenience ----
    def init_params(self, rng, batch=1, seq=8):
        tokens = jnp.zeros((batch, seq), dtype=jnp.int32)
        return self.init(rng, tokens)["params"]

    def empty_cache(self, batch: int, max_len: int,
                    dtype=jnp.bfloat16):
        cfg = self.cfg
        return [
            (jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.head_dim),
                       dtype=dtype),
             jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.head_dim),
                       dtype=dtype),
             jnp.zeros((batch,), dtype=jnp.int32))
            for _ in range(cfg.n_layers)
        ]
