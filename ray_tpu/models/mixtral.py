"""Sparse-MoE decoders (Mixtral, OLMoE), TPU-first.

The reference serves/trains these through HF torch (dynamic per-token
expert gather). Here the MoE MLP is static-shaped: by default dropless
(`ops.moe.moe_dropless`: sort by expert, grouped matmuls, gather back),
which is what serving and the plain forward run; a config whose
`capacity_factor` is a number takes the GShard capacity dispatch
instead, whose einsums XLA turns into an all-to-all when the stacked
expert weights' leading axis is sharded over the `ep` mesh axis (see
parallel/sharding.py DEFAULT_RULES: `experts_*`).

The two families differ in configuration only: the routing convention
(`routing`, `norm_topk_prob`) and OLMoE's q/k RMSNorm (`qk_norm`).
Attention/RoPE/norms reuse the Llama blocks — weight layout stays
`layer_{i}/attention/...` so serve/train tooling treats all families
uniformly.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import rms_norm, rope_frequencies, swiglu
from ..ops.moe import (MOE_STATS, ROUTINGS, moe_dispatch_combine,
                       moe_dropless, route, router_aux)
from .llama import LlamaAttention, LlamaConfig, _LMHead


@dataclasses.dataclass(frozen=True)
class MixtralConfig:
    vocab_size: int = 32000
    d_model: int = 2048
    n_layers: int = 16
    n_heads: int = 16
    n_kv_heads: int = 8
    d_ff: int = 5632
    n_experts: int = 8
    experts_per_token: int = 2
    # None: dropless (serving, the plain forward). A number: GShard
    # capacity dispatch, which drops what overflows an expert; for
    # training with the experts sharded over `ep`.
    capacity_factor: Optional[float] = None
    # "topk_softmax" (Mixtral) or "softmax_topk" (OLMoE), ops/moe.py:route
    routing: str = "topk_softmax"
    norm_topk_prob: bool = False
    qk_norm: bool = False
    max_seq_len: int = 2048
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    router_aux_coef: float = 0.01
    router_z_coef: float = 1e-3
    remat: bool = False
    dtype: Any = jnp.bfloat16
    # storage dtype of embeddings and matmul kernels (as LlamaConfig);
    # norm weights and the router stay float32
    param_dtype: Any = jnp.float32
    attn_impl: str = "auto"

    def __post_init__(self):
        if self.routing not in ROUTINGS:
            raise ValueError(f"routing={self.routing!r}; valid: "
                             f"{ROUTINGS}")
        if self.experts_per_token > self.n_experts:
            raise ValueError(
                f"experts_per_token={self.experts_per_token} exceeds "
                f"n_experts={self.n_experts}")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    def attn_cfg(self) -> LlamaConfig:
        """The attention sub-config shared with the Llama blocks."""
        return LlamaConfig(
            vocab_size=self.vocab_size, d_model=self.d_model,
            n_layers=self.n_layers, n_heads=self.n_heads,
            n_kv_heads=self.n_kv_heads, d_ff=self.d_ff,
            max_seq_len=self.max_seq_len, rope_theta=self.rope_theta,
            norm_eps=self.norm_eps, dtype=self.dtype,
            param_dtype=self.param_dtype, attn_impl=self.attn_impl,
            qk_norm=self.qk_norm)

    @staticmethod
    def mixtral_8x7b(**kw) -> "MixtralConfig":
        return MixtralConfig(**{**dict(
            vocab_size=32000, d_model=4096, n_layers=32, n_heads=32,
            n_kv_heads=8, d_ff=14336, n_experts=8, experts_per_token=2,
            max_seq_len=8192, remat=True), **kw})

    @staticmethod
    def olmoe_1b_7b(**kw) -> "MixtralConfig":
        """OLMoE-1B-7B (0924 and 0125), as published: 64 experts of
        width 1024, 8 a token, softmax over all 64 then top-8 without
        renormalising, q/k RMSNorm, plain MHA."""
        return MixtralConfig(**{**dict(
            vocab_size=50304, d_model=2048, n_layers=16, n_heads=16,
            n_kv_heads=16, d_ff=1024, n_experts=64, experts_per_token=8,
            routing="softmax_topk", norm_topk_prob=False, qk_norm=True,
            max_seq_len=4096, rope_theta=10000.0, norm_eps=1e-5), **kw})

    @staticmethod
    def debug(**kw) -> "MixtralConfig":
        return MixtralConfig(**{**dict(
            vocab_size=256, d_model=64, n_layers=2, n_heads=4,
            n_kv_heads=2, d_ff=128, n_experts=4, experts_per_token=2,
            max_seq_len=128), **kw})


class MoEMLP(nn.Module):
    """Top-k routed SwiGLU experts with stacked (E, ...) weights."""
    cfg: MixtralConfig

    @nn.compact
    def __call__(self, x, row_mask=None):
        cfg = self.cfg
        b, s, d = x.shape
        router_w = self.param(
            "router_kernel", nn.initializers.normal(0.02),
            (d, cfg.n_experts))
        # Stacked expert weights; names match sharding DEFAULT_RULES so the
        # expert axis lands on `ep` and the ff dims on fsdp/tp.
        # the expert axis is a batch axis: each expert's fan-in is d (or
        # d_ff), not E times that
        init = nn.initializers.lecun_normal(batch_axis=(0,))
        w_gate = self.param("experts_gate_kernel", init,
                            (cfg.n_experts, d, cfg.d_ff), cfg.param_dtype)
        w_up = self.param("experts_up_kernel", init,
                          (cfg.n_experts, d, cfg.d_ff), cfg.param_dtype)
        w_down = self.param("experts_down_kernel", init,
                            (cfg.n_experts, cfg.d_ff, d), cfg.param_dtype)

        tokens = x.reshape(b * s, d).astype(cfg.dtype)
        k = cfg.experts_per_token
        dropless = cfg.capacity_factor is None
        with jax.named_scope("moe.route"):
            router_logits = jnp.einsum(
                "gd,de->ge", tokens.astype(jnp.float32),
                router_w.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
            if dropless:
                weights, top_idx = route(router_logits, k, cfg.routing,
                                         cfg.norm_topk_prob)
        if dropless:
            out, stats = moe_dropless(
                tokens, weights, top_idx, w_gate, w_up, w_down,
                None if row_mask is None else row_mask.reshape(b * s))
            aux = router_aux(router_logits, top_idx)
            self.sow("step_stats", "moe", stats)
            # which experts each position chose, for a reference check
            self.sow("routing", "top_idx", top_idx.reshape(b, s, k))
        else:
            def expert_fn(batch):   # (E, C, d) -> (E, C, d)
                gate = jnp.einsum("ecd,edf->ecf", batch,
                                  w_gate.astype(cfg.dtype))
                up = jnp.einsum("ecd,edf->ecf", batch,
                                w_up.astype(cfg.dtype))
                return jnp.einsum("ecf,efd->ecd", swiglu(gate, up),
                                  w_down.astype(cfg.dtype))

            out, aux = moe_dispatch_combine(
                tokens, router_logits, expert_fn, k=k,
                capacity_factor=cfg.capacity_factor, routing=cfg.routing,
                norm_topk_prob=cfg.norm_topk_prob)
        self.sow("aux_loss", "router",
                 cfg.router_aux_coef * aux.load_balance_loss
                 + cfg.router_z_coef * aux.router_z_loss)
        return out.reshape(b, s, d).astype(cfg.dtype)


class MixtralBlock(nn.Module):
    cfg: MixtralConfig

    @nn.compact
    def __call__(self, x, cos, sin, cache=None, positions=None,
                 row_mask=None):
        cfg = self.cfg
        attn_norm_w = self.param("attn_norm", nn.initializers.ones,
                                 (cfg.d_model,))
        mlp_norm_w = self.param("mlp_norm", nn.initializers.ones,
                                (cfg.d_model,))
        h, new_cache = LlamaAttention(cfg.attn_cfg(), name="attention")(
            rms_norm(x, attn_norm_w, cfg.norm_eps), cos, sin, cache,
            positions)
        x = x + h
        x = x + MoEMLP(cfg, name="moe")(
            rms_norm(x, mlp_norm_w, cfg.norm_eps), row_mask)
        return x, new_cache


class Mixtral(nn.Module):
    """tokens (B, S) -> (logits, cache). Same calling convention as Llama
    so the serve engine and trainers are model-family agnostic.

    The summed router aux loss is exposed via the "aux_loss" collection:
    `model.apply(vars, tokens, mutable=["aux_loss"])`. Under
    `mutable=["step_stats"]` every dropless expert layer leaves the int32
    vector `step_stats` names (ops/moe.py:MOE_STATS), counted over the
    rows `row_mask` (B, S) marks as real (all of them if None): the
    others are bucket padding or empty slots and are given to no expert.
    `moe_assignments` counts the (row, expert) pairs that ran here; these
    models hold every expert, so it equals `moe_routed_assignments`
    (`moe_dropless` may also hold a share of a layer's experts: that is
    models/latent_moe.py).
    """
    cfg: MixtralConfig
    step_stats = MOE_STATS

    @nn.compact
    def __call__(self, tokens, cache=None, positions=None, row_mask=None):
        cfg = self.cfg
        embed = nn.Embed(cfg.vocab_size, cfg.d_model, name="token_embed",
                         dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         embedding_init=nn.initializers.normal(0.02))
        x = embed(tokens)
        cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len,
                                    cfg.rope_theta)
        block_cls = (nn.remat(MixtralBlock)
                     if (cfg.remat and cache is None) else MixtralBlock)
        new_cache = []
        for i in range(cfg.n_layers):
            block = block_cls(cfg, name=f"layer_{i}")
            x, c = block(x, cos, sin,
                         None if cache is None else cache[i], positions,
                         row_mask)
            new_cache.append(c)
        final_w = self.param("final_norm", nn.initializers.ones,
                             (cfg.d_model,))
        x = rms_norm(x, final_w, cfg.norm_eps)
        logits = _LMHead(cfg.vocab_size, cfg.param_dtype,
                         name="lm_head")(x)
        return logits, (new_cache if cache is not None else None)

    def init_params(self, rng, batch=1, seq=8):
        tokens = jnp.zeros((batch, seq), dtype=jnp.int32)
        return self.init(rng, tokens)["params"]

    def empty_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16):
        cfg = self.cfg
        return [
            (jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.head_dim),
                       dtype=dtype),
             jnp.zeros((batch, max_len, cfg.n_kv_heads, cfg.head_dim),
                       dtype=dtype),
             jnp.zeros((batch,), dtype=jnp.int32))
            for _ in range(cfg.n_layers)
        ]

    @staticmethod
    def aux_loss(mutables) -> jax.Array:
        """Sum the sown per-layer router losses from `mutable=["aux_loss"]`."""
        leaves = jax.tree_util.tree_leaves(mutables.get("aux_loss", {}))
        if not leaves:
            return jnp.float32(0.0)
        return sum(jnp.sum(leaf) for leaf in leaves)
