"""Hybrid decoders: a mixer a layer, chosen by `layer_types`, and a
feed-forward a layer, chosen by the layer's index (or by `ff_types`,
among dense / experts / none), TPU-first. Four families: `olmo_hybrid`
(Olmo-Hybrid-7B), `lfm2_moe` (LFM2-24B-A2B), `solar_open2`
(Solar-Open2-250B) and `nemotron_h` (Nemotron-3-Super-120B-A12B).

A block is a mixer and a feed-forward around the residual stream. The
mixer of layer i is what `layer_types[i]` names:

  * "full_attention": `LlamaAttention` (models/llama.py). `olmo_hybrid`
    uses its `qk_norm` arm over the whole projected q and k and no
    rotation (the family's `rope_theta` is null: its full layers see no
    positions); `lfm2_moe` normalises each head's width of q and of k
    (`qk_norm="head"`) and then rotates (`rope_theta`); `solar_open2`
    has grouped heads wider than d_model / n_heads (`attn_head_dim`), no
    norm, no rotation and an output gate (`out_gate`):
    y = W_o [softmax(q k^T / sqrt(hd)) v * sigmoid(W_gate x)];
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
    everything that touches it in float32;
  * "kda": `KimiDeltaAttention`, the delta rule with a decay a key
    CHANNEL (Kimi Linear, arXiv:2510.26692), H heads of d_k = d_v, r =
    `kda_rank` the width of the two low-rank pairs:
        [q~ | k~ | v~] = SiLU(conv_K(W_qkv x))    depthwise, causal
        q = l2norm(q~) d_k^-1/2, k = l2norm(k~)   over each head's d_k
        g = -exp(A_log_h) softplus(W_f2 (W_f1 x) + dt_bias)  (H, d_k)
        beta = sigmoid(w_b x) (x 2 if allow_neg_eigval)      a head
        S <- S Diag(exp g) (I - beta k k^T) + beta v k^T,  o = S q
        y = W_o [RMSNorm_dv(o_h) * sigmoid(W_g2 (W_g1 x))_h]
    the same two forms and the same slot state as "linear_attention"
    (the step kernel is `kda_decode_step`);
  * "conv": `ShortConv`, a gated short convolution of width K =
    `conv_kernel` over the model's width d, no bias, no activation:
        [B | C | X] = W_in x                      three blocks of d
        z = B * X
        c_t = sum_j w_j z_{t-j}, j = 0..K-1       depthwise, causal
        y = W_out (C * c)
    Its memory is z at the sequence's last K - 1 positions.
  * "mamba2": `Mamba2`, the selective state-space layer of ops/ssm.py
    (Dao, Gu, arXiv:2405.21060). H = `ssm_n_heads` heads of P =
    `ssm_head_dim`, G = `ssm_groups` groups of N = `ssm_state` state
    channels, head h reading group h // (H / G):
        [z | u | dt~] = W_in x          H P | H P + 2 G N | H columns
        [xs | B | C] = SiLU(conv_K(u) + b_conv)   depthwise, causal
        dt = softplus(dt~ + dt_bias), a = exp(dt A), A = -exp(A_log)
        S_h <- a_h S_h + (dt_h xs_h) B_g^T,  y_h = S_h C_g + D_h xs_h
        out = W_out RMSNorm_G(y * SiLU(z))   the gate first, a norm a
                                             group of H P / G channels
    the delta rule without its correction (k = B, q = C, v = xs,
    beta = dt), over the same slot state (N, H x P) float32 and the
    same two forms (the step kernel is `ssm_decode_step`).

`nemotron_h` publishes ONE sub-layer a layer, a mixer or a feed-forward
behind one norm and one residual add. A pre-norm block here, h = x +
Mixer(Norm x), y = h + FF(Norm h), is exactly two such layers where the
pattern has a mixer and then experts, and one where the feed-forward is
"none" (a mixer followed by a mixer): `nemotron_blocks` pairs a
published pattern so, one cache entry a block as everywhere.

The feed-forward of layer i is a dense SwiGLU (`LlamaMLP`, width `d_ff`)
where `n_dense_layers` is None or i < `n_dense_layers`, and otherwise
the expert layer of models/latent_moe.py (`ShareMoE`: sigmoid scores, a
selection bias, `n_experts` SwiGLU experts of width `d_expert`,
`experts_per_token` a token; `lfm2_moe` holds them all and shares none,
`solar_open2` holds `expert_count` from `expert_first`, a chip's share
of an expert-parallel deployment, beside `n_shared_experts` shared;
`nemotron_h` computes its routed experts, two matmuls and relu^2 each,
in a latent of `moe_latent_dim` beside a shared expert of `d_shared` at
full width), which leaves the `step_stats` counters of ops/moe.py.

Where the norms stand is the family's (`pre_norm`): `olmo_hybrid`
normalises each sub-layer's OUTPUT, as the OLMo 2 and 3 family does:
h = x + Norm(Mixer(x)), y = h + Norm(FF(h)); `lfm2_moe` and `solar_open2`
its input: h = x + Mixer(Norm(x)), y = h + FF(Norm(h)).

What a layer caches it says itself (`paged_cache_spec`): a full layer
pages K and V a token (heads narrower than 128 lanes packed side by
side: ops/attention.py:packed_kv_shape), a linear layer keeps a state
and the convolution's last K - 1 inputs a SLOT, a conv layer its last
K - 1 inputs a slot (ops/attention.py:SlotState), those inputs side by
side in one row of (K - 1) x C lanes (ops/gated_deltanet.py:
causal_conv says why they are not K - 1 rows).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..ops import rms_norm, rope_frequencies
from ..ops import gated_deltanet as gdn
from ..ops import ssm
from ..ops.attention import (LayerCache, PagedKV, SlotState,
                             packed_kv_shape)
from ..ops.moe import MOE_STATS
from ..util import knobs
from .latent_moe import ShareMoE
from .llama import LlamaAttention, LlamaMLP, _LMHead, _proj, head_logits

LINEAR, FULL, CONV = "linear_attention", "full_attention", "conv"
KDA = "kda"
MAMBA2 = "mamba2"
DENSE, EXPERTS, NO_FF = "dense", "experts", "none"


# Nemotron-3-Super-120B-A12B's `hybrid_override_pattern`, as published
NEMOTRON_3_SUPER_PATTERN = (
    "MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
    "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")


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
    kda_rank: int = 128             # r of the "kda" layers' low-rank pairs
    # the "mamba2" layers': H heads of P, G groups of N state channels
    ssm_n_heads: int = 128
    ssm_head_dim: int = 64
    ssm_state: int = 128
    ssm_groups: int = 8
    ssm_conv_kernel: int = 4
    ssm_chunk: int = 128            # tokens a chunk of the chunkwise form
    max_seq_len: int = 65536
    norm_eps: float = 1e-6
    # LlamaAttention reads these two: True, the whole projected q and k;
    # "head", each head's width. None: no rotation
    qk_norm: "bool | str" = True
    rope_theta: Optional[float] = None
    out_gate: bool = False          # LlamaAttention's sigmoid output gate
    # a full layer's head width where it is not d_model / n_heads
    attn_head_dim: Optional[int] = None
    conv_kernel: int = 3            # K of the "conv" layers
    # False: each sub-layer's output is normalised; True: its input
    pre_norm: bool = False
    tie_embeddings: bool = False    # the head is the embedding
    # leading layers with a dense SwiGLU of d_ff; the layers after them
    # are expert layers (models/latent_moe.py:ShareMoE reads the fields
    # below). None: every layer is dense
    n_dense_layers: Optional[int] = None
    # one entry a layer, "dense", "experts" or "none" (the block is its
    # mixer alone); None: by `n_dense_layers`
    ff_types: Optional[Tuple[str, ...]] = None
    d_expert: int = 1536
    n_experts: int = 64
    experts_per_token: int = 4
    norm_topk_prob: bool = True
    route_norm_eps: float = 1e-6
    routed_scaling: float = 1.0
    # what ShareMoE asks beside: shared experts every token passes, and
    # the share of each expert layer held here, experts expert_first ..
    # expert_first + expert_count - 1 of the router's n_experts (None:
    # all of them)
    n_shared_experts: int = 0
    expert_first: int = 0
    expert_count: Optional[int] = None
    # ShareMoE's further arms: routed experts computed in a latent of
    # this width (None: at d_model); experts of two matmuls and relu^2
    # (False) instead of SwiGLU; the shared expert's width (None:
    # n_shared_experts x d_expert)
    moe_latent_dim: Optional[int] = None
    expert_gated: bool = True
    d_shared: Optional[int] = None
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
        bad = set(self.layer_types) - {LINEAR, FULL, CONV, KDA, MAMBA2}
        if bad or len(self.layer_types) != self.n_layers:
            raise ValueError(
                f"layer_types must name {self.n_layers} layers as "
                f"{LINEAR!r}, {FULL!r} or {CONV!r} (or {KDA!r}, "
                f"{MAMBA2!r}); got {self.layer_types}")
        if self.ff_types is not None:
            object.__setattr__(self, "ff_types", tuple(self.ff_types))
            if set(self.ff_types) - {DENSE, EXPERTS, NO_FF} \
                    or len(self.ff_types) != self.n_layers:
                raise ValueError(
                    f"ff_types must name {self.n_layers} layers as "
                    f"{DENSE!r}, {EXPERTS!r} or {NO_FF!r}; got "
                    f"{self.ff_types}")
        if not (0 <= self.expert_first and self.expert_first
                + self.experts_held <= self.n_experts):
            raise ValueError(
                f"experts {self.expert_first}.."
                f"{self.expert_first + self.experts_held} are not among "
                f"the router's {self.n_experts}")
        if self.d_model % self.n_heads or self.n_heads % self.n_kv_heads:
            raise ValueError("d_model / n_heads / n_kv_heads do not divide")

    @property
    def head_dim(self) -> int:
        return self.attn_head_dim or self.d_model // self.n_heads

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

    @property
    def ssm_conv_width(self) -> int:
        """Columns of a "mamba2" layer's convolved xs | B | C."""
        return (self.ssm_n_heads * self.ssm_head_dim
                + 2 * self.ssm_groups * self.ssm_state)

    def ff_kind(self, i: int) -> str:
        """Layer i's feed-forward: "dense", "experts" or "none"."""
        if self.ff_types is not None:
            return self.ff_types[i]
        return DENSE if self.n_dense_layers is None \
            or i < self.n_dense_layers else EXPERTS

    def dense_ff(self, i: int) -> bool:
        """Whether layer i's feed-forward is the dense SwiGLU."""
        return self.ff_kind(i) == DENSE

    @property
    def experts_held(self) -> int:
        return (self.n_experts if self.expert_count is None
                else self.expert_count)

    @staticmethod
    def olmo_hybrid_7b(**kw) -> "HybridConfig":
        return HybridConfig(**kw)

    @staticmethod
    def lfm2_24b_a2b(**kw) -> "HybridConfig":
        """LFM2-24B-A2B as published (config.json, model_type lfm2_moe):
        40 layers, a full-attention layer at every index that is 2
        modulo 4 and gated short convolutions between them; fewer
        `n_layers` keep the first of them."""
        n = kw.get("n_layers", 40)
        return HybridConfig(**{**dict(
            vocab_size=65536, d_model=2048, n_layers=n,
            layer_types=tuple(FULL if i % 4 == 2 else CONV
                              for i in range(n)),
            n_heads=32, n_kv_heads=8, d_ff=11776, conv_kernel=3,
            n_dense_layers=2, d_expert=1536, n_experts=64,
            experts_per_token=4, norm_topk_prob=True, routed_scaling=1.0,
            max_seq_len=128000, norm_eps=1e-5, qk_norm="head",
            rope_theta=1e6, pre_norm=True, tie_embeddings=True), **kw})

    @staticmethod
    def solar_open2_250b(**kw) -> "HybridConfig":
        """Solar-Open2-250B as published (config.json, model_type
        solar_open2): 48 layers, softmax attention at every index that
        is 0 modulo 4 (`gqa_layers`), 64 query and 8 KV heads of 128
        without rotation and with an output gate, and three "kda"
        layers after each; every feed-forward 320 routed experts of
        1 280, 8 a token, beside a shared one; fewer `n_layers` keep
        the first of them."""
        n = kw.get("n_layers", 48)
        return HybridConfig(**{**dict(
            vocab_size=196608, d_model=4096, n_layers=n,
            layer_types=tuple(FULL if i % 4 == 0 else KDA
                              for i in range(n)),
            n_heads=64, n_kv_heads=8, attn_head_dim=128, out_gate=True,
            qk_norm=False, rope_theta=None, linear_n_heads=64,
            linear_key_dim=128, linear_value_dim=128, linear_conv_kernel=4,
            linear_allow_neg_eigval=True, kda_rank=128, n_dense_layers=0,
            d_expert=1280, n_experts=320, experts_per_token=8,
            n_shared_experts=1, norm_topk_prob=True, routed_scaling=1.0,
            max_seq_len=1048576, norm_eps=1e-5, pre_norm=True,
            # on a TPU the flash kernel in every prefill bucket, not
            # from 2 048 tokens as "auto" has it: a step program with
            # delta-rule layers behind XLA's plain attention over 2 x
            # 1 024 tokens never came back from the chip, though either
            # kind of layer alone at that shape did (ROADMAP A1 (c))
            attn_impl=("pallas" if jax.default_backend() == "tpu"
                       else "auto")), **kw})

    @staticmethod
    def solar_debug(**kw) -> "HybridConfig":
        return HybridConfig.solar_open2_250b(**{**dict(
            vocab_size=256, d_model=64, n_layers=4, n_heads=4,
            n_kv_heads=2, attn_head_dim=32, linear_n_heads=4,
            linear_key_dim=16, linear_value_dim=16, linear_chunk=16,
            kda_rank=8, d_expert=32, n_experts=8, experts_per_token=2,
            max_seq_len=256), **kw})

    @staticmethod
    def nemotron_blocks(pattern: str) -> Tuple[tuple, tuple]:
        """(layer_types, ff_types) of the blocks that a published
        `hybrid_override_pattern` pairs into (module docstring): `M` a
        Mamba-2 layer, `*` attention, `E` experts, `-` a dense
        feed-forward; a feed-forward rides with the mixer before it."""
        mixers, ffs = [], []
        for at, c in enumerate(pattern):
            if c in "M*":
                mixers.append(MAMBA2 if c == "M" else FULL)
                ffs.append(NO_FF)
            elif c in "E-" and ffs and ffs[-1] == NO_FF:
                ffs[-1] = EXPERTS if c == "E" else DENSE
            else:
                raise ValueError(
                    f"{pattern!r}: {c!r} at {at} is no mixer and follows "
                    "none: a block is a mixer and at most one "
                    "feed-forward")
        return tuple(mixers), tuple(ffs)

    @staticmethod
    def nemotron_3_super_120b(pattern: str = NEMOTRON_3_SUPER_PATTERN,
                              **kw) -> "HybridConfig":
        """Nemotron-3-Super-120B-A12B as published (config.json,
        model_type nemotron_h): 88 one-sub-layer layers by
        `hybrid_override_pattern` (40 Mamba-2, 8 attention, 40 expert
        layers), paired into 48 blocks; 32 query heads over 2 KV heads of
        128 without rotation; 512 relu^2 experts of 2 688 in a latent of
        1 024, 22 a token, scaling 5, beside a shared expert of 5 376 at
        full width. A shorter `pattern` keeps those layers (`n_layers`
        is its blocks). The multi-token-prediction module is not built
        (docs/SERVING.md)."""
        mixers, ffs = HybridConfig.nemotron_blocks(pattern)
        return HybridConfig(**{**dict(
            vocab_size=131072, d_model=4096, n_layers=len(mixers),
            layer_types=mixers, ff_types=ffs, n_heads=32, n_kv_heads=2,
            attn_head_dim=128, qk_norm=False, rope_theta=None,
            ssm_n_heads=128, ssm_head_dim=64, ssm_state=128, ssm_groups=8,
            ssm_conv_kernel=4, ssm_chunk=128, d_expert=2688, n_experts=512,
            experts_per_token=22, n_shared_experts=1, d_shared=5376,
            moe_latent_dim=1024, expert_gated=False, norm_topk_prob=True,
            route_norm_eps=1e-20, routed_scaling=5.0, max_seq_len=262144,
            norm_eps=1e-5, pre_norm=True,
            # as Solar-Open2's: the flash kernel in every prefill bucket
            # on a TPU (ROADMAP A1 (c))
            attn_impl=("pallas" if jax.default_backend() == "tpu"
                       else "auto")), **kw})

    @staticmethod
    def nemotron_debug(**kw) -> "HybridConfig":
        return HybridConfig.nemotron_3_super_120b(
            kw.pop("pattern", "MEMEMEM*EME"), **{**dict(
                vocab_size=256, d_model=64, n_heads=4, n_kv_heads=2,
                attn_head_dim=32, ssm_n_heads=8, ssm_head_dim=16,
                ssm_state=16, ssm_groups=2, ssm_chunk=16, d_expert=32,
                d_shared=48, moe_latent_dim=32, n_experts=8,
                experts_per_token=3, max_seq_len=256), **kw})

    @staticmethod
    def debug(**kw) -> "HybridConfig":
        return HybridConfig(**{**dict(
            vocab_size=256, d_model=64, n_layers=4, n_heads=4,
            n_kv_heads=4, d_ff=128, linear_n_heads=4, linear_key_dim=8,
            linear_value_dim=16, linear_chunk=8, max_seq_len=256), **kw})

    @staticmethod
    def lfm2_debug(**kw) -> "HybridConfig":
        return HybridConfig.lfm2_24b_a2b(**{**dict(
            vocab_size=256, d_model=64, n_layers=5, n_heads=4,
            n_kv_heads=2, d_ff=128, n_dense_layers=1, d_expert=32,
            n_experts=8, experts_per_token=2, max_seq_len=256), **kw})


def _uniform(bound: float):
    def init(key, shape, dtype=jnp.float32):
        return jax.random.uniform(key, shape, dtype, -bound, bound)
    return init


def _a_log_init(key, shape, dtype=jnp.float32):
    """log A, A uniform in (0, 16): the published kernels' draw."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-3, 16.0))


def _ssm_a_log_init(key, shape, dtype=jnp.float32):
    """log A, A uniform in (1, 16): Mamba-2's draw."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """softplus^-1 of a step drawn log-uniform in (0.001, 0.1)."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, jnp.log(1e-3),
                                    jnp.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def _delta_rule(mod, x, cache, u, gate, g, beta, scope: str, gate_fn):
    """What the two delta-rule mixers share once the fused q | k | v
    projection `u`, the output gate's `gate` and the gates `g`, `beta`
    are made: the convolution and the norms, the recurrence in one of
    its two forms against the slot state (frozen behind each row's true
    length), the normed and gated output. `scope` prefixes the named
    scopes; `g`'s rank says whether the decay is a head's or a
    channel's (ops/gated_deltanet.py)."""
    cfg = mod.cfg
    h, dk, dv = (cfg.linear_n_heads, cfg.linear_key_dim,
                 cfg.linear_value_dim)
    b, s, _ = x.shape
    conv_w = mod.param(
        "conv_kernel", _uniform(cfg.linear_conv_kernel ** -0.5),
        (cfg.linear_conv_kernel, cfg.conv_width), cfg.param_dtype)
    state = tail = n_new = None
    if cache is not None:
        state, tail = cache.read()
        n_new = cache.n_new
        g, beta = gdn.freeze(
            g, beta, jnp.arange(s)[None, :] < n_new[:, None])
    # a decode step is the one-token form on (B, H, .) arrays: an axis
    # of one token in the tiled second-minor place costs a re-tiling of
    # everything that carries it (ops/gated_deltanet.py:causal_conv)
    one = cache is not None and s == 1
    lead = (b,) if one else (b, s)
    with jax.named_scope(f"{scope}.conv"):
        qkv, tail = gdn.causal_conv(u, conv_w, tail, n_new)
        q, k, v = jnp.split(qkv[:, 0] if one else qkv,
                            [h * dk, 2 * h * dk], axis=-1)
        q = gdn.l2norm(q.reshape(*lead, h, dk)) * dk ** -0.5
        k = gdn.l2norm(k.reshape(*lead, h, dk))
        v = v.reshape(*lead, h, dv)
    if one:
        with jax.named_scope(f"{scope}.step"):
            o, state = _step(q, k, v, g[:, 0], beta[:, 0], state)
            q, k, v, o = (x[:, None] for x in (q, k, v, o))
    else:
        with jax.named_scope(f"{scope}.scan"):
            o, state = _scan(q, k, v, g, beta, state, n_new,
                             cfg.linear_chunk)
    # what went into the recurrence and what came out, for a caller that
    # asks for the collection (a check of the recurrence alone on
    # bit-equal inputs); nothing is traced for one that does not
    mod.sow("recurrence", "io", (q, k, v, g, beta, o))
    with jax.named_scope(f"{scope}.gate_out"):
        o = rms_norm(o, mod.param("o_norm", nn.initializers.ones,
                                  (dv,)), cfg.norm_eps)
        o = (o.reshape(b, s, h * dv)
             * gate_fn(gate.astype(jnp.float32))).astype(cfg.dtype)
        y = _proj(cfg, cfg.d_model, "o_proj")(o)
    return y, (None if cache is None else cache.write(state, tail))


def _scan(q, k, v, g, beta, state, n_new, chunk: int):
    """The chunkwise form. A rate a key channel over a slot state on the
    TPU (serving: `n_new` rides with the cache entry) is the fused
    kernel, which does nothing for the chunks past a row's true length
    (ops/pallas/kda_prefill.py; inference only). Everything else, the
    plain forward and training among it, is the `jax.numpy` form, which
    has a backward."""
    if g.ndim == 4 and n_new is not None \
            and jax.default_backend() == "tpu":
        from ..ops.pallas.kda_prefill import kda_chunk_scan  # noqa: PLC0415
        return kda_chunk_scan(q, k, v, g, beta, state, n_new, chunk=chunk)
    return gdn.chunk_scan(q, k, v, g, beta, state, chunk=chunk)


def _step(q, k, v, g, beta, state, state_space: bool = False):
    """The one-token form: the fused kernel on the TPU (or under
    RAY_TPU_PAGED_ATTN_IMPL=pallas, interpreted on the CPU), plain XLA
    elsewhere (and under =gather). ONE kernel under three names: a rate
    a key channel (g with d_k behind the heads), a rate a head, and
    `state_space`, the recurrence of ops/ssm.py (no correction, q and k
    a group's)."""
    impl = knobs.get_str("RAY_TPU_PAGED_ATTN_IMPL")
    if impl != "gather" and (impl == "pallas"
                             or jax.default_backend() == "tpu"):
        from ..ops.pallas.gdn_decode import (  # noqa: PLC0415
            gdn_decode_step, kda_decode_step, ssm_decode_step)
        kernel = ssm_decode_step if state_space else \
            kda_decode_step if g.ndim == 3 else gdn_decode_step
        return kernel(q, k, v, g, beta, state)
    return (ssm.step if state_space else gdn.step)(q, k, v, g, beta, state)


class GatedDeltaNet(nn.Module):
    cfg: HybridConfig

    @nn.compact
    def __call__(self, x, cache: Optional[SlotState] = None):
        cfg = self.cfg
        h, dv = cfg.linear_n_heads, cfg.linear_value_dim
        with jax.named_scope("gdn.project"):
            u = _proj(cfg, cfg.conv_width, "qkv_proj")(x)
            gate = _proj(cfg, h * dv, "g_proj")(x)
            a = _proj(cfg, h, "a_proj")(x)
            bb = _proj(cfg, h, "b_proj")(x)
            g, beta = gdn.gates(
                a, bb, self.param("A_log", _a_log_init, (h,)),
                self.param("dt_bias", _dt_bias_init, (h,)),
                cfg.linear_allow_neg_eigval)
        return _delta_rule(self, x, cache, u, gate, g, beta, "gdn",
                           jax.nn.silu)


class KimiDeltaAttention(nn.Module):
    """The "kda" mixer (module docstring): GatedDeltaNet with a decay a
    key channel through a low-rank pair, and a sigmoid output gate
    through a second."""
    cfg: HybridConfig

    @nn.compact
    def __call__(self, x, cache: Optional[SlotState] = None):
        cfg = self.cfg
        h, dk, dv, r = (cfg.linear_n_heads, cfg.linear_key_dim,
                        cfg.linear_value_dim, cfg.kda_rank)
        b, s, _ = x.shape
        with jax.named_scope("kda.proj"):
            u = _proj(cfg, cfg.conv_width, "qkv_proj")(x)
            gate = _proj(cfg, h * dv, "g_b_proj")(
                _proj(cfg, r, "g_a_proj")(x))
            a = _proj(cfg, h * dk, "f_b_proj")(_proj(cfg, r, "f_a_proj")(x))
            bb = _proj(cfg, h, "b_proj")(x)
        with jax.named_scope("kda.gates"):
            g, beta = gdn.gates(
                a.reshape(b, s, h, dk), bb,
                self.param("A_log", _a_log_init, (h,)),
                self.param("dt_bias", _dt_bias_init, (h, dk)),
                cfg.linear_allow_neg_eigval)
        return _delta_rule(self, x, cache, u, gate, g, beta, "kda",
                           jax.nn.sigmoid)


class ShortConv(nn.Module):
    """The gated short convolution (module docstring). Its cache entry
    is a SlotState of one array, z at the sequence's last K - 1
    positions side by side in a row, carried by the function
    GatedDeltaNet's convolution uses."""
    cfg: HybridConfig

    @nn.compact
    def __call__(self, x, cache: Optional[SlotState] = None):
        cfg = self.cfg
        d = cfg.d_model
        with jax.named_scope("shortconv.project"):
            b_gate, c_gate, u = jnp.split(
                _proj(cfg, 3 * d, "in_proj")(x), 3, axis=-1)
            z = b_gate * u
        conv_w = self.param(
            "conv_kernel", _uniform(cfg.conv_kernel ** -0.5),
            (cfg.conv_kernel, d), cfg.param_dtype)
        tail = n_new = None
        if cache is not None:
            (tail,) = cache.read()
            n_new = cache.n_new
        with jax.named_scope("shortconv.conv"):
            c, tail = gdn.causal_conv(z, conv_w, tail, n_new,
                                      activation=None)
        with jax.named_scope("shortconv.out"):
            y = _proj(cfg, d, "out_proj")(c_gate * c)
        return y, (None if cache is None else cache.write(tail))


class Mamba2(nn.Module):
    """The "mamba2" mixer (module docstring). Its cache entry is the
    delta-rule layers': the float32 state (N, H x P) and the
    convolution's last K - 1 inputs a slot."""
    cfg: HybridConfig

    @nn.compact
    def __call__(self, x, cache: Optional[SlotState] = None):
        cfg = self.cfg
        h, p, n, grp = (cfg.ssm_n_heads, cfg.ssm_head_dim, cfg.ssm_state,
                        cfg.ssm_groups)
        inner, width = h * p, cfg.ssm_conv_width
        b, s, _ = x.shape
        with jax.named_scope("ssm.proj"):
            z, u, dt_raw = jnp.split(
                _proj(cfg, inner + width + h, "in_proj")(x),
                [inner, inner + width], axis=-1)
            g, beta = ssm.gates(
                dt_raw, self.param("A_log", _ssm_a_log_init, (h,)),
                self.param("dt_bias", _dt_bias_init, (h,)))
        conv_w = self.param(
            "conv_kernel", _uniform(cfg.ssm_conv_kernel ** -0.5),
            (cfg.ssm_conv_kernel, width), cfg.param_dtype)
        conv_b = self.param("conv_bias",
                            _uniform(cfg.ssm_conv_kernel ** -0.5), (width,))
        state = tail = n_new = None
        if cache is not None:
            state, tail = cache.read()
            n_new = cache.n_new
            g, beta = gdn.freeze(
                g, beta, jnp.arange(s)[None, :] < n_new[:, None])
        # a decode step is the one-token form on (B, .) arrays
        # (ops/gated_deltanet.py:causal_conv)
        one = cache is not None and s == 1
        lead = (b,) if one else (b, s)
        with jax.named_scope("ssm.conv"):
            xbc, tail = gdn.causal_conv(u, conv_w, tail, n_new, bias=conv_b)
            xs, bm, cm = jnp.split(xbc[:, 0] if one else xbc,
                                   [inner, inner + grp * n], axis=-1)
            xs = xs.reshape(*lead, h, p)
            bm = bm.reshape(*lead, grp, n)
            cm = cm.reshape(*lead, grp, n)
        if one:
            with jax.named_scope("ssm.step"):
                y, state = _step(cm, bm, xs, g[:, 0], beta[:, 0], state,
                                 state_space=True)
                cm, bm, xs, y = (a[:, None] for a in (cm, bm, xs, y))
        else:
            with jax.named_scope("ssm.scan"):
                y, state = ssm.chunk_scan(cm, bm, xs, g, beta, state,
                                          chunk=cfg.ssm_chunk)
        # as `_delta_rule`: the recurrence's inputs and output, for a
        # caller that asks for the collection
        self.sow("recurrence", "io", (cm, bm, xs, g, beta, y))
        with jax.named_scope("ssm.norm"):
            skip = self.param("D", nn.initializers.ones, (h,))
            y = y + skip.astype(jnp.float32)[:, None] * xs.astype(jnp.float32)
            y = ssm.gated_group_norm(
                y.reshape(b, s, inner), z,
                self.param("norm", nn.initializers.ones, (inner,)), grp,
                cfg.norm_eps).astype(cfg.dtype)
            out = _proj(cfg, cfg.d_model, "out_proj")(y)
        return out, (None if cache is None else cache.write(state, tail))


class HybridBlock(nn.Module):
    cfg: HybridConfig
    kind: str
    ff: str = DENSE

    @nn.compact
    def __call__(self, x, cos=None, sin=None, cache=None, positions=None,
                 row_mask=None):
        cfg = self.cfg
        mixer_w = self.param("attn_norm", nn.initializers.ones,
                             (cfg.d_model,))
        mlp_w = None if self.ff == NO_FF else self.param(
            "mlp_norm", nn.initializers.ones, (cfg.d_model,))

        def mixer(x):
            if self.kind == FULL:
                # cos = sin = None: no rotation
                return LlamaAttention(cfg, name="attention")(
                    x, cos, sin, cache, positions)
            if self.kind == CONV:
                return ShortConv(cfg, name="conv")(x, cache)
            if self.kind == KDA:
                return KimiDeltaAttention(cfg, name="kda")(x, cache)
            if self.kind == MAMBA2:
                return Mamba2(cfg, name="mamba2")(x, cache)
            return GatedDeltaNet(cfg, name="linear_attention")(x, cache)

        def ff(x):
            if self.ff == DENSE:
                return LlamaMLP(cfg, name="mlp")(x)
            return ShareMoE(cfg, name="moe")(x, row_mask)

        if cfg.pre_norm:
            h, new_cache = mixer(rms_norm(x, mixer_w, cfg.norm_eps))
            x = x + h
            if self.ff != NO_FF:
                x = x + ff(rms_norm(x, mlp_w, cfg.norm_eps))
        else:
            h, new_cache = mixer(x)
            x = x + rms_norm(h, mixer_w, cfg.norm_eps)
            if self.ff != NO_FF:
                x = x + rms_norm(ff(x), mlp_w, cfg.norm_eps)
        return x, new_cache


class Hybrid(nn.Module):
    """tokens (B, S) -> (logits, cache): Llama's calling convention, so
    that the serve engine is family agnostic. `cache` is None (the plain
    forward: every layer starts from nothing) or one entry a layer as
    `paged_cache_spec` says. A model with expert layers declares
    `step_stats` (ops/moe.py:MOE_STATS) and, under
    `mutable=["step_stats"]`, every expert layer leaves that vector,
    counted over the rows `row_mask` (B, S) marks as real.
    `return_hidden`: models/llama.py."""
    cfg: HybridConfig

    def head(self, params, hidden):
        """models/llama.py:Llama.head."""
        if self.cfg.tie_embeddings:
            return head_logits(
                hidden, embedding=params["token_embed"]["embedding"])
        return head_logits(hidden, kernel=params["lm_head"]["kernel"])

    @property
    def step_stats(self):
        cfg = self.cfg
        return MOE_STATS if EXPERTS in map(cfg.ff_kind,
                                           range(cfg.n_layers)) else ()

    @nn.compact
    def __call__(self, tokens, cache=None, positions=None, row_mask=None,
                 return_hidden: bool = False):
        cfg = self.cfg
        embed = nn.Embed(cfg.vocab_size, cfg.d_model, name="token_embed",
                         dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                         embedding_init=nn.initializers.normal(0.02))
        x = embed(tokens)
        cos = sin = None
        if cfg.rope_theta is not None:
            cos, sin = rope_frequencies(cfg.head_dim, cfg.max_seq_len,
                                        cfg.rope_theta)
        new_cache = []
        for i, kind in enumerate(cfg.layer_types):
            x, c = HybridBlock(cfg, kind, cfg.ff_kind(i),
                               name=f"layer_{i}")(
                x, cos, sin, None if cache is None else cache[i],
                positions, row_mask)
            new_cache.append(c)
        x = rms_norm(x, self.param("final_norm", nn.initializers.ones,
                                   (cfg.d_model,)), cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = head_logits(x, embedding=embed.embedding)
        else:
            logits = _LMHead(cfg.vocab_size, cfg.param_dtype,
                             name="lm_head")(x)
        out = (logits, new_cache if cache is not None else None)
        return out + (x,) if return_hidden else out

    def init_params(self, rng, batch=1, seq=8):
        return self.init(rng, jnp.zeros((batch, seq), jnp.int32))["params"]

    def chunk_scan_layers(self):
        """(delta-rule layers, tokens a chunk of their chunkwise form):
        what the serving engine counts prefill's chunks from
        (`prefill_chunks_window`, `prefill_chunks_live`)."""
        kinds = self.cfg.layer_types
        if MAMBA2 in kinds:
            return kinds.count(MAMBA2), self.cfg.ssm_chunk
        return (sum(kind in (LINEAR, KDA) for kind in kinds),
                self.cfg.linear_chunk)

    def paged_cache_spec(self):
        """A full layer pages K and V of `packed_kv_shape(kv_pool_heads,
        head_dim)` a token; a linear layer keeps, a slot, the float32
        state (d_k, H x d_v) and the convolution's last K - 1 inputs,
        one row of (K - 1) x C; a conv layer its last K - 1 inputs,
        likewise (ops/attention.py:kv_cache_spec). A "kda" layer keeps
        what a linear layer does, and a "mamba2" layer the same two at
        its own widths: the state (N, H x P)."""
        cfg = self.cfg
        kv = packed_kv_shape(cfg.kv_pool_heads, cfg.head_dim)
        linear = LayerCache(
            SlotState,
            ((cfg.linear_key_dim,
              cfg.linear_n_heads * cfg.linear_value_dim),
             ((cfg.linear_conv_kernel - 1) * cfg.conv_width,)),
            (jnp.float32, cfg.dtype), by_slot=True)
        by_kind = {
            FULL: LayerCache(PagedKV, (kv, kv), (cfg.dtype, cfg.dtype)),
            LINEAR: linear, KDA: linear,
            MAMBA2: LayerCache(
                SlotState,
                ((cfg.ssm_state, cfg.ssm_n_heads * cfg.ssm_head_dim),
                 ((cfg.ssm_conv_kernel - 1) * cfg.ssm_conv_width,)),
                (jnp.float32, cfg.dtype), by_slot=True),
            CONV: LayerCache(
                SlotState, (((cfg.conv_kernel - 1) * cfg.d_model,),),
                (cfg.dtype,), by_slot=True)}
        return [by_kind[kind] for kind in cfg.layer_types]
