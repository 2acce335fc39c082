"""Plain reference of the Mistral-7B forward pass (and its loss), kept
with the benchmark: jax.numpy in float32 under
`default_matmul_precision("highest")`, no cache, no kernels, no batching
tricks. It follows the published model (HF `MistralForCausalLM`):
RMSNorm -> GQA attention with rotary embedding (half-split rotation, as
HF's `rotate_half`) -> residual -> RMSNorm -> SwiGLU -> residual, a final
RMSNorm and an untied head. No sliding window (v0.3 has none).

Weights are read from the system's own parameter tree (flax names of
ray_tpu/models/llama.py), one layer cast to float32 at a time so that
the reference fits beside the served model.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    # x: (S, H, D); rotate halves (x1, x2) by position * inv_freq
    s, _h, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def layer_forward(x, p, m: dict):
    """One decoder layer on x (S, hidden) with float32 weights p."""
    s = x.shape[0]
    nh, nkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    a = p["attention"]
    h = _rms(x, p["attn_norm"], eps)
    q = (h @ a["q_proj"]["kernel"]).reshape(s, nh, hd)
    k = (h @ a["k_proj"]["kernel"]).reshape(s, nkv, hd)
    v = (h @ a["v_proj"]["kernel"]).reshape(s, nkv, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    rep = nh // nkv
    causal = jnp.tril(jnp.ones((s, s), bool))

    def group(qkv):
        # one key/value head and the `rep` query heads that share it; a
        # group at a time (and rematerialised in a backward pass) so that
        # the float32 (heads, S, S) scores of a 4096-token sequence fit
        qg, kg, vg = qkv                       # (rep, S, D), (S, D), (S, D)
        scores = jnp.einsum("rqd,kd->rqk", qg, kg) / jnp.sqrt(F32(hd))
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return jnp.einsum("rqk,kd->rqd", jax.nn.softmax(scores, -1), vg)

    qg = q.transpose(1, 0, 2).reshape(nkv, rep, s, hd)
    attn = jax.lax.map(jax.checkpoint(group),
                       (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    attn = attn.reshape(nh, s, hd).transpose(1, 0, 2)      # (S, H, D)
    x = x + attn.reshape(s, nh * hd) @ a["o_proj"]["kernel"]
    h = _rms(x, p["mlp_norm"], eps)
    mlp = p["mlp"]
    gate = h @ mlp["gate_proj"]["kernel"]
    up = h @ mlp["up_proj"]["kernel"]
    return x + (jax.nn.silu(gate) * up) @ mlp["down_proj"]["kernel"]


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(F32), tree)


def forward_logits(params, tokens, m: dict, last: int | None = None):
    """Logits (S or last, vocab) in float32 for one sequence of token
    ids. Each layer is its own jitted call on that layer's weights cast
    to float32, so only one float32 layer is live at a time."""
    with jax.default_matmul_precision("highest"):
        x = params["token_embed"]["embedding"][tokens].astype(F32)
        step = jax.jit(lambda x, p: layer_forward(x, _f32(p), m))
        for i in range(m["num_hidden_layers"]):
            x = step(x, params[f"layer_{i}"])
        if last is not None:
            x = x[-last:]
        x = _rms(x, params["final_norm"].astype(F32), m["rms_norm_eps"])
        return x @ params["lm_head"]["kernel"].astype(F32)


def sequence_loss(params, tokens, m: dict):
    """Mean next-token cross-entropy of one sequence (S+1 ids), float32,
    differentiable; each layer is rematerialised so that the float32
    backward pass fits."""
    with jax.default_matmul_precision("highest"):
        x = params["token_embed"]["embedding"][tokens[:-1]].astype(F32)
        layer = jax.checkpoint(lambda x, p: layer_forward(x, _f32(p), m))
        for i in range(m["num_hidden_layers"]):
            x = layer(x, params[f"layer_{i}"])
        x = _rms(x, params["final_norm"].astype(F32), m["rms_norm_eps"])
        logits = x @ params["lm_head"]["kernel"].astype(F32)
        logp = jax.nn.log_softmax(logits, -1)
        return -jnp.mean(jnp.take_along_axis(
            logp, tokens[1:, None], axis=-1))
