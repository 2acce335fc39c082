"""The benchmark's side of a sarvam-105b replica (latent attention, an
expert share, a shared expert): `BenchServer` with the LatentMoE model
factory and the comparison against `reference_sarvam`. Everything else
(warm-up, sampler, trace, stats) is `BenchServer`'s. The program's
`LatentMoEConfig.sarvam_105b` preset is looked up before anything is
built: a program without it cannot run this configuration and says so
at once.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

from . import reference_sarvam
from .replica import BenchServer

MODEL_KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
              "head_dim", "kv_lora_rank", "q_head_dim", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "intermediate_size",
              "moe_intermediate_size", "num_experts", "num_experts_per_tok",
              "num_shared_experts", "first_k_dense_replace",
              "routed_scaling_factor", "rope_theta", "rope_scaling",
              "rms_norm_eps", "vocab_size", "max_position_embeddings",
              "tie_word_embeddings")


def model_section(cfg: dict) -> dict:
    """The published keys the program, the reference and the cost
    arithmetic read, and the share: `num_experts` experts held from
    `expert_first` of a router `router_width` wide."""
    missing = [k for k in MODEL_KEYS + ("expert_parallel",) if k not in cfg]
    if missing:
        raise SystemExit(f"benchmark: {cfg['name']}.json lacks {missing}")
    if (cfg["head_dim"] != cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
            or cfg["q_head_dim"] != cfg["qk_nope_head_dim"]
            + cfg["qk_rope_head_dim"] or cfg["tie_word_embeddings"]):
        raise SystemExit("benchmark: models/latent_moe.py caches kv_lora_rank"
                         " + qk_rope_head_dim a token and has an untied "
                         "head; this file disagrees")
    ep = cfg["expert_parallel"]
    if ep["router_width"] != ep["ways"] * cfg["num_experts"]:
        raise SystemExit("benchmark: the experts held times the ways of "
                         "expert parallelism is not the router's width")
    return {**{k: cfg[k] for k in MODEL_KEYS},
            "router_width": ep["router_width"],
            "expert_first": ep["rank"] * cfg["num_experts"]}


def sarvam_preset():
    """The program's preset, or a clean failure where it has none."""
    try:
        from ray_tpu.models import LatentMoEConfig
    except ImportError:
        LatentMoEConfig = None
    preset = getattr(LatentMoEConfig, "sarvam_105b", None)
    if preset is None:
        raise SystemExit(
            "benchmark: this program has no LatentMoEConfig.sarvam_105b "
            "(latent attention over a paged pool of latents, an expert "
            "share, a shared expert, biased sigmoid routing): it cannot "
            "run a sarvam-105b configuration")
    return preset


def latent_moe_config(cfg: dict, *, param_dtype, **kw):
    m, y = model_section(cfg), cfg["rope_scaling"]
    return sarvam_preset()(
        vocab_size=m["vocab_size"], d_model=m["hidden_size"],
        n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
        qk_nope_dim=m["qk_nope_head_dim"], qk_rope_dim=m["qk_rope_head_dim"],
        v_head_dim=m["v_head_dim"], kv_lora_rank=m["kv_lora_rank"],
        d_ff=m["intermediate_size"],
        first_dense=m["first_k_dense_replace"],
        d_expert=m["moe_intermediate_size"], n_experts=m["router_width"],
        experts_per_token=m["num_experts_per_tok"],
        n_shared_experts=m["num_shared_experts"],
        routed_scaling=float(m["routed_scaling_factor"]),
        expert_first=m["expert_first"], expert_count=m["num_experts"],
        # the rope tables' rows: what the engine can reach, not the
        # published 131 072 (the frequencies do not depend on it)
        max_seq_len=cfg["engine"]["max_seq_len"],
        rope_theta=float(m["rope_theta"]), rope_factor=float(y["factor"]),
        rope_original_max_len=y["original_max_position_embeddings"],
        rope_beta_fast=float(y["beta_fast"]),
        rope_beta_slow=float(y["beta_slow"]),
        rope_mscale_all_dim=float(y["mscale_all_dim"]),
        norm_eps=float(m["rms_norm_eps"]), param_dtype=param_dtype, **kw)


def model_factory(cfg: dict, seed: int):
    """Runs inside the replica: (model, params), bf16 weights made on the
    replica's device in one jitted call from the seed."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import LatentMoE
    from ray_tpu.util.jaxenv import enable_compile_cache
    mcfg = latent_moe_config(cfg, param_dtype=jnp.bfloat16)
    enable_compile_cache()
    # persist every program, also those that compile in under a second
    # (the engine's small eager ops): each run is a new process
    if jax.default_backend() == "tpu":
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    model = LatentMoE(mcfg)
    key = jax.random.PRNGKey(np.uint32(int(seed) % (2 ** 32)))
    params = jax.jit(lambda k: model.init(
        k, jnp.zeros((1, 8), jnp.int32))["params"])(key)
    jax.block_until_ready(params)
    return model, params


def _routing(sown, layers):
    """Per expert layer the (S, k) experts a call chose, from the
    `routing` collection the model sows."""
    return [np.asarray(sown["routing"][f"layer_{i}"]["moe"]["top_idx"][0][0])
            for i in layers]


def system_logits(engine, prompt: np.ndarray, gen: np.ndarray):
    """The system's own model code and kernels as the engine's step
    programs call them: prefill of the prompt into pages of latents
    (expanded form), then one decode step a generated token through the
    paged latent cache (absorbed form, the Pallas kernel on the chip).
    Returns the logits of every prompt position and decode step
    (p + g - 1, vocab) float32 and, per expert layer, the experts chosen
    at each of those positions."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.attention import PagedLatent

    p, g = prompt.size, gen.size
    model, params = engine.model, engine.params
    mc = model.cfg
    ps = engine.cfg.kv_page_size
    pad = engine._bucket(p)
    moe_layers = range(mc.first_dense, mc.n_layers)
    n_pages = -(-(pad + g + 1) // ps)
    pools = [jnp.zeros(((n_pages + 1) * ps, mc.cache_width), mc.dtype)
             for _ in range(mc.n_layers)]
    table = jnp.arange(n_pages, dtype=jnp.int32)[None, :]

    @jax.jit
    def prefill(params, pools, tokens):
        entries = [PagedLatent(c, table[:, :-(-pad // ps)],
                               jnp.zeros((1,), jnp.int32), ps, fresh=True)
                   for c in pools]
        pos = jnp.arange(pad)[None, :]
        (logits, new), sown = model.apply(
            {"params": params}, tokens, cache=entries, positions=pos,
            row_mask=pos < p, mutable=["routing"])
        return logits[0, :p], [e.flat for e in new], sown

    @jax.jit
    def decode(params, pools, token, length):
        entries = [PagedLatent(c, table, length, ps) for c in pools]
        (logits, new), sown = model.apply(
            {"params": params}, token[:, None], cache=entries,
            positions=length[:, None], mutable=["routing"])
        return logits[0, 0], [e.flat for e in new], sown

    toks = np.zeros((1, pad), np.int32)
    toks[0, :p] = prompt
    block, pools, sown = prefill(params, pools, jnp.asarray(toks))
    got = [np.asarray(block, np.float32)]
    chose = [c[:p] for c in _routing(sown, moe_layers)]
    for j in range(1, g):
        row, pools, sown = decode(params, pools, jnp.asarray(gen[j - 1:j]),
                                  jnp.asarray([p + j - 1], jnp.int32))
        got.append(np.asarray(row, np.float32)[None])
        chose = [np.concatenate([a, b]) for a, b in
                 zip(chose, _routing(sown, moe_layers))]
    return np.concatenate(got), chose, pad


def compare(got, chose, params, seq, gen, p: int, m: dict, tol: dict):
    """`got` (p + g - 1, vocab) against the reference's full forward of
    `seq`, the reference following the system's `chose`n experts inside
    `tie_margin_rel` (reference_sarvam's docstring)."""
    import jax.numpy as jnp
    g = gen.size
    margin = float(tol["tie_margin_rel"])
    ref, rec = reference_sarvam.forward(
        params, jnp.asarray(seq), m,
        follow=[jnp.asarray(c) for c in chose], tie_margin=margin)
    ref = np.asarray(ref, np.float32)
    margins = np.stack([np.asarray(r["margin_rel"]) for r in rec])  # (L, S)
    same = np.stack([np.asarray(r["own"]) for r in rec])
    bad = np.stack([np.asarray(r["not_followed"]) for r in rec])
    swap = np.stack([np.asarray(r["swap_rel"]) for r in rec])
    no_tie = (margins >= margin).all(0)                # positions

    scale = float(ref.std())
    err_pos = np.abs(got - ref).max(-1) / scale
    err = float(err_pos.max())
    last = ref[p - 1:]                                 # the g sampled rows
    gap = float((last.max(-1) - last[np.arange(g), gen]).max()) / scale
    ok = (err <= tol["logit_tol_rel"] and gap <= tol["argmax_tol_rel"]
          and not bad.any() and bool(np.isfinite(got).all()))
    return {"logit_err_rel": err, "logit_tol_rel": tol["logit_tol_rel"],
            "logit_err_rel_prefill": float(err_pos[:p].max()),
            "logit_err_rel_decode": float(err_pos[p:].max())
            if g > 1 else None,
            "argmax_gap_rel": gap, "argmax_tol_rel": tol["argmax_tol_rel"],
            "tie_margin_rel": margin,
            "tie_pair_share": float((margins < margin).mean()),
            "same_experts_pair_share": float(same.mean()),
            "not_followed": int(bad.sum()),
            "swap_rel_max": float(swap.max()),
            "no_tie_positions": int(no_tie.sum()),
            "logit_err_rel_no_tie": float(err_pos[no_tie].max())
            if no_tie.any() else None,
            "positions": int(got.shape[0]), "logit_std": scale,
            "ok": bool(ok)}


def serve_check(engine, spec: Dict[str, Any]) -> Dict[str, Any]:
    """`system_logits` on a seeded prompt and the tokens the engine
    itself answered with, against the float32 reference's full forward
    pass: logits of every prompt position and of every decode step.
    `spec["controls"]` (a builder's tool, never a benchmark run): names
    of `reference_sarvam`'s deliberately wrong models; the answer then
    holds, under `controls`, the same comparison against each."""
    prompt = np.asarray(spec["prompt"], np.int32)
    gen = np.asarray(spec["generated"], np.int32)
    p = prompt.size
    seq = np.concatenate([prompt, gen])[:-1]          # p + g - 1 inputs
    got, chose, pad = system_logits(engine, prompt, gen)
    m = spec["model"]
    out = compare(got, chose, engine.params, seq, gen, p, m, spec["check"])
    out.update(layers=int(m["num_hidden_layers"]), prompt_len=int(p),
               new_tokens=int(gen.size), prefill_bucket=int(pad))
    if spec.get("controls"):
        out["controls"] = {
            name: compare(got, chose, engine.params, seq, gen, p,
                          dict(m, controls=frozenset([name])),
                          spec["check"])
            for name in spec["controls"]}
    return out


class SarvamBenchServer(BenchServer):

    def bench_check(self, spec: Dict[str, Any]) -> Dict[str, Any]:
        return serve_check(self.engine, spec)
