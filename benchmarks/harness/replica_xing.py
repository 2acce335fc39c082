"""The benchmark's side of a Xing4.0-29B-A4B replica (four residual
streams mixed by manifold-constrained hyper-connections around every
sub-layer, latent attention with a low-rank query, sigmoid-routed experts
all held here, a shared expert): `BenchServer` with the LatentMoE model
factory and the comparison against `reference_xing`. Everything else
(warm-up, sampler, trace, stats) is `BenchServer`'s. The program's
`LatentMoEConfig.xing4_29b_a4b` preset is looked up before anything is
built: a program without it cannot run this configuration and says so
at once.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

from . import reference_xing
from .replica import BenchServer
from .replica_lfm2moe import system_logits

MODEL_KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
              "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
              "qk_rope_head_dim", "v_head_dim", "intermediate_size",
              "moe_intermediate_size", "n_routed_experts",
              "num_experts_per_tok", "n_shared_experts",
              "first_k_dense_replace", "routed_scaling_factor",
              "norm_topk_prob", "rope_theta", "rope_scaling",
              "rms_norm_eps", "vocab_size", "max_position_embeddings",
              "tie_word_embeddings", "hc_mult", "hc_sinkhorn_iters",
              "hc_eps", "mhc_h_res_clamp_min", "mhc_h_res_clamp_max")


def model_section(cfg: dict) -> dict:
    """The published keys the program, the reference, the cost
    arithmetic and the accepted readers read."""
    missing = [k for k in MODEL_KEYS + ("scoring_func", "topk_method",
                                        "n_group", "topk_group")
               if k not in cfg]
    if missing:
        raise SystemExit(f"benchmark: {cfg['name']}.json lacks {missing}")
    if (cfg["tie_word_embeddings"] or cfg["scoring_func"] != "sigmoid"
            or cfg["topk_method"] != "noaux_tc" or cfg["n_group"] != 1
            or cfg["topk_group"] != 1
            or not 0 < cfg["first_k_dense_replace"]
            < cfg["num_hidden_layers"]):
        raise SystemExit(
            "benchmark: reference_xing.py has an untied head, sigmoid "
            "scores with a selection bias, no group stage, and dense "
            "layers before expert layers; this file disagrees")
    # `num_experts` is the key the accepted moe_counter reader divides
    # by; the published file calls the same number n_routed_experts
    return dict({k: cfg[k] for k in MODEL_KEYS},
                num_experts=cfg["n_routed_experts"])


def xing_preset():
    """The program's preset, or a clean failure where it has none."""
    try:
        from ray_tpu.models import LatentMoEConfig
    except ImportError:
        LatentMoEConfig = None
    preset = getattr(LatentMoEConfig, "xing4_29b_a4b", None)
    if preset is None:
        raise SystemExit(
            "benchmark: this program has no LatentMoEConfig.xing4_29b_a4b "
            "(residual streams mixed by manifold-constrained "
            "hyper-connections, latent attention with a low-rank query): "
            "it cannot run a Xing4.0 configuration")
    return preset


def latent_moe_config(cfg: dict, *, param_dtype, **kw):
    m, y = model_section(cfg), cfg["rope_scaling"]
    return xing_preset()(
        vocab_size=m["vocab_size"], d_model=m["hidden_size"],
        n_layers=m["num_hidden_layers"], n_heads=m["num_attention_heads"],
        qk_nope_dim=m["qk_nope_head_dim"], qk_rope_dim=m["qk_rope_head_dim"],
        v_head_dim=m["v_head_dim"], kv_lora_rank=m["kv_lora_rank"],
        q_lora_rank=m["q_lora_rank"], d_ff=m["intermediate_size"],
        first_dense=m["first_k_dense_replace"],
        d_expert=m["moe_intermediate_size"], n_experts=m["n_routed_experts"],
        experts_per_token=m["num_experts_per_tok"],
        n_shared_experts=m["n_shared_experts"],
        routed_scaling=float(m["routed_scaling_factor"]),
        norm_topk_prob=bool(m["norm_topk_prob"]),
        hc_mult=m["hc_mult"], hc_sinkhorn_iters=m["hc_sinkhorn_iters"],
        hc_eps=float(m["hc_eps"]),
        hc_res_clamp=(float(m["mhc_h_res_clamp_min"]),
                      float(m["mhc_h_res_clamp_max"])),
        # the rope tables' rows: what the engine can reach, not the
        # published 262 144 (the frequencies do not depend on it)
        max_seq_len=cfg["engine"]["max_seq_len"],
        rope_theta=float(m["rope_theta"]), rope_factor=float(y["factor"]),
        rope_original_max_len=y["original_max_position_embeddings"],
        rope_beta_fast=float(y["beta_fast"]),
        rope_beta_slow=float(y["beta_slow"]),
        rope_mscale_all_dim=float(y["mscale_all_dim"]),
        norm_eps=float(m["rms_norm_eps"]), param_dtype=param_dtype, **kw)


def model_factory(cfg: dict, seed: int):
    """Runs inside the replica: (model, params), bf16 weights made on the
    replica's device in one jitted call from the seed (the mapping's b
    and a are the model's own seeded draws: models/latent_moe.py,
    LatentMoEBlock._mapping)."""
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


def first_mappings(engine, prompt: np.ndarray, pad: int):
    """The first sub-layer's packed mappings by the system's own route
    (`ops/hyper_connections.py:read`: the `hc_mix_in` kernel on a TPU)
    over the prompt padded to its bucket, the rows of the engine's
    prefill call; and the input they were computed from. It is the one
    place where the system's and the reference's mapping inputs are
    bit-equal (the embedding's stored values repeated into the streams),
    so what differs there is the mapping's arithmetic alone."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops import hyper_connections as hc
    mc, layer = engine.model.cfg, engine.params["layer_0"]
    toks = np.zeros((1, pad), np.int32)
    toks[0, :prompt.size] = prompt
    x = jnp.tile(engine.params["token_embed"]["embedding"][
        jnp.asarray(toks)].astype(mc.dtype), (1, 1, mc.hc_mult))
    _h, maps = jax.jit(lambda x, phi, b, a: hc.read(
        x, phi, b, a, mc.hc_params))(
            x, layer["hc_attn_phi"], layer["hc_attn_b"], layer["hc_attn_a"])
    return {"maps": np.asarray(maps[0, :prompt.size, :hc.n_maps(mc.hc_mult)]),
            "x": x[0, :prompt.size].astype(jnp.float32), "layer": layer}


def compare(got, chose, params, seq, gen, idle, p: int, m: dict, tol: dict,
            positions: bool = False, first=None):
    """`got` (p + g - 1, vocab) against the reference's full forward of
    `seq`, the reference following the system's `chose`n experts inside
    `tie_margin_rel` (reference_xing's docstring); every position's
    largest logit error in units of the logits' standard deviation. The
    MEAN over the prompt's positions (the expanded form, the flash
    kernel and the grouped matmuls at a prefill's rows, `hc_mix_*` at a
    prefill's tiles) is the tight limit: it moves little from seed to
    seed, and a Sinkhorn loop cut short or weights below bf16 move it.
    The mean over the decode steps (the absorbed form over the paged
    latents, `hc_mix_*` at 129 rows) shows what the prompt cannot: a
    pool row read wrongly. The largest error of all positions
    is the backstop for a fault at few of them. The mapping's own
    precision is read where rounding elsewhere cannot hide it: `first`
    (`first_mappings`) against the reference's mappings of the same
    bit-equal input, largest absolute difference of any of the 24 values
    of any prompt position (float32 both sides reads the two routes'
    orders of summation; a mapping in bfloat16 or a Sinkhorn loop cut
    short reads a thousand times that). A choice of experts that
    the reference did not follow fails the comparison by itself. And the
    engine's own greedy tokens (this answer's, and the `idle` engine's
    as far as the two answers share their context) may each lie only so
    far under the reference's largest logit."""
    import jax
    import jax.numpy as jnp
    g = gen.size
    margin = float(tol["tie_margin_rel"])
    ref, rec = reference_xing.forward(
        params, jnp.asarray(seq), m,
        follow=[jnp.asarray(c) for c in chose], tie_margin=margin)
    ref = np.asarray(ref, np.float32)
    margins = np.stack([np.asarray(r["margin_rel"]) for r in rec])  # (L, S)
    same = np.stack([np.asarray(r["own"]) for r in rec])
    bad = np.stack([np.asarray(r["not_followed"]) for r in rec])
    swap = np.stack([np.asarray(r["swap_rel"]) for r in rec])
    scale = float(ref.std())
    err_pos = np.abs(got - ref).max(-1) / scale
    err = float(err_pos.max())
    mean = float(err_pos[:p].mean())
    mean_decode = float(err_pos[p:].mean()) if g > 1 else 0.0
    last = ref[p - 1:]                                 # the g sampled rows
    shared_ctx = idle[:g] == gen[:idle.size]
    shared = (shared_ctx.size if shared_ctx.all()
              else int(shared_ctx.argmin()) + 1)
    gap = float(max(
        (last.max(-1) - last[np.arange(g), gen]).max(),
        (last[:shared].max(-1)
         - last[np.arange(shared), idle[:shared]]).max(initial=0.0))) / scale
    map_err = None
    if first is not None:
        with jax.default_matmul_precision("highest"):
            pre, post, res = reference_xing.mappings(
                first["x"], first["layer"], "attn", m)
        map_err = float(np.abs(first["maps"] - np.concatenate(
            [np.asarray(pre), np.asarray(post),
             np.asarray(res).reshape(pre.shape[0], -1)], -1)).max())
    ok = (err <= tol["logit_tol_rel"] and mean <= tol["logit_mean_tol_rel"]
          and (map_err is None or map_err <= tol["mapping_tol_abs"])
          and mean_decode <= tol["logit_decode_mean_tol_rel"]
          and gap <= tol["argmax_tol_rel"] and not bad.any()
          and bool(np.isfinite(got).all()))
    out = {"logit_err_rel": err, "logit_tol_rel": tol["logit_tol_rel"],
           "logit_err_rel_mean": mean,
           "logit_mean_tol_rel": tol["logit_mean_tol_rel"],
           "logit_err_rel_decode_mean": mean_decode,
           "logit_decode_mean_tol_rel": tol["logit_decode_mean_tol_rel"],
           "logit_err_rel_decode": float(err_pos[p:].max())
           if g > 1 else None,
           "worst_position": int(err_pos.argmax()),
           "argmax_gap_rel": gap, "argmax_tol_rel": tol["argmax_tol_rel"],
           "mapping_err_abs": map_err,
           "mapping_tol_abs": tol.get("mapping_tol_abs"),
           "tie_margin_rel": margin,
           "tie_pair_share": float((margins < margin).mean()),
           "same_experts_pair_share": float(same.mean()),
           "not_followed": int(bad.sum()),
           "swap_rel_max": float(swap.max()),
           "positions": int(got.shape[0]), "logit_std": scale,
           "ok": bool(ok)}
    if positions:
        out["err_positions"] = [round(float(e), 5) for e in err_pos]
    return out


def serve_check(engine, spec: Dict[str, Any]) -> Dict[str, Any]:
    """`replica_lfm2moe.system_logits` (the logits and the expert
    choices of the ENGINE's own step programs, with every slot live: the
    prefill of the seeded prompt in its bucket, then the decode steps
    through the paged latents at 129 rows) against the float32
    reference's full forward pass over the prompt and that answer. What
    the idle engine answered over HTTP (`spec["generated"]`) is held to
    the same reference as far as it shares the busy answer's context.
    `tokens_as_idle` and `tokens_with_logits_as_timed` are readings
    only: a near-tie of two experts or of two words may fall the other
    way in a program compiled apart or run beside other rows (PERF.md,
    PR 39). `spec["controls"]` (a builder's tool, never a benchmark run):
    names of `reference_xing`'s deliberately wrong models; the answer
    then holds, under `controls`, the same comparison against each."""
    prompt = np.asarray(spec["prompt"], np.int32)
    idle = np.asarray(spec["generated"], np.int32)
    tol = spec["check"]
    p = prompt.size
    got, chose, gen, timed, pad, beside = system_logits(
        engine, prompt, int(tol.get("busy_new_tokens", idle.size)))
    seq = np.concatenate([prompt, gen])[:-1]          # p + g - 1 inputs
    m = spec["model"]
    detail = bool(spec.get("controls"))
    first = first_mappings(engine, prompt, pad)
    out = compare(got, chose, engine.params, seq, gen, idle, p, m, tol,
                  detail, first)
    stats = engine.get_stats()
    out.update(layers=int(m["num_hidden_layers"]), prompt_len=int(p),
               new_tokens=int(gen.size), prefill_bucket=int(pad),
               slots=int(engine.cfg.max_slots), requests_beside=int(beside),
               tokens_as_idle=bool(
                   idle.size and (timed[:idle.size] == idle).all()),
               tokens_with_logits_as_timed=bool((gen == timed).all()),
               hc_rows=int(stats.get("hc_rows", 0)),
               hc_unconverged_rows=int(stats.get("hc_unconverged_rows", 0)),
               hc_clamped_rows=int(stats.get("hc_clamped_rows", 0)))
    if detail:
        out["controls"] = {
            name: compare(got, chose, engine.params, seq, gen, idle, p,
                          dict(m, controls=frozenset([name])), tol, True,
                          first)
            for name in spec["controls"]}
    return out


class XingBenchServer(BenchServer):

    def bench_check(self, spec: Dict[str, Any]) -> Dict[str, Any]:
        return serve_check(self.engine, spec)
