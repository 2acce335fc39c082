"""The benchmark's side of an OLMoE replica: `BenchServer` with the
Mixtral/OLMoE model factory and the comparison against
`reference_olmoe`. Everything else (warm-up, sampler, trace, stats) is
`BenchServer`'s. The program's `MixtralConfig.olmoe_1b_7b` preset is
looked up before anything is built: a program without it cannot run
this configuration and says so at once.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

from . import modelcfg, reference_olmoe
from .replica import BenchServer

MOE_KEYS = ("num_experts", "num_experts_per_tok", "norm_topk_prob")


def model_section(cfg: dict) -> dict:
    return {**modelcfg.model_section(cfg), **{k: cfg[k] for k in MOE_KEYS}}


def olmoe_preset():
    """The program's OLMoE preset, or a clean failure where it has none."""
    from ray_tpu.models import MixtralConfig
    preset = getattr(MixtralConfig, "olmoe_1b_7b", None)
    if preset is None:
        raise SystemExit(
            "benchmark: this program has no MixtralConfig.olmoe_1b_7b "
            "(dropless top-k routing over all experts, q/k norm): it "
            "cannot run an OLMoE configuration")
    return preset


def mixtral_config(cfg: dict, *, param_dtype, **kw):
    return olmoe_preset()(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], n_experts=cfg["num_experts"],
        experts_per_token=cfg["num_experts_per_tok"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        max_seq_len=cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), param_dtype=param_dtype, **kw)


def model_factory(cfg: dict, seed: int):
    """Runs inside the replica: (model, params), bf16 weights made on the
    replica's device in one jitted call from the seed."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import Mixtral
    from ray_tpu.util.jaxenv import enable_compile_cache
    mcfg = mixtral_config(cfg, param_dtype=jnp.bfloat16)
    enable_compile_cache()
    # persist every program, also those that compile in under a second
    # (the engine's small eager ops): each run is a new process
    if jax.default_backend() == "tpu":
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    model = Mixtral(mcfg)
    key = jax.random.PRNGKey(np.uint32(int(seed) % (2 ** 32)))
    params = jax.jit(lambda k: model.init(
        k, jnp.zeros((1, 8), jnp.int32))["params"])(key)
    jax.block_until_ready(params)
    return model, params


def _routing(sown, n_layers: int):
    """Per layer the (S, k) experts a call chose, from the `routing`
    collection the model sows."""
    return [np.asarray(sown["routing"][f"layer_{i}"]["moe"]["top_idx"][0][0])
            for i in range(n_layers)]


def serve_check(engine, spec: Dict[str, Any]) -> Dict[str, Any]:
    """The system's own model code and kernels (prefill into pages, then
    decode through the paged cache, as the engine's step programs call
    them) against the float32 reference's full forward pass, on a seeded
    prompt and the tokens the engine itself answered with. Logits of
    every prompt position and of every decode step are compared.

    Near-ties (reference_olmoe's docstring): the reference follows the
    system's choice of experts where every expert swapped lies within
    `tie_margin_rel` of the reference's own k-th probability. A choice
    outside it fails the check (`not_followed` > 0). The logit error is
    taken with the reference following; beside it are the error on the
    positions where no layer had a margin under `tie_margin_rel` and the
    error against the reference's own choices.
    """
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.attention import PagedKV

    tol = spec["check"]
    prompt = np.asarray(spec["prompt"], np.int32)
    gen = np.asarray(spec["generated"], np.int32)
    p, g = prompt.size, gen.size
    seq = np.concatenate([prompt, gen])[:-1]          # p + g - 1 inputs
    model, params = engine.model, engine.params
    mc = model.cfg
    m = dict(spec["model"], num_experts=mc.n_experts,
             num_experts_per_tok=mc.experts_per_token,
             norm_topk_prob=mc.norm_topk_prob)
    ps = engine.cfg.kv_page_size
    pad = engine._bucket(p)

    n_pages = -(-(pad + g + 1) // ps)
    rows = (n_pages + 1) * ps
    pools = [(jnp.zeros((rows, mc.n_kv_heads, mc.head_dim), mc.dtype),
              jnp.zeros((rows, mc.n_kv_heads, mc.head_dim), mc.dtype))
             for _ in range(mc.n_layers)]
    table = jnp.arange(n_pages, dtype=jnp.int32)[None, :]

    @jax.jit
    def prefill(params, pools, tokens):
        entries = [PagedKV(k, v, table[:, :-(-pad // ps)],
                           jnp.zeros((1,), jnp.int32), ps, fresh=True)
                   for k, v in pools]
        pos = jnp.arange(pad)[None, :]
        (logits, new), sown = model.apply(
            {"params": params}, tokens, cache=entries, positions=pos,
            row_mask=pos < p, mutable=["routing"])
        return logits[0, :p], [(e.k_flat, e.v_flat) for e in new], sown

    @jax.jit
    def decode(params, pools, token, length):
        entries = [PagedKV(k, v, table, length, ps) for k, v in pools]
        (logits, new), sown = model.apply(
            {"params": params}, token[:, None], cache=entries,
            positions=length[:, None], mutable=["routing"])
        return logits[0, 0], [(e.k_flat, e.v_flat) for e in new], sown

    toks = np.zeros((1, pad), np.int32)
    toks[0, :p] = prompt
    block, pools, sown = prefill(params, pools, jnp.asarray(toks))
    got = [np.asarray(block, np.float32)]
    chose = [c[:p] for c in _routing(sown, mc.n_layers)]
    for j in range(1, g):
        row, pools, sown = decode(params, pools, jnp.asarray(gen[j - 1:j]),
                                  jnp.asarray([p + j - 1], jnp.int32))
        got.append(np.asarray(row, np.float32)[None])
        chose = [np.concatenate([a, b]) for a, b in
                 zip(chose, _routing(sown, mc.n_layers))]
    got = np.concatenate(got)                          # (p + g - 1, vocab)
    del pools

    margin = float(tol["tie_margin_rel"])
    ref, rec = reference_olmoe.forward(
        params, jnp.asarray(seq), m,
        follow=[jnp.asarray(c) for c in chose], tie_margin=margin)
    ref = np.asarray(ref, np.float32)
    own, _ = reference_olmoe.forward(params, jnp.asarray(seq), m)
    own = np.asarray(own, np.float32)
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
            "argmax_gap_rel": gap, "argmax_tol_rel": tol["argmax_tol_rel"],
            "tie_margin_rel": margin,
            "tie_pair_share": float((margins < margin).mean()),
            "same_experts_pair_share": float(same.mean()),
            "not_followed": int(bad.sum()),
            "swap_rel_max": float(swap.max()),
            "no_tie_positions": int(no_tie.sum()),
            "logit_err_rel_no_tie": float(err_pos[no_tie].max())
            if no_tie.any() else None,
            "logit_err_rel_own_choices": float(
                np.abs(got - own).max()) / float(own.std()),
            "positions": int(got.shape[0]), "layers": int(mc.n_layers),
            "prompt_len": int(p), "new_tokens": int(g),
            "prefill_bucket": int(pad), "logit_std": scale, "ok": bool(ok)}


class OlmoeBenchServer(BenchServer):

    def bench_check(self, spec: Dict[str, Any]) -> Dict[str, Any]:
        return serve_check(self.engine, spec)
