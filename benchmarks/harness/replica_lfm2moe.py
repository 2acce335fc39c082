"""The benchmark's side of an LFM2-24B-A2B replica (gated short
convolutions with a two-token state a slot beside full-attention layers
that page K and V of 64-wide heads, routed experts after a leading dense
layer): `BenchServer` with the Hybrid model factory and the comparison
against `reference_lfm2moe`. Everything else (warm-up, sampler, trace,
stats) is `BenchServer`'s. The program's `HybridConfig.lfm2_24b_a2b`
preset is looked up before anything is built: a program without it
cannot run this configuration and says so at once.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

from . import modelcfg, reference_lfm2moe
from .replica import BenchServer
from .replica_olmohybrid import _busy_traffic

LFM2_KEYS = ("layer_types", "conv_L_cache", "conv_bias", "norm_eps",
             "moe_intermediate_size", "num_experts", "num_experts_per_tok",
             "num_dense_layers", "norm_topk_prob", "routed_scaling_factor",
             "use_expert_bias", "rope_parameters")
# spread of the per-head q/k norm weights about 1 (model_factory)
QK_NORM_SPREAD = 0.25


def model_section(cfg: dict) -> dict:
    """The published keys the program, the reference and the cost
    arithmetic read: the Llama-shaped section and the family's own keys,
    `layer_types` cut to the `num_hidden_layers` layers held."""
    m = modelcfg.model_section(cfg)
    missing = [k for k in LFM2_KEYS if k not in cfg]
    if missing:
        raise SystemExit(f"benchmark: {cfg['name']}.json lacks {missing}")
    n = m["num_hidden_layers"]
    kinds = list(cfg["layer_types"])[:n]
    rope = cfg["rope_parameters"]
    if (len(kinds) != n or cfg["conv_bias"] or not m["tie_word_embeddings"]
            or not cfg["use_expert_bias"]
            or rope.get("rope_type") != "default"
            or rope.get("rope_theta") != m["rope_theta"]
            or cfg["norm_eps"] != m["rms_norm_eps"]
            or not 0 < cfg["num_dense_layers"] < n):
        raise SystemExit(
            "benchmark: reference_lfm2moe.py has a mixer for every layer "
            "held, dense layers before expert layers, no bias on the "
            "convolution, a tied head, a selection bias and the default "
            "rotation, and rope_theta / rms_norm_eps repeat what "
            "rope_parameters / norm_eps publish; this file disagrees")
    return {**m, **{k: cfg[k] for k in LFM2_KEYS}, "layer_types": kinds}


def lfm2_preset():
    """The program's preset, or a clean failure where it has none."""
    try:
        from ray_tpu.models import HybridConfig
    except ImportError:
        HybridConfig = None
    preset = getattr(HybridConfig, "lfm2_24b_a2b", None)
    if preset is None:
        raise SystemExit(
            "benchmark: this program has no HybridConfig.lfm2_24b_a2b "
            "(gated short-convolution layers with their last inputs a "
            "slot, a feed-forward chosen by layer, heads of 64 packed in "
            "the page pool): it cannot run an LFM2-MoE configuration")
    return preset


def hybrid_config(cfg: dict, *, param_dtype, **kw):
    m = model_section(cfg)
    return lfm2_preset()(
        vocab_size=m["vocab_size"], d_model=m["hidden_size"],
        n_layers=m["num_hidden_layers"],
        layer_types=tuple(m["layer_types"]),
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], d_ff=m["intermediate_size"],
        conv_kernel=m["conv_L_cache"], n_dense_layers=m["num_dense_layers"],
        d_expert=m["moe_intermediate_size"], n_experts=m["num_experts"],
        experts_per_token=m["num_experts_per_tok"],
        norm_topk_prob=bool(m["norm_topk_prob"]),
        routed_scaling=float(m["routed_scaling_factor"]),
        rope_theta=float(m["rope_theta"]),
        # the rope tables' rows: what the engine can reach, not the
        # published 128 000 (the frequencies do not depend on it)
        max_seq_len=cfg["engine"]["max_seq_len"],
        norm_eps=float(m["norm_eps"]), param_dtype=param_dtype, **kw)


def model_factory(cfg: dict, seed: int):
    """Runs inside the replica: (model, params), bf16 weights made on the
    replica's device in one jitted call from the seed. The per-head q
    and k norm weights are drawn about 1 (+- QK_NORM_SPREAD, normal):
    at the initialiser's ones a rotation before the norm and one after
    it are the same function, and a check could not tell them apart."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import Hybrid
    from ray_tpu.util.jaxenv import enable_compile_cache
    mcfg = hybrid_config(cfg, param_dtype=jnp.bfloat16)
    enable_compile_cache()
    # persist every program, also those that compile in under a second
    # (the engine's small eager ops): each run is a new process
    if jax.default_backend() == "tpu":
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    model = Hybrid(mcfg)
    key = jax.random.PRNGKey(np.uint32(int(seed) % (2 ** 32)))

    def make(k):
        params = model.init(k, jnp.zeros((1, 8), jnp.int32))["params"]
        for i, kind in enumerate(mcfg.layer_types):
            attn = params[f"layer_{i}"].get("attention")
            if attn is None:
                continue
            for j, name in enumerate(("q_norm", "k_norm")):
                noise = jax.random.normal(
                    jax.random.fold_in(k, 1000 + 2 * i + j),
                    attn[name].shape)
                attn[name] = attn[name] * (1.0 + QK_NORM_SPREAD * noise)
        return params
    params = jax.jit(make)(key)
    jax.block_until_ready(params)
    return model, params


class _WithRouting:
    """The engine's model for the length of one trace: `apply` asks for
    the `routing` collection beside what the engine asks for and keeps
    it in `seen`, so that the engine's own `_apply_counted` runs as it
    is."""

    def __init__(self, model, seen: list):
        self._model, self._seen = model, seen

    def __getattr__(self, name):
        return getattr(self._model, name)

    def apply(self, variables, *args, mutable=(), **kw):
        out, sown = self._model.apply(
            variables, *args, mutable=[*mutable, "routing"], **kw)
        sown = dict(sown)
        self._seen.append(sown.pop("routing"))
        return out, sown


def _routing(sown, layers, cut):
    """Per expert layer the experts one row chose, `cut` out of the
    (rows, S, k) the model sows."""
    return [cut(sown[f"layer_{i}"]["moe"]["top_idx"][0]) for i in layers]


def system_logits(engine, prompt: np.ndarray, n_new: int):
    """The tokens, the logits and the experts chosen by the engine's own
    step programs, on its own pools, with every slot live: what
    `replica_olmohybrid.system_logits` does (the check's prompt goes in
    through `submit` between `_busy_traffic`'s two halves, the engine's
    loop serves them all, twice; the second time the dispatches that
    carry the check's request run `_prefill_paged_step` /
    `_decode_paged_step` traced once more with the logits handed out),
    and beside the logits the `routing` collection of the same call,
    cut to the request's row: the choices of the 129-row programs the
    window runs, which the reference follows inside the tie margin.

    Returns the logits of every prompt position and of every decode
    step (p + n_new - 1, vocab) float32, per expert layer the experts
    chosen at those positions (p + n_new - 1, k), the n_new tokens
    answered with them, the n_new tokens the timed programs answered,
    the bucket, and how many requests ran beside."""
    import jax
    import jax.numpy as jnp

    eng, p = engine, prompt.size
    mc = eng.model.cfg
    moe_layers = [i for i in range(mc.n_layers) if not mc.dense_ff(i)]
    first, behind = _busy_traffic(eng, prompt, n_new)
    mine: Dict[str, Any] = {"rid": None, "prefill": None, "decode": []}

    def traced_once_more(step, static, keep, keep_routing):
        def run(params, pools, state, ctl, **kw):
            seen, routed = [], []
            inner, model = eng._apply_counted, eng.model

            def spy(*args):
                out = inner(*args)
                seen.append(out[0])
                return out
            eng._apply_counted = spy
            eng.model = _WithRouting(model, routed)
            try:
                out = step(params, pools, state, ctl, **kw)
            finally:
                del eng._apply_counted
                eng.model = model
            return (out, keep(seen[0]).astype(jnp.float32),
                    _routing(routed[0], moe_layers, keep_routing))
        return jax.jit(run, static_argnames=static, donate_argnums=(1, 2))

    def dispatch_prefill(inflight, pad_len, members):
        rows = [i for i, (req, _slot) in enumerate(members)
                if req.request_id == mine["rid"]]
        if rows:
            program = traced_once_more(
                eng._prefill_paged_step, ("pad_len",),
                lambda logits: logits[rows[0], :p],
                lambda top: top[rows[0], :p])

            def with_logits(*args, **kw):
                out, logits, chose = program(*args, **kw)
                mine["prefill"] = (np.asarray(logits),
                                   [np.asarray(c) for c in chose])
                return out
            real, eng._prefill_paged_jit = eng._prefill_paged_jit, with_logits
        try:
            return type(eng)._dispatch_prefill(eng, inflight, pad_len,
                                               members)
        finally:
            if rows:
                eng._prefill_paged_jit = real

    def dispatch_decode(inflight, snapshot, props, allow, pen, window):
        slot = [s for s, req in snapshot if req.request_id == mine["rid"]]
        if slot:
            if "program" not in mine:
                mine["program"] = traced_once_more(
                    eng._decode_paged_step, ("window_pages",),
                    lambda logits: logits[slot[0], 0],
                    lambda top: top[slot[0]])

            def with_logits(*args, **kw):
                out, logits, chose = mine["program"](*args, **kw)
                mine["decode"].append((logits, chose))
                return out
            real, eng._decode_paged_jit = eng._decode_paged_jit, with_logits
        try:
            return type(eng)._dispatch_decode(eng, inflight, snapshot, props,
                                              allow, pen, window)
        finally:
            if slot:
                eng._decode_paged_jit = real

    def serve(tapped: bool):
        rids = []

        def begin():
            if tapped:
                eng._dispatch_prefill = dispatch_prefill
                eng._dispatch_decode = dispatch_decode
            for tokens, new in first:
                rids.append(eng.submit(tokens, max_new_tokens=new))
            mine["rid"] = eng.submit(prompt, max_new_tokens=n_new)
            for tokens, new in behind:
                rids.append(eng.submit(tokens, max_new_tokens=new))
        try:
            # from the loop's own thread, between two steps: one order
            # of admission, whatever the caller's thread is doing
            eng._run_on_loop(begin)
            answer = list(eng.stream(mine["rid"]))
            for rid in rids:
                for _ in eng.stream(rid):
                    pass
        finally:
            eng._run_on_loop(lambda: (
                eng.__dict__.pop("_dispatch_prefill", None),
                eng.__dict__.pop("_dispatch_decode", None)))
        return np.asarray(answer, np.int32)

    timed = serve(False)        # the timed programs themselves: tokens
    answer = serve(True)        # and once more, handing out logits
    # the loop runs ahead of what it has drained: steps dispatched after
    # the request's last token are discarded by the engine, and here
    steps = mine["decode"][:n_new - 1]
    block, chose = mine["prefill"]
    got = np.concatenate([block] + [np.asarray(row)[None]
                                    for row, _ in steps])
    chose = [np.concatenate([c] + [np.asarray(step[j]) for _, step in steps])
             for j, c in enumerate(chose)]
    return got, chose, answer, timed, eng._bucket(p), len(first) + len(behind)


def compare(got, chose, params, seq, gen, idle, p: int, m: dict, tol: dict,
            positions: bool = False):
    """`got` (p + g - 1, vocab) against the reference's full forward of
    `seq`, the reference following the system's `chose`n experts inside
    `tie_margin_rel` (reference_lfm2moe's docstring); every position's
    largest logit error in units of the logits' standard deviation. The
    mean over the PROMPT's positions (random tokens through the
    convolution over the whole prompt, the flash or plain attention and
    the grouped matmuls at a prefill's rows) is the tight limit; the
    mean over the DECODE steps (the engine's own greedy tokens through
    the slot state, the packed pool and the paged kernel) shows what
    the prompt cannot: a state that was not stopped at the prompt's
    true length, a pool row read wrongly. The largest error of all
    positions is the backstop for a fault at few of them. The
    convolution's START-UP (positions before its K taps are full) is
    read on its own and held to the same limits. A choice of experts
    that the reference did not follow fails the comparison by itself.
    And the engine's own greedy tokens (this answer's, and the `idle`
    engine's as far as the two answers share their context) may each
    lie only so far under the reference's largest logit."""
    import jax.numpy as jnp
    g = gen.size
    margin = float(tol["tie_margin_rel"])
    ref, rec = reference_lfm2moe.forward(
        params, jnp.asarray(seq), m,
        follow=[jnp.asarray(c) for c in chose], tie_margin=margin)
    ref = np.asarray(ref, np.float32)
    margins = np.stack([np.asarray(r["margin_rel"]) for r in rec])  # (L, S)
    same = np.stack([np.asarray(r["own"]) for r in rec])
    bad = np.stack([np.asarray(r["not_followed"]) for r in rec])
    swap = np.stack([np.asarray(r["swap_rel"]) for r in rec])
    scale = float(ref.std())
    err_pos = np.abs(got - ref).max(-1) / scale
    k = int(m["conv_L_cache"]) - 1
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
    ok = (err <= tol["logit_tol_rel"] and mean <= tol["logit_mean_tol_rel"]
          and mean_decode <= tol["logit_decode_mean_tol_rel"]
          and gap <= tol["argmax_tol_rel"] and not bad.any()
          and bool(np.isfinite(got).all()))
    out = {"logit_err_rel": err, "logit_tol_rel": tol["logit_tol_rel"],
           "logit_err_rel_mean": mean,
           "logit_mean_tol_rel": tol["logit_mean_tol_rel"],
           "logit_err_rel_decode_mean": mean_decode,
           "logit_decode_mean_tol_rel": tol["logit_decode_mean_tol_rel"],
           "logit_err_rel_startup": float(err_pos[:k].max()),
           "logit_err_rel_decode": float(err_pos[p:].max())
           if g > 1 else None,
           "worst_position": int(err_pos.argmax()),
           "argmax_gap_rel": gap, "argmax_tol_rel": tol["argmax_tol_rel"],
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
    """`system_logits` on the seeded prompt, answered once more while
    every slot is live, against the float32 reference's full forward
    pass over the prompt and that answer: logits of every prompt
    position and of every decode step, the reference following the
    choices of the engine's own 129-row programs inside the tie margin.
    What the idle engine answered over HTTP (`spec["generated"]`) is
    held to the same reference as far as it shares the busy answer's
    context. `tokens_as_idle` and `tokens_with_logits_as_timed` are
    readings only: in an expert model a near-tie of two experts or of
    two words may fall the other way in a program compiled apart or run
    beside other rows (PERF.md, PR 39). `spec["controls"]` (a builder's
    tool, never a benchmark run): names of `reference_lfm2moe`'s
    deliberately wrong models; the answer then holds, under `controls`,
    the same comparison against each."""
    prompt = np.asarray(spec["prompt"], np.int32)
    idle = np.asarray(spec["generated"], np.int32)
    tol = spec["check"]
    p = prompt.size
    got, chose, gen, timed, pad, beside = system_logits(
        engine, prompt, int(tol.get("busy_new_tokens", idle.size)))
    seq = np.concatenate([prompt, gen])[:-1]          # p + g - 1 inputs
    m = spec["model"]
    detail = bool(spec.get("controls"))
    out = compare(got, chose, engine.params, seq, gen, idle, p, m, tol,
                  detail)
    out.update(layers=int(m["num_hidden_layers"]), prompt_len=int(p),
               new_tokens=int(gen.size), prefill_bucket=int(pad),
               slots=int(engine.cfg.max_slots), requests_beside=int(beside),
               tokens_as_idle=bool(
                   idle.size and (timed[:idle.size] == idle).all()),
               tokens_with_logits_as_timed=bool((gen == timed).all()))
    if detail:
        out["controls"] = {
            name: compare(got, chose, engine.params, seq, gen, idle, p,
                          dict(m, controls=frozenset([name]), bucket=pad,
                               prompt_len=p), tol, True)
            for name in spec["controls"]}
    return out


class Lfm2MoeBenchServer(BenchServer):

    def bench_check(self, spec: Dict[str, Any]) -> Dict[str, Any]:
        return serve_check(self.engine, spec)
