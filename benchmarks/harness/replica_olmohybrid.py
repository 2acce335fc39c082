"""The benchmark's side of an Olmo-Hybrid-7B replica (Gated DeltaNet
layers with a recurrent state a slot beside full-attention layers that
page K and V): `BenchServer` with the Hybrid model factory and the
comparison against `reference_olmohybrid`. Everything else (warm-up,
sampler, trace, stats) is `BenchServer`'s. The program's
`HybridConfig.olmo_hybrid_7b` preset is looked up before anything is
built: a program without it cannot run this configuration and says so
at once.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

from . import modelcfg, reference_olmohybrid
from .replica import BenchServer

LINEAR_KEYS = ("layer_types", "linear_num_key_heads",
               "linear_num_value_heads", "linear_key_head_dim",
               "linear_value_head_dim", "linear_conv_kernel_dim",
               "linear_allow_neg_eigval")


def model_section(cfg: dict) -> dict:
    """The published keys the program, the reference and the cost
    arithmetic read: the Llama-shaped section and the `linear_*` keys,
    `layer_types` cut to the `num_hidden_layers` layers held."""
    m = modelcfg.model_section(cfg)
    missing = [k for k in LINEAR_KEYS if k not in cfg]
    if missing:
        raise SystemExit(f"benchmark: {cfg['name']}.json lacks {missing}")
    kinds = list(cfg["layer_types"])[:m["num_hidden_layers"]]
    if (len(kinds) != m["num_hidden_layers"] or m["rope_theta"] is not None
            or m["tie_word_embeddings"]
            or cfg["linear_num_key_heads"] != cfg["linear_num_value_heads"]):
        raise SystemExit(
            "benchmark: reference_olmohybrid.py has a mixer for every "
            "layer held, no rotation in the full layers, an untied head "
            "and as many key as value heads in the linear layers; this "
            "file disagrees")
    return {**m, **{k: cfg[k] for k in LINEAR_KEYS}, "layer_types": kinds}


def hybrid_preset():
    """The program's preset, or a clean failure where it has none."""
    try:
        from ray_tpu.models import HybridConfig
    except ImportError:
        HybridConfig = None
    preset = getattr(HybridConfig, "olmo_hybrid_7b", None)
    if preset is None:
        raise SystemExit(
            "benchmark: this program has no HybridConfig.olmo_hybrid_7b "
            "(a mixer a layer from layer_types, Gated DeltaNet layers "
            "with a recurrent state a slot beside the page pool): it "
            "cannot run an Olmo-Hybrid configuration")
    return preset


def hybrid_config(cfg: dict, *, param_dtype, **kw):
    m = model_section(cfg)
    return hybrid_preset()(
        vocab_size=m["vocab_size"], d_model=m["hidden_size"],
        n_layers=m["num_hidden_layers"],
        layer_types=tuple(m["layer_types"]),
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], d_ff=m["intermediate_size"],
        linear_n_heads=m["linear_num_value_heads"],
        linear_key_dim=m["linear_key_head_dim"],
        linear_value_dim=m["linear_value_head_dim"],
        linear_conv_kernel=m["linear_conv_kernel_dim"],
        linear_allow_neg_eigval=bool(m["linear_allow_neg_eigval"]),
        # what the engine can reach, not the published 65 536 (nothing
        # of the model is sized by it: the full layers do not rotate)
        max_seq_len=cfg["engine"]["max_seq_len"],
        norm_eps=float(m["rms_norm_eps"]), param_dtype=param_dtype, **kw)


def model_factory(cfg: dict, seed: int):
    """Runs inside the replica: (model, params), bf16 weights made on the
    replica's device in one jitted call from the seed."""
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
    params = jax.jit(lambda k: model.init(
        k, jnp.zeros((1, 8), jnp.int32))["params"])(key)
    jax.block_until_ready(params)
    return model, params


def _busy_traffic(engine, prompt: np.ndarray, n_new: int):
    """What runs beside the check's request so that it is answered as a
    request of the window is: as many short requests as the engine has
    slots in front of it (every slot taken, so the check's prompt goes
    into a slot another sequence has just left) and as many behind it
    that outlive it (every other slot decoding while it decodes).
    Seeded from the prompt; each is shorter than the check's request,
    so the decode window is the check's. [(prompt, new tokens)] x 2."""
    rng = np.random.default_rng([int(t) for t in prompt[:8]])
    vocab = int(engine.model.cfg.vocab_size)
    slots = int(engine.cfg.max_slots)
    buckets = sorted(engine.cfg.prefill_buckets)
    hi = max([b for b in buckets if b < prompt.size] or [buckets[0]])
    hi = min(hi, prompt.size - 1)
    lo = max(1, min(buckets[0] // 2, hi))

    def some(new_tokens):
        return [(rng.integers(1, vocab, int(rng.integers(lo, hi + 1))),
                 int(t)) for t in new_tokens]
    return (some(rng.integers(2, 13, slots)),
            some(np.full(slots, n_new + 16)))


def system_logits(engine, prompt: np.ndarray, n_new: int):
    """The tokens and the logits of the engine's own step programs, on
    its own pools, with every slot live. The check's prompt goes in
    through `submit` between `_busy_traffic`'s two halves, and the
    engine's loop admits, prefills, decodes and releases all of them as
    it does in the window (its scheduler, page allocator, slot pool,
    `pipeline_depth`); twice. The first time every program is the timed
    one, and the request's tokens are what they answer. The second time
    the dispatches that carry the check's request run the engine's own
    `_prefill_paged_step` / `_decode_paged_step`, traced once more with
    one more output: the logits `_apply_counted` handed back, cut to
    the request's row. Nothing else differs from the timed programs:
    the packed control vector, `slots` / `restart` / `fresh`, the
    scratch row, the donated pools, the window of the page table.

    Returns the logits of every prompt position and of every decode
    step (p + n_new - 1, vocab) float32, the n_new tokens answered with
    them, the n_new tokens the timed programs answered, the bucket, and
    how many requests ran beside."""
    import jax
    import jax.numpy as jnp

    eng, p = engine, prompt.size
    first, behind = _busy_traffic(eng, prompt, n_new)
    mine: Dict[str, Any] = {"rid": None, "prefill": None, "decode": []}

    def traced_once_more(step, static, keep):
        def run(params, pools, state, ctl, **kw):
            seen = []
            inner = eng._apply_counted

            def spy(*args):
                out = inner(*args)
                seen.append(out[0])
                return out
            eng._apply_counted = spy
            try:
                out = step(params, pools, state, ctl, **kw)
            finally:
                del eng._apply_counted
            return out, keep(seen[0]).astype(jnp.float32)
        return jax.jit(run, static_argnames=static, donate_argnums=(1, 2))

    def dispatch_prefill(inflight, pad_len, members):
        rows = [i for i, (req, _slot) in enumerate(members)
                if req.request_id == mine["rid"]]
        if rows:
            program = traced_once_more(
                eng._prefill_paged_step, ("pad_len",),
                lambda logits: logits[rows[0], :p])

            def with_logits(*args, **kw):
                out, logits = program(*args, **kw)
                mine["prefill"] = np.asarray(logits)
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
                    lambda logits: logits[slot[0], 0])

            def with_logits(*args, **kw):
                out, logits = mine["program"](*args, **kw)
                mine["decode"].append(logits)
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
    steps = [np.asarray(row)[None] for row in mine["decode"][:n_new - 1]]
    got = np.concatenate([mine["prefill"]] + steps)
    return got, answer, timed, eng._bucket(p), len(first) + len(behind)


def compare(got, params, seq, gen, idle, p: int, m: dict, tol: dict,
            positions: bool = False):
    """`got` (p + g - 1, vocab) against the reference's full forward of
    `seq`, every position's largest logit error in units of the logits'
    standard deviation, read in three stretches. The START-UP, the
    first `linear_conv_kernel_dim` positions, is reported and held to
    nothing: until the convolution's window is full the state holds one
    to three terms, o_0 = beta (k_0 . q_0) v_0, the per-head RMSNorm
    divides beta (k . q) out again, and where k . q lies within bf16's
    rounding of zero the head's sign is the rounding's, in the system
    and in the reference alike (0.07-0.74 at position 0 over 43 runs).
    The rest of the PROMPT (random tokens through the chunkwise form)
    is well conditioned: its mean is the tight limit. The DECODE steps
    (the engine's own greedy tokens through the step kernel) show what
    the prompt cannot, a state that was not stopped at the prompt's
    true length, and their mean has a limit of its own, a looser one:
    a greedy continuation is less well conditioned than random tokens,
    and more so the longer it runs. The largest error of prompt and
    decode steps is the backstop for a fault at few positions. And the
    engine's own greedy tokens (this answer's, and the `idle` engine's
    as far as the two answers share their context) may each lie only
    so far under the reference's largest logit."""
    import jax.numpy as jnp
    g = gen.size
    ref = np.asarray(reference_olmohybrid.forward_logits(
        params, jnp.asarray(seq), m), np.float32)
    scale = float(ref.std())
    err_pos = np.abs(got - ref).max(-1) / scale
    k = int(m["linear_conv_kernel_dim"])
    err = float(err_pos[k:].max())
    mean = float(err_pos[k:p].mean())
    mean_decode = float(err_pos[p:].mean()) if g > 1 else 0.0
    last = ref[p - 1:]                                 # the g sampled rows
    same = idle[:g] == gen[:idle.size]
    shared = same.size if same.all() else int(same.argmin()) + 1
    gap = float(max(
        (last.max(-1) - last[np.arange(g), gen]).max(),
        (last[:shared].max(-1)
         - last[np.arange(shared), idle[:shared]]).max(initial=0.0))) / scale
    ok = (err <= tol["logit_tol_rel"] and mean <= tol["logit_mean_tol_rel"]
          and mean_decode <= tol["logit_decode_mean_tol_rel"]
          and gap <= tol["argmax_tol_rel"] and bool(np.isfinite(got).all()))
    out = {"logit_err_rel": err, "logit_tol_rel": tol["logit_tol_rel"],
           "logit_err_rel_mean": mean,
           "logit_mean_tol_rel": tol["logit_mean_tol_rel"],
           "logit_err_rel_decode_mean": mean_decode,
           "logit_decode_mean_tol_rel": tol["logit_decode_mean_tol_rel"],
           "logit_err_rel_startup": float(err_pos[:k].max()),
           "logit_err_rel_decode": float(err_pos[p:].max())
           if g > 1 else None,
           "worst_position": k + int(err_pos[k:].argmax()),
           "argmax_gap_rel": gap, "argmax_tol_rel": tol["argmax_tol_rel"],
           "positions": int(got.shape[0]), "logit_std": scale,
           "ok": bool(ok)}
    if positions:
        out["err_positions"] = [round(float(e), 5) for e in err_pos]
    return out


def serve_check(engine, spec: Dict[str, Any]) -> Dict[str, Any]:
    """`system_logits` on the seeded prompt, answered once more while
    every slot is live, against the float32 reference's full forward
    pass over the prompt and that answer: logits of every prompt
    position and of every decode step. What the idle engine answered
    over HTTP (`spec["generated"]`) is held to the same reference, and
    to what the timed programs answer with every slot live
    (`tokens_as_idle`, part of `ok`: a slot's sequence does not depend
    on its neighbours or on who held the slot before).
    `tokens_with_logits_as_timed` is a reading only: the programs that
    hand out logits are compiled apart from the timed ones, and a
    near-tie may fall the other way. `spec["controls"]` (a builder's
    tool, never a benchmark run): names of `reference_olmohybrid`'s
    deliberately wrong models; the answer then holds, under `controls`,
    the same comparison against each."""
    prompt = np.asarray(spec["prompt"], np.int32)
    idle = np.asarray(spec["generated"], np.int32)
    tol = spec["check"]
    p = prompt.size
    got, gen, timed, pad, beside = system_logits(
        engine, prompt, int(tol.get("busy_new_tokens", idle.size)))
    seq = np.concatenate([prompt, gen])[:-1]          # p + g - 1 inputs
    m = spec["model"]
    detail = bool(spec.get("controls"))
    out = compare(got, engine.params, seq, gen, idle, p, m, tol, detail)
    as_idle = bool(idle.size and (timed[:idle.size] == idle).all())
    out.update(layers=int(m["num_hidden_layers"]), prompt_len=int(p),
               new_tokens=int(gen.size), prefill_bucket=int(pad),
               slots=int(engine.cfg.max_slots), requests_beside=int(beside),
               tokens_as_idle=as_idle,
               tokens_with_logits_as_timed=bool((gen == timed).all()),
               ok=bool(out["ok"] and as_idle))
    if detail:
        out["controls"] = {
            name: compare(got, engine.params, seq, gen, idle, p,
                          dict(m, controls=frozenset([name]), bucket=pad,
                               prompt_len=p), tol, True)
            for name in spec["controls"]}
    return out


class OlmoHybridBenchServer(BenchServer):

    def bench_check(self, spec: Dict[str, Any]) -> Dict[str, Any]:
        return serve_check(self.engine, spec)
