"""The benchmark's side of a Solar-Open2-250B replica (delta-rule layers
with a decay a key channel and a 4 MiB state a slot beside gated NoPE
grouped-query layers that page K and V, an expert share and a shared
expert in every layer): `BenchServer` with the Hybrid model factory and
the comparison against `reference_solar`. Everything else (warm-up,
sampler, trace, stats) is `BenchServer`'s. The program's
`HybridConfig.solar_open2_250b` preset is looked up before anything is
built: a program without it cannot run this configuration and says so
at once.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

from . import reference_solar
from .replica import BenchServer
from .replica_lfm2moe import _routing
from .replica_olmohybrid import _busy_traffic

MODEL_KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "head_dim", "intermediate_size",
              "moe_intermediate_size", "n_routed_experts",
              "n_shared_experts", "num_experts_per_tok", "norm_topk_prob",
              "routed_scaling_factor", "first_k_dense_replace",
              "rms_norm_eps", "vocab_size", "max_position_embeddings",
              "tie_word_embeddings", "use_rope", "use_gqa_gate",
              "gqa_layers", "linear_attn_config", "kda_use_full_proj",
              "kda_allow_neg_eigval", "kda_rank")


def model_section(cfg: dict) -> dict:
    """The published keys the program, the reference, the cost
    arithmetic and the accepted readers read, and the share:
    `num_experts` (= `n_routed_experts`, the key the accepted
    moe_counter reader divides by) experts held from `expert_first` of a
    router `router_width` wide; `layer_types` one entry a layer held,
    from `gqa_layers`."""
    missing = [k for k in MODEL_KEYS + ("expert_parallel",) if k not in cfg]
    if missing:
        raise SystemExit(f"benchmark: {cfg['name']}.json lacks {missing}")
    lin, ep = cfg["linear_attn_config"], cfg["expert_parallel"]
    if (cfg["tie_word_embeddings"] or cfg["use_rope"]
            or cfg["first_k_dense_replace"] or cfg["kda_use_full_proj"]
            or not cfg["use_gqa_gate"] or lin["num_kv_heads"] is not None
            or cfg["n_shared_experts"] != 1):
        raise SystemExit(
            "benchmark: reference_solar.py has an untied head, full layers "
            "without rotation and with an output gate, low-rank decay and "
            "gate pairs, as many key as query heads in the linear layers, "
            "an expert layer with one shared expert in every block; this "
            "file disagrees")
    if ep["router_width"] != ep["ways"] * cfg["n_routed_experts"]:
        raise SystemExit("benchmark: the experts held times the ways of "
                         "expert parallelism is not the router's width")
    m = {k: cfg[k] for k in MODEL_KEYS}
    m.update(num_experts=cfg["n_routed_experts"],
             router_width=ep["router_width"],
             expert_first=ep["rank"] * cfg["n_routed_experts"])
    m["layer_types"] = reference_solar.layer_types(m)
    return m


def solar_preset():
    """The program's preset, or a clean failure where it has none."""
    try:
        from ray_tpu.models import HybridConfig
    except ImportError:
        HybridConfig = None
    preset = getattr(HybridConfig, "solar_open2_250b", None)
    if preset is None:
        raise SystemExit(
            "benchmark: this program has no HybridConfig.solar_open2_250b "
            "(delta-rule layers with a decay a key channel, gated "
            "grouped-query layers without rotation, an expert share "
            "beside a shared expert inside a hybrid): it cannot run a "
            "Solar-Open2 configuration")
    return preset


def hybrid_config(cfg: dict, *, param_dtype, **kw):
    m, lin = model_section(cfg), cfg["linear_attn_config"]
    return solar_preset()(
        vocab_size=m["vocab_size"], d_model=m["hidden_size"],
        n_layers=m["num_hidden_layers"],
        layer_types=tuple(m["layer_types"]),
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], attn_head_dim=m["head_dim"],
        out_gate=bool(m["use_gqa_gate"]), d_ff=m["intermediate_size"],
        linear_n_heads=lin["num_heads"], linear_key_dim=lin["head_dim"],
        linear_value_dim=lin["head_dim"],
        linear_conv_kernel=lin["short_conv_kernel_size"],
        linear_allow_neg_eigval=bool(m["kda_allow_neg_eigval"]),
        kda_rank=m["kda_rank"], n_dense_layers=m["first_k_dense_replace"],
        d_expert=m["moe_intermediate_size"], n_experts=m["router_width"],
        expert_first=m["expert_first"], expert_count=m["num_experts"],
        experts_per_token=m["num_experts_per_tok"],
        n_shared_experts=m["n_shared_experts"],
        norm_topk_prob=bool(m["norm_topk_prob"]),
        routed_scaling=float(m["routed_scaling_factor"]),
        # what the engine can reach, not the published 1 048 576
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


class _WithSown:
    """The engine's model for the length of one trace
    (`replica_lfm2moe._WithRouting` with a second collection): `apply`
    asks for `routing` (the experts chosen) and `recurrence` (what went
    into a delta-rule layer's recurrence and what came out) beside what
    the engine asks for and keeps them in `seen`, so that the engine's
    own `_apply_counted` runs as it is."""

    def __init__(self, model, seen: list):
        self._model, self._seen = model, seen

    def __getattr__(self, name):
        return getattr(self._model, name)

    def apply(self, variables, *args, mutable=(), **kw):
        out, sown = self._model.apply(
            variables, *args, mutable=[*mutable, "routing", "recurrence"],
            **kw)
        sown = dict(sown)
        self._seen.append((sown.pop("routing"), sown.pop("recurrence")))
        return out, sown


def system_logits(engine, prompt: np.ndarray, n_new: int):
    """`replica_lfm2moe.system_logits` (the tokens, the logits and the
    experts chosen by the engine's own step programs, on its own pools,
    with every slot live: the check's prompt goes in through `submit`
    between `_busy_traffic`'s two halves, the engine's loop serves them
    all, twice; the second time the dispatches that carry the check's
    request run `_prefill_paged_step` / `_decode_paged_step` traced once
    more with the logits handed out) with one thing more handed out of
    the same calls: the FIRST delta-rule layer's q, k, v, g, beta and
    the recurrence's output o at the request's row
    (`models/hybrid.py:_delta_rule` sows them), the chunkwise form over
    the bucket in the prefill program, `kda_decode_step` over the slot
    pool in the decode program.

    Returns the logits of every prompt position and of every decode
    step (p + n_new - 1, vocab) float32, per expert layer the experts
    chosen at those positions, the n_new tokens answered with them, the
    n_new tokens the timed programs answered, the bucket, how many
    requests ran beside, and the recurrence: {"inputs": (q, k, v, g,
    beta), "o": o}, float32, (p + n_new - 1, H, .) each."""
    import jax
    import jax.numpy as jnp

    eng, p = engine, prompt.size
    mc = eng.model.cfg
    moe_layers = [i for i in range(mc.n_layers) if not mc.dense_ff(i)]
    kda = f"layer_{mc.layer_types.index('kda')}"
    first, behind = _busy_traffic(eng, prompt, n_new)
    mine: Dict[str, Any] = {"rid": None, "prefill": None, "decode": []}

    def traced_once_more(step, static, cut):
        def run(params, pools, state, ctl, **kw):
            seen, sown = [], []
            inner, model = eng._apply_counted, eng.model

            def spy(*args):
                out = inner(*args)
                seen.append(out[0])
                return out
            eng._apply_counted = spy
            eng.model = _WithSown(model, sown)
            try:
                out = step(params, pools, state, ctl, **kw)
            finally:
                del eng._apply_counted
                eng.model = model
            routed, recurrence = sown[0]
            return (out, cut(seen[0]).astype(jnp.float32),
                    _routing(routed, moe_layers, cut),
                    [cut(x).astype(jnp.float32)
                     for x in recurrence[kda]["kda"]["io"][0]])
        return jax.jit(run, static_argnames=static, donate_argnums=(1, 2))

    def dispatch_prefill(inflight, pad_len, members):
        rows = [i for i, (req, _slot) in enumerate(members)
                if req.request_id == mine["rid"]]
        if rows:
            program = traced_once_more(eng._prefill_paged_step, ("pad_len",),
                                       lambda x: x[rows[0], :p])

            def handing_out(*args, **kw):
                out, *mine["prefill"] = program(*args, **kw)
                return out
            real, eng._prefill_paged_jit = eng._prefill_paged_jit, handing_out
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
                    lambda x: x[slot[0], 0])

            def handing_out(*args, **kw):
                out, *taps = mine["program"](*args, **kw)
                mine["decode"].append(taps)
                return out
            real, eng._decode_paged_jit = eng._decode_paged_jit, handing_out
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
    answer = serve(True)        # and once more, handing things out
    # the loop runs ahead of what it has drained: steps dispatched after
    # the request's last token are discarded by the engine, and here
    steps = mine["decode"][:n_new - 1]
    block, chose, io = mine["prefill"]

    def whole(first_part, later):
        return np.concatenate([np.asarray(first_part)]
                              + [np.asarray(x)[None] for x in later])
    got = whole(block, [s[0] for s in steps])
    chose = [whole(c, [s[1][j] for s in steps]) for j, c in enumerate(chose)]
    io = [whole(x, [s[2][j] for s in steps]) for j, x in enumerate(io)]
    return (got, chose, answer, timed, eng._bucket(p),
            len(first) + len(behind), {"inputs": tuple(io[:5]), "o": io[5]})


def compare(got, chose, params, seq, gen, idle, p: int, m: dict, tol: dict,
            positions: bool = False, recurrence=None):
    """`got` (p + g - 1, vocab) against the reference's full forward of
    `seq`, the reference following the system's `chose`n experts inside
    `tie_margin_rel` (reference_solar's docstring); every position's
    largest logit error in units of the logits' standard deviation. The
    mean over the PROMPT's positions (the chunkwise form of the delta
    rule over the whole prompt, stopped at its true length inside the
    bucket, the flash or plain attention with its gate, the grouped
    matmuls of the share at a prefill's rows) is the tight limit; the
    mean over the DECODE steps (the engine's own greedy tokens through
    the slot state and `kda_decode_step`, the paged kernel over 8 KV
    heads) shows what the prompt cannot: a state that was not stopped
    at the prompt's true length, a state row or a pool row read
    wrongly. The largest error of all positions is the backstop for a
    fault at few of them. A choice of experts that the reference did
    not follow fails the comparison by itself. And the engine's own
    greedy tokens (this answer's, and the `idle` engine's as far as the
    two answers share their context) may each lie only so far under the
    reference's largest logit. The recurrence's own precision is read
    where rounding elsewhere cannot hide it: `recurrence`
    (`system_logits`: what the engine's own prefill and decode programs
    fed the first delta-rule layer's recurrence at this request's row,
    and what they got from it) against the reference's token-by-token
    recurrence on those same inputs, the largest difference of any
    output of any position in units of the outputs' standard deviation.
    Float32 both sides reads two orders of summation; a state held in
    bfloat16 a thousand times that."""
    import jax
    import jax.numpy as jnp
    g = gen.size
    margin = float(tol["tie_margin_rel"])
    rec_err = None
    if recurrence is not None:
        with jax.default_matmul_precision("highest"):
            o_ref = np.asarray(jax.jit(
                lambda *a: reference_solar.kda_recurrence(*a, m)[0])(
                    *recurrence["inputs"]))
        rec_err = float(np.abs(recurrence["o"] - o_ref).max() / o_ref.std())
    ref, rec = reference_solar.forward(
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
    ok = (err <= tol["logit_tol_rel"] and mean <= tol["logit_mean_tol_rel"]
          and (rec_err is None or rec_err <= tol["recurrence_tol_rel"])
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
           "recurrence_err_rel": rec_err,
           "recurrence_tol_rel": tol.get("recurrence_tol_rel"),
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
    """`system_logits` (the logits, the expert choices and the first
    recurrence of the ENGINE's own step programs, with every slot live: the
    prefill of the seeded prompt, which ends inside its bucket and goes
    into a slot another sequence has just left, then the decode steps
    through the slot state and the pages at 129 rows) against the
    float32 reference's full forward pass over the prompt and that
    answer. What the idle engine answered over HTTP
    (`spec["generated"]`) is held to the same reference as far as it
    shares the busy answer's context. `tokens_as_idle` and
    `tokens_with_logits_as_timed` are readings only: a near-tie of two
    experts or of two words may fall the other way in a program compiled
    apart or run beside other rows (PERF.md, PR 39). `spec["controls"]`
    (a builder's tool, never a benchmark run): names of
    `reference_solar`'s deliberately wrong models; the answer then
    holds, under `controls`, the same comparison against each."""
    prompt = np.asarray(spec["prompt"], np.int32)
    idle = np.asarray(spec["generated"], np.int32)
    tol = spec["check"]
    p = prompt.size
    got, chose, gen, timed, pad, beside, recurrence = system_logits(
        engine, prompt, int(tol.get("busy_new_tokens", idle.size)))
    seq = np.concatenate([prompt, gen])[:-1]          # p + g - 1 inputs
    m = spec["model"]
    detail = bool(spec.get("controls"))
    out = compare(got, chose, engine.params, seq, gen, idle, p, m, tol,
                  detail, recurrence)
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
                               prompt_len=p), tol, True, recurrence)
            for name in spec["controls"]}
    return out


def chunk_scan_seconds(trace_dir: str, loops_a_prefill: int,
                       device_plane_re: str = r"^/device:TPU:\d+$",
                       prefill_re: str = r"_prefill_paged_step") -> float:
    """Device seconds of the chunkwise form's loops over chunks. A TPU
    trace names an operation by its HLO line without its metadata
    (PERF.md section 7, PR 26 (a)): a named scope does not reach it, and
    the loop is a `while` like the binary search inside the grouped
    matmul. They are told apart by where and how long they run: in each
    run of a prefill program (the `XLA Modules` line) the
    `loops_a_prefill` longest `while` operations are the delta-rule
    layers' loops, one a layer (2 to 32 chunks of matmuls each; a search
    is microseconds). A decode program has searches only. A mean over
    the devices."""
    import re
    from jax.profiler import ProfileData
    from . import trace_reduce
    data = ProfileData.from_file(trace_reduce.find_xplane(trace_dir))
    seconds, devices = 0.0, 0
    for plane in data.planes:
        if not re.search(device_plane_re, plane.name):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        ops = trace_reduce._line_events(lines, "XLA Ops")
        if not ops:
            continue
        devices += 1
        loops = sorted((s, e) for s, e, name in ops
                       if trace_reduce.category(name) == "while")
        for m0, m1, name in trace_reduce._line_events(lines, "XLA Modules"):
            if re.search(prefill_re, name):
                inside = sorted((e - s for s, e in loops if m0 <= s < m1),
                                reverse=True)
                seconds += sum(inside[:loops_a_prefill])
    return seconds / devices if devices else 0.0


class SolarBenchServer(BenchServer):

    def bench_trace_reduce(self, spec: Dict[str, Any]) -> Dict[str, Any]:
        out = super().bench_trace_reduce(spec)
        if out.get("devices"):
            out["kda_scan_s"] = chunk_scan_seconds(
                spec["dir"], self.engine.model.cfg.layer_types.count("kda"))
        return out

    def bench_check(self, spec: Dict[str, Any]) -> Dict[str, Any]:
        return serve_check(self.engine, spec)
