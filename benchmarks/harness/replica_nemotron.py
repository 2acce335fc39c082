"""The benchmark's side of a Nemotron-3-Super-120B-A12B replica (Mamba-2
layers with a 4 MiB state a slot, one NoPE 32Q/2KV layer that pages K
and V, expert layers that hold a share of 512 relu^2 experts computed in
a latent beside a shared expert at full width): `BenchServer` with the
Hybrid model factory and the comparison against `reference_nemotron`.
Everything else (warm-up, sampler, trace, stats) is `BenchServer`'s, and
the drive of the engine's own step programs is `replica_solar`'s, by
import. The program's `HybridConfig.nemotron_3_super_120b` preset is
looked up before anything is built: a program without it cannot run this
configuration and says so at once.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

from . import reference_nemotron, replica_solar
from .replica import BenchServer

# every number of the catalog row's `config`, under its own key
MODEL_KEYS = (
    "attention_bias", "chunk_size", "conv_kernel", "expand", "head_dim",
    "hidden_size", "hybrid_override_pattern", "intermediate_size",
    "layer_norm_epsilon", "mamba_head_dim", "mamba_num_heads",
    "mamba_proj_bias", "max_position_embeddings", "mlp_bias",
    "mlp_hidden_act", "moe_intermediate_size", "moe_latent_size",
    "moe_shared_expert_intermediate_size", "n_group", "n_groups",
    "n_routed_experts", "n_shared_experts", "norm_topk_prob",
    "num_attention_heads", "num_experts_per_tok", "num_hidden_layers",
    "num_key_value_heads", "rope_theta", "routed_scaling_factor",
    "ssm_state_size", "tie_word_embeddings", "topk_group", "use_bias",
    "use_conv_bias", "vocab_size")


def model_section(cfg: dict) -> dict:
    """The published keys the program, the reference, the cost
    arithmetic and the accepted readers read, and the share:
    `num_experts` (= `n_routed_experts`, the key the accepted moe_counter
    reader divides by) experts held from `expert_first` of a router
    `router_width` wide."""
    missing = [k for k in MODEL_KEYS + ("expert_parallel",) if k not in cfg]
    if missing:
        raise SystemExit(f"benchmark: {cfg['name']}.json lacks {missing}")
    ep, pattern = cfg["expert_parallel"], cfg["hybrid_override_pattern"]
    if (cfg["tie_word_embeddings"] or cfg["attention_bias"]
            or cfg["mamba_proj_bias"] or cfg["mlp_bias"] or cfg["use_bias"]
            or cfg["mlp_hidden_act"] != "relu2" or not cfg["use_conv_bias"]
            or cfg["n_group"] != 1 or cfg["topk_group"] != 1
            or cfg["n_shared_experts"] != 1
            or cfg["expand"] * cfg["hidden_size"]
            != cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
            or len(pattern) != cfg["num_hidden_layers"]
            or set(pattern) - set("M*E")):
        raise SystemExit(
            "benchmark: reference_nemotron.py has an untied head, no "
            "projection bias, a convolution bias, relu^2 experts without "
            "expert groups beside one shared expert, an inner width of "
            "expand x hidden_size and one of M, *, E a layer of "
            "hybrid_override_pattern; this file disagrees")
    if ep["router_width"] != ep["ways"] * cfg["n_routed_experts"]:
        raise SystemExit("benchmark: the experts held times the ways of "
                         "expert parallelism is not the router's width")
    m = {k: cfg[k] for k in MODEL_KEYS}
    m.update(num_experts=cfg["n_routed_experts"],
             router_width=ep["router_width"],
             expert_first=ep["rank"] * cfg["n_routed_experts"])
    return m


def nemotron_preset():
    """The program's preset, or a clean failure where it has none."""
    try:
        from ray_tpu.models import HybridConfig
    except ImportError:
        HybridConfig = None
    preset = getattr(HybridConfig, "nemotron_3_super_120b", None)
    if preset is None:
        raise SystemExit(
            "benchmark: this program has no "
            "HybridConfig.nemotron_3_super_120b (Mamba-2 layers over a "
            "slot state, blocks whose feed-forward is experts or none, "
            "relu^2 experts computed in a latent beside a shared expert "
            "at full width): it cannot run a Nemotron-3 configuration")
    return preset


def hybrid_config(cfg: dict, *, param_dtype, **kw):
    m = model_section(cfg)
    return nemotron_preset()(
        m["hybrid_override_pattern"],
        vocab_size=m["vocab_size"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], attn_head_dim=m["head_dim"],
        ssm_n_heads=m["mamba_num_heads"], ssm_head_dim=m["mamba_head_dim"],
        ssm_state=m["ssm_state_size"], ssm_groups=m["n_groups"],
        ssm_conv_kernel=m["conv_kernel"], ssm_chunk=m["chunk_size"],
        d_expert=m["moe_intermediate_size"],
        d_shared=m["moe_shared_expert_intermediate_size"],
        moe_latent_dim=m["moe_latent_size"], n_experts=m["router_width"],
        expert_first=m["expert_first"], expert_count=m["num_experts"],
        experts_per_token=m["num_experts_per_tok"],
        n_shared_experts=m["n_shared_experts"],
        norm_topk_prob=bool(m["norm_topk_prob"]),
        routed_scaling=float(m["routed_scaling_factor"]),
        # what the engine can reach, not the published 262 144
        max_seq_len=cfg["engine"]["max_seq_len"],
        norm_eps=float(m["layer_norm_epsilon"]), param_dtype=param_dtype,
        **kw)


# the aux-loss-free balancing rule's steps: each round every expert's
# selection bias moves by this much against its load, from 0.05 (a
# fifth of the scores' spread) down to 0.001 in equal ratios
BALANCE_STEPS = tuple(0.05 * (0.001 / 0.05) ** (i / 63) for i in range(64))


def balance_selection_bias(model, params, seed: int):
    """The selection bias as training would have left it. An expert
    here is relu(.)^2 between two matrices: its result is never
    negative before the second matrix, so with seeded weights every
    token's residual stream gains the same direction in every expert
    layer, the router's input is ever more alike from token to token,
    and by the fifth expert layer every token of 2 048 chose the same
    few experts (134 of the router's 512 ever touched, 17 of the 64
    held: my chip run, PR 56). A trained model's selection bias is what
    balanced its load (`assumed.selection_bias`), so the seeded one is
    put through the rule that makes it (aux-loss-free balancing:
    b_e <- b_e - gamma sign(load_e - mean load), all expert layers at
    once) for `BALANCE_STEPS` rounds on 2 x 1 024 seeded token ids in
    the program's plain forward, on the device, before the engine is
    built. Program and reference read the same bias; nothing else of
    the parameters moves."""
    import jax
    import jax.numpy as jnp
    mc = model.cfg
    layers = [i for i in range(mc.n_layers) if mc.ff_kind(i) == "experts"]
    rng = np.random.default_rng([int(seed) % (2 ** 32), 56])
    tokens = jnp.asarray(rng.integers(
        1, mc.vocab_size, (2, min(1024, mc.max_seq_len))), jnp.int32)

    def one_round(params, gamma):
        _, sown = model.apply({"params": params}, tokens,
                              mutable=["routing", "step_stats"])
        params = dict(params)
        for i in layers:
            chose = sown["routing"][f"layer_{i}"]["moe"]["top_idx"][0]
            load = jnp.zeros((mc.n_experts,), jnp.float32).at[
                chose.reshape(-1)].add(1.0)
            layer = dict(params[f"layer_{i}"])
            layer["moe"] = dict(layer["moe"])
            layer["moe"]["router_bias"] = layer["moe"]["router_bias"] \
                - gamma * jnp.sign(load - load.mean())
            params[f"layer_{i}"] = layer
        return params
    step = jax.jit(one_round, donate_argnums=(0,))
    for gamma in BALANCE_STEPS:
        params = step(params, jnp.float32(gamma))
    return params


def centre_second_matrices(params):
    """Every relu(.)^2 feed-forward's SECOND matrix with the mean over
    its rows taken off each column (routed experts and the shared one).
    What stands in front of that matrix is never negative, so a seeded
    matrix maps it to a result with the same large component for every
    token; with the routed sum scaled by 5 that component feeds on
    itself from layer to layer until every row of a decode step carries
    the same hidden state, answers the same greedy token and chooses
    the same 22 experts (17.8 of the 64 held touched a layer call in one
    seed and 60.9 in another, 10 % apart in tokens per second: my chip
    runs, PR 56, `chiprun_out/pr56/set1b`). A matrix whose columns sum
    to nothing maps a constant vector to nothing: the seeded draw with
    that one linear constraint, which is what a trained feed-forward
    behind a non-negative activation has to have learned."""
    import jax.numpy as jnp

    def centred(w):                     # (.., f, d): over the f rows
        w32 = w.astype(jnp.float32)
        return (w32 - w32.mean(-2, keepdims=True)).astype(w.dtype)
    params = dict(params)
    for name, layer in params.items():
        if not (isinstance(layer, dict) and "moe" in layer):
            continue
        layer = dict(layer)
        moe = dict(layer["moe"])
        moe["experts_down_kernel"] = centred(moe["experts_down_kernel"])
        shared = dict(moe["shared"])
        shared["down_proj"] = {
            "kernel": centred(shared["down_proj"]["kernel"])}
        moe["shared"] = shared
        layer["moe"] = moe
        params[name] = layer
    return params


def model_factory(cfg: dict, seed: int):
    """Runs inside the replica: (model, params), bf16 weights made on the
    replica's device in one jitted call from the seed, the relu^2
    feed-forwards' second matrices centred (`centre_second_matrices`),
    the selection bias then balanced (`balance_selection_bias`)."""
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
    params = jax.jit(lambda k: centre_second_matrices(model.init(
        k, jnp.zeros((1, 8), jnp.int32))["params"]))(key)
    params = balance_selection_bias(model, params, seed)
    jax.block_until_ready(params)
    return model, params


class _CfgAsSolar:
    """The model's configuration under the names
    `replica_solar.system_logits` asks by: the layer whose recurrence is
    tapped is called "kda" there, and a layer counts as an expert layer
    where `dense_ff` is false (a block without a feed-forward has no
    routing to hand out)."""

    def __init__(self, cfg):
        self._cfg = cfg
        self.layer_types = tuple("kda" if k == "mamba2" else k
                                 for k in cfg.layer_types)

    def __getattr__(self, name):
        return getattr(self._cfg, name)

    def dense_ff(self, i: int) -> bool:
        return self._cfg.ff_kind(i) != "experts"


class _ModelAsSolar:
    """The engine's model for the length of one `serve_check`: as it
    is, but for `cfg` (above) and the name of the sown recurrence."""

    def __init__(self, model):
        self._model = model
        self.cfg = _CfgAsSolar(model.cfg)

    def __getattr__(self, name):
        return getattr(self._model, name)

    def apply(self, variables, *args, **kw):
        out = self._model.apply(variables, *args, **kw)
        if "recurrence" in (kw.get("mutable") or ()):
            sown = dict(out[1])
            sown["recurrence"] = {
                layer: ({"kda": v["mamba2"]} if "mamba2" in v else v)
                for layer, v in sown["recurrence"].items()}
            out = (out[0], sown)
        return out


class _ReferenceAsSolar:
    """`reference_nemotron` under the two names `replica_solar.compare`
    asks its reference by."""
    forward = staticmethod(reference_nemotron.forward)
    kda_recurrence = staticmethod(reference_nemotron.ssm_recurrence)


def serve_check(engine, spec: Dict[str, Any]) -> Dict[str, Any]:
    """`replica_solar.serve_check` itself, with this family's model and
    reference under the names it asks by for the length of the call
    (that file is the accepted benchmark's and takes neither as a
    parameter): the engine's own step programs with every slot live
    (prefill of the seeded prompt, which ends inside its bucket and goes
    into a slot another sequence has just left, then the decode steps
    through the slot state and the pages), the logits, the experts
    chosen in the five expert layers and the FIRST Mamba-2 layer's
    recurrence (C, B, xs, g, dt in, y out: `models/hybrid.py:Mamba2`
    sows them; the chunkwise form over the bucket in the prefill
    program, `ssm_decode_step` over the slot pool in the decode program)
    against `reference_nemotron`'s full forward pass and its
    token-by-token recurrence; the limits are `replica_solar.compare`'s.
    `spec["controls"]` (a builder's tool, never a benchmark run): names
    of `reference_nemotron`'s deliberately wrong models."""
    model, reference = engine.model, replica_solar.reference_solar
    engine.model = _ModelAsSolar(model)
    replica_solar.reference_solar = _ReferenceAsSolar
    try:
        return replica_solar.serve_check(engine, spec)
    finally:
        engine.model = model
        replica_solar.reference_solar = reference


def _fields(buf: bytes):
    """(field number, value) for every field of one protobuf message in
    wire format: a varint as an int, a length-delimited field as bytes,
    fixed-width fields skipped (None)."""
    def varint(i):
        out = shift = 0
        while True:
            b = buf[i]
            i += 1
            out |= (b & 0x7F) << shift
            if not b & 0x80:
                return out, i
            shift += 7
    i, n = 0, len(buf)
    while i < n:
        key, i = varint(i)
        kind, value = key & 7, None
        if kind == 0:
            value, i = varint(i)
        elif kind == 2:
            size, i = varint(i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
        else:
            raise ValueError(f"wire type {kind}")
        yield key >> 3, value


def scope_ops(xspace: bytes, scope: str) -> dict:
    """{program: names of its HLO instructions traced under the named
    scope `scope`} out of a profile's own record of the programs that
    ran. A TPU trace names an operation by its HLO line without its
    metadata, so a scope does not reach an event; but the profiler keeps
    every program's HloProto in the plane `/host:metadata`, one event
    metadata a program under the name its runs have on the `XLA Modules`
    line (`jit__prefill_paged_step(123)`), and there each instruction
    has its `op_name`, scopes and all. Read with nothing but the wire
    format (XSpace.planes 1; XPlane.name 2, .event_metadata 4 as map
    entries, .stat_metadata 5; XEventMetadata.name 2, .stats 5;
    XStat.metadata_id 1, .bytes_value 6; HloProto.hlo_module 1;
    HloModuleProto.computations 3; HloComputationProto.instructions 2;
    HloInstructionProto.name 1, .metadata 7; OpMetadata.op_name 2). {}
    where the profile keeps no program."""
    out = {}
    want = scope.encode()
    for number, plane in _fields(xspace):
        if number != 1:
            continue
        parts = list(_fields(plane))
        if (2, b"/host:metadata") not in parts:
            continue
        hlo_stat = None
        for n, entry in parts:
            if n == 5:
                meta = dict(_fields(dict(_fields(entry)).get(2, b"")))
                if meta.get(2) == b"Hlo Proto":
                    hlo_stat = meta.get(1)
        for n, entry in parts:
            if n != 4:
                continue
            program = list(_fields(dict(_fields(entry)).get(2, b"")))
            name = dict(program).get(2, b"").decode()
            for m, stat in program:
                if m != 5:
                    continue
                stat = dict(_fields(stat))
                if stat.get(1) != hlo_stat or not stat.get(6):
                    continue
                names = out.setdefault(name, set())
                module = dict(_fields(stat[6])).get(1, b"")
                for c, comp in _fields(module):
                    if c != 3:
                        continue
                    for k, ins in _fields(comp):
                        if k != 2:
                            continue
                        ins = dict(_fields(ins))
                        op_name = dict(_fields(ins.get(7, b""))).get(2, b"")
                        if want in op_name:
                            names.add(ins[1].decode())
    return out


def scope_seconds(trace_dir: str, scope: str,
                  device_plane_re: str = r"^/device:TPU:\d+$",
                  ops_line: str = "XLA Ops",
                  modules_line: str = "XLA Modules") -> "float | None":
    """Device seconds of the operations traced under the named scope
    `scope`: an event of the operations' line counts where its
    instruction's name is one `scope_ops` found under the scope in the
    program whose run (the modules' line) it lies in. The union of their
    intervals, so that a loop and the operations of its body count
    once. A mean over the devices; None where the profile keeps no
    program (nothing to read a scope from, which is not 0)."""
    import re
    from jax.profiler import ProfileData
    from . import trace_reduce
    path = trace_reduce.find_xplane(trace_dir)
    with open(path, "rb") as f:
        by_program = scope_ops(f.read(), scope)
    if not by_program:
        return None
    data = ProfileData.from_file(path)
    seconds, devices = 0.0, 0
    for plane in data.planes:
        if not re.search(device_plane_re, plane.name):
            continue
        lines = {ln.name: ln for ln in plane.lines}
        ops = trace_reduce._line_events(lines, ops_line)
        if not ops:
            continue
        devices += 1
        runs = sorted(trace_reduce._line_events(lines, modules_line))
        hits, at = [], 0
        for s, e, name in sorted(ops):
            while at < len(runs) and runs[at][1] <= s:
                at += 1
            if at == len(runs) or runs[at][0] > s:
                continue
            short = name.split(" = ", 1)[0].strip().lstrip("%")
            if short in by_program.get(runs[at][2], ()):
                hits.append((s, e))
        seconds += trace_reduce.total(trace_reduce.union(hits))
    return seconds / devices if devices else None


class NemotronBenchServer(BenchServer):

    def bench_trace_reduce(self, spec: Dict[str, Any]) -> Dict[str, Any]:
        out = super().bench_trace_reduce(spec)
        if out.get("devices"):
            scan_s = scope_seconds(spec["dir"], "ssm.scan")
            if scan_s is not None:
                out["ssm_scan_s"] = scan_s
        return out

    def bench_check(self, spec: Dict[str, Any]) -> Dict[str, Any]:
        return serve_check(self.engine, spec)
