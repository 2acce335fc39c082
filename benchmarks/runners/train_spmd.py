"""Runner of the training cells: `SpmdTrainer.fit()` in this process.

The trainer reports every step (`log_every=1`), and a report reads the
loss on the host, so each call of `report_fn` is a fenced step boundary.
Set-up: devices, the float32 reference's loss and gradient norm on the
first batch (the same seed gives `fit()` the same initial parameters),
`fit()`'s own init and compile, and `warmup_steps` whole steps. The
window opens at a step boundary and only whole steps that end inside it
count; a traced run traces a few whole steps right after its window. `fit()` has no way to stop early, so the report that sees the
window closed raises and the runner catches it.
"""
from __future__ import annotations

import os
import shutil
import sys
import time

import numpy as np

from ..harness import modelcfg

CLOCK = time.perf_counter
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class _WindowClosed(Exception):
    pass


def batches(seed: int, vocab: int, batch: int, seq: int):
    """Seeded token batches from a host iterator; batch k is a function
    of (seed, k) alone."""
    k = 0
    while True:
        rng = np.random.default_rng([int(seed), 5, k])
        yield {"tokens": rng.integers(0, vocab, (batch, seq + 1),
                                      dtype=np.int32)}
        k += 1


def reference_step0(model, mesh_spec, cfg, batch, seed):
    """Loss and global gradient norm of the first batch under the
    float32 reference, on the parameters `fit()` will start from."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.parallel import build_mesh
    from ray_tpu.parallel.sharding import ShardingRules, path_str
    from jax.sharding import NamedSharding
    from ..harness import reference

    mesh = build_mesh(mesh_spec, devices=jax.devices())
    tokens = jnp.asarray(batch["tokens"])

    def init(rng):
        return model.init(rng, tokens[:1, :8])["params"]

    key = jax.random.PRNGKey(seed)
    abstract = jax.eval_shape(init, key)
    rules = ShardingRules()
    shard = jax.tree_util.tree_map_with_path(
        lambda p, leaf: NamedSharding(mesh, rules.spec_for(
            path_str(("params",) + p), leaf.shape, mesh)), abstract)
    old = jax.config.jax_threefry_partitionable
    jax.config.update("jax_threefry_partitionable", True)
    try:
        params = jax.jit(init, out_shardings=shard)(key)
    finally:
        jax.config.update("jax_threefry_partitionable", old)
    m = modelcfg.model_section(cfg)

    def mean_loss(p, toks):
        return jnp.mean(jax.vmap(
            lambda row: reference.sequence_loss(p, row, m))(toks))

    # one sequence to a device where the batch allows it (weights are
    # gathered layer by layer): the float32 backward pass of the whole
    # batch then fits beside the float32 parameters (12.6 GiB of a
    # chip's 15.75 at 16 layers, memory_analysis of the AOT compile)
    from jax.sharding import PartitionSpec as P
    spread = (P(tuple(mesh.axis_names), None)
              if tokens.shape[0] % mesh.devices.size == 0 else P())
    tokens = jax.device_put(tokens, NamedSharding(mesh, spread))
    loss, grads = jax.jit(jax.value_and_grad(mean_loss),
                          out_shardings=(None, shard))(params, tokens)
    sq = jax.jit(lambda g: sum(jnp.sum(jnp.square(x)) for x in
                               jax.tree_util.tree_leaves(g)))(grads)
    loss, gnorm = float(loss), float(np.sqrt(float(sq)))
    del params, grads
    return loss, gnorm


def run(ctx: dict):
    cfg, traffic = ctx["config"], ctx["traffic"]
    seconds, seed, rehearse = ctx["seconds"], ctx["seed"], ctx["rehearse"]
    chips = ctx["cell"]["chips"]
    phases, mark = {}, ctx["t_start"]

    def phase(name):
        nonlocal mark
        now = CLOCK()
        phases[name] = now - mark
        mark = now

    import jax
    import jax.numpy as jnp
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    peaks = None
    if not rehearse:
        if device["platform"] != "tpu" or device["count"] < chips:
            print(f"benchmark: need {chips} TPU device(s), jax reports "
                  f"{device}", file=sys.stderr)
            sys.stdout.flush()
            os._exit(3)
        from ..harness.peaks import peaks_for
        peaks = peaks_for(device["kind"])
        from ray_tpu.util.jaxenv import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, s, **_kw: compiles.append(CLOCK())
        if ev == _COMPILE_EVENT else None)

    from ray_tpu.models import Llama
    from ray_tpu.parallel import MeshSpec
    from ray_tpu.train import SpmdTrainer, SpmdTrainerConfig

    tr = cfg["trainer"]
    seq, batch = traffic["seq_len"], traffic["global_batch"]
    model = Llama(modelcfg.llama_config(
        cfg, param_dtype=jnp.float32, remat=tr["remat"], max_seq_len=seq))
    mesh_spec = MeshSpec(**tr["mesh"])
    data_seed = int(seed) % (2 ** 31)
    phase("devices_s")

    first = next(batches(seed, cfg["vocab_size"], batch, seq))
    ref_loss, ref_gnorm = reference_step0(model, mesh_spec, cfg, first,
                                          data_seed)
    phase("reference_s")

    warm = traffic["warmup_steps"]
    trace_dir = os.path.join(ctx["root"], ".bench_out",
                             "trace-" + ctx["cell"]["name"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    log = {"t": [], "loss": [], "gnorm": [], "open": None, "trace": None}

    def report(m):
        now = CLOCK()
        log["t"].append(now)
        log["loss"].append(m["loss"])
        log["gnorm"].append(m["grad_norm"])
        n = len(log["t"])
        if n == warm:
            log["open"] = now                   # a fenced step boundary
        if log["open"] is None:
            return
        if now <= log["open"] + seconds:
            return
        # the window is closed. A traced run goes on for a few whole steps
        # under the profiler, so that neither starting nor stopping it
        # falls inside the steps the window counted
        if ctx["trace"] and log["trace"] is None:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            log["trace"] = [CLOCK(), None, n]
            return
        if ctx["trace"] and n < log["trace"][2] + traffic["trace"]["steps"]:
            return
        if ctx["trace"]:
            jax.profiler.stop_trace()
            log["trace"][1] = CLOCK()
        raise _WindowClosed

    trainer = SpmdTrainer(
        SpmdTrainerConfig(model=model, mesh=mesh_spec,
                          optimizer=tr["optimizer"],
                          learning_rate=tr["learning_rate"],
                          warmup_steps=tr["lr_warmup_steps"],
                          total_steps=tr["total_steps"], log_every=1,
                          grad_clip=tr["grad_clip"], seed=data_seed),
        lambda: batches(seed, cfg["vocab_size"], batch, seq),
        report_fn=report)
    try:
        trainer.fit()
        raise SystemExit("benchmark: fit() ended before the window closed; "
                         "raise trainer.total_steps")
    except _WindowClosed:
        pass
    t_open = log["open"]
    steps = [t for t in log["t"] if t_open < t <= t_open + seconds]
    setup_s = t_open - ctx["t_start"]
    phases["fit_init_compile_warm_s"] = t_open - mark
    mark = CLOCK()

    trace = None
    if ctx["trace"]:
        from ..harness import trace_reduce
        trace = trace_reduce.reduce_dir(trace_dir)
        trace["traced_s"] = log["trace"][1] - log["trace"][0]
        shutil.rmtree(trace_dir, ignore_errors=True)
    mem = [d.memory_stats() or {} for d in devs]
    device["memory_peak_bytes"] = max(
        (m.get("peak_bytes_in_use") or 0) for m in mem) or None

    chk = cfg["check"]
    loss_err = abs(log["loss"][0] - ref_loss)
    gnorm_err = abs(log["gnorm"][0] - ref_gnorm) / max(ref_gnorm, 1e-12)
    finite = bool(np.isfinite(log["loss"]).all())
    checks = {"loss_step0": log["loss"][0], "reference_loss": ref_loss,
              "loss_err": loss_err, "loss_tol": chk["loss_tol"],
              "grad_norm_step0": log["gnorm"][0],
              "reference_grad_norm": ref_gnorm,
              "grad_norm_err_rel": gnorm_err,
              "grad_norm_tol_rel": chk["grad_norm_tol_rel"],
              "losses_finite": finite,
              "loss_last": log["loss"][-1]}
    correct = (finite and loss_err <= chk["loss_tol"]
               and gnorm_err <= chk["grad_norm_tol_rel"])
    phase("reduce_checks_s")
    return {
        "kind": "train", "steps": steps, "window_open": t_open,
        "seconds": seconds, "setup_s": setup_s, "chips": chips,
        "tokens_per_step": batch * seq, "seq_len": seq,
        "model": modelcfg.model_section(cfg), "peaks": peaks,
        "trace": trace, "device": device, "config": cfg,
        "compiles_in_window": sum(
            1 for t in compiles if t_open <= t <= t_open + seconds),
        "correct": bool(correct), "attempted": len(steps), "failed": 0,
        "checks": checks, "phases": phases,
        "detail": {"losses": log["loss"], "step_times": [
            b - a for a, b in zip([t_open] + steps, steps)],
            "trace": trace, "memory": mem},
    }
