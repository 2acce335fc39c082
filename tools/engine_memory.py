"""`compiled.memory_analysis()` of the engine's OWN step programs at a
benchmark configuration's real size, compiled for a described v5e with
no chip attached (on-chip-measurement guide, section 2.3): what a
configuration file's `memory_analysis` group is filled from. Nothing
runs; no number here is a device measurement.

    JAX_PLATFORMS=cpu python -m tools.engine_memory \\
        --config benchmarks/configs/xing4.0-29b-a4b-serve-l6.json \\
        [--prefill 2048x2,2048x1,1024x2] [--out chiprun_out/x.json]

The engine is built on abstract parameters (`jax.eval_shape` of the
family's `model_factory` shapes) with `jax.default_backend` steered to
the TPU here, in the script, so that every kernel route is the chip's.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
GIB = 2.0 ** 30


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--prefill", default="2048x2,2048x1,1024x2,128x2")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from benchmarks.harness import modelcfg
    from ray_tpu import models
    from ray_tpu.serve.llm.engine import LLMEngine, LLMEngineConfig

    cfg = modelcfg.load(args.config, False)
    runner = importlib.import_module("benchmarks.runners." + cfg["runner"])
    family = next(v for k, v in vars(runner).items()
                  if k.endswith("_family") and callable(v))()
    replica = sys.modules[family["model_factory"].__module__]
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    jax.config.update("jax_enable_compilation_cache", False)
    jax.default_backend = lambda: "tpu"

    # the family's own way from its file to the program's config
    # (replica_sarvam / replica_xing: latent_moe_config; replica_lfm2moe:
    # hybrid_config), and the module that takes it
    if hasattr(replica, "latent_moe_config"):
        model = models.LatentMoE(replica.latent_moe_config(
            cfg, param_dtype=jnp.bfloat16))
    else:
        model = models.Hybrid(replica.hybrid_config(
            cfg, param_dtype=jnp.bfloat16))

    def abstract(tree):
        return jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=chip), tree)
    params = abstract(jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((1, 8), jnp.int32))["params"],
        jax.random.PRNGKey(0)))
    ecfg = dict(cfg["engine"])
    ecfg["prefill_buckets"] = tuple(ecfg["prefill_buckets"])
    pool_tokens = ecfg["kv_pool_tokens"]
    eng = LLMEngine(model, params, LLMEngineConfig(**ecfg))
    try:
        pools = abstract(eng._pools)
        state = abstract(eng._state)
        s, p = eng._pages.rows_shape()
        n_params = sum(int(a.size) for a in jax.tree_util.tree_leaves(params))
        w_bytes = sum(int(a.size) * a.dtype.itemsize
                      for a in jax.tree_util.tree_leaves(params))
        pool_bytes = sum(int(a.size) * a.dtype.itemsize
                         for a in jax.tree_util.tree_leaves(pools))
        out = {"parameters": n_params, "weights_GiB": w_bytes / GIB,
               "kv_pool_GiB": pool_bytes / GIB,
               "kv_pool_tokens": pool_tokens, "programs": {}}

        def report(name, lowered):
            try:
                mem = lowered.compile().memory_analysis()
            except Exception as e:  # noqa: BLE001: the compiler's refusal
                out["programs"][name] = {"refused": str(e)[:400]}
                print(name, "REFUSED", str(e)[:400], flush=True)
                return
            total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                     + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
            out["programs"][name] = {
                "temp_GiB": mem.temp_size_in_bytes / GIB,
                "argument_GiB": mem.argument_size_in_bytes / GIB,
                "alias_GiB": mem.alias_size_in_bytes / GIB,
                "total_GiB": total / GIB}
            print(name, json.dumps(out["programs"][name]), flush=True)

        def ctl(n):
            return jax.ShapeDtypeStruct((n,), jnp.int32, sharding=chip)
        report("decode", eng._decode_paged_jit.lower(
            params, pools, state, ctl(s + 3 * s + s * p), window_pages=0))
        for spec in args.prefill.split(","):
            pad, g = (int(v) for v in spec.split("x"))
            report(f"prefill_{pad}x{g}", eng._prefill_paged_jit.lower(
                params, pools, state,
                ctl(s + 1 + 4 * g + s * p + g * pad), pad_len=pad))
    finally:
        eng.shutdown()
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
