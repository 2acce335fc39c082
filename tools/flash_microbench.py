"""One-chip microbenchmark of the flash kernel (PERF.md, PR 27).

Forward, dQ and dK/dV of `ops/pallas/flash_attention.py` at one shape
under a list of tiles, against the whole forward + backward by
`impl="xla"`, `impl="dpa"`, the kernels JAX ships
(`jax.experimental.pallas.ops.tpu.flash_attention`, `splash_attention`)
and, with `--parent-dir`, another checkout's kernel file. Every
candidate is a jitted function of its own name, run `--reps` times
under one profiler trace; its time is the device time of its program on
the trace's `XLA Modules` line, not a host clock. Needs the chip:

    python -m tools.flash_microbench --out chiprun_out/flash.json

The default shape is `mistral7b_train_fsdp2_tp2`'s per-chip share.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import tempfile
import time


def executed_flops(kernel: str, b, hq, sq, sk, d, bq, bk, causal=True):
    """FLOPs of the tiles the kernel runs (a tile the diagonal crosses
    counts whole): 2 matmuls a tile forward, 3 in dQ, 4 in dK/dV."""
    nq, nk = -(-sq // bq), -(-sk // bk)
    tiles = sum(min(((i + 1) * bq - 1) // bk, nk - 1) + 1 if causal else nk
                for i in range(nq))
    return b * hq * tiles * 2 * bq * bk * d * {"fwd": 2, "dq": 3, "dkv": 4}[
        kernel]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--seq", type=int, default=4096)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--kv-heads", type=int, default=4)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--tiles", default="128x128,256x256,512x512,"
                    "512x1024,1024x512,1024x1024,2048x1024,1024x2048")
    ap.add_argument("--parent-dir", default=None)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from benchmarks.harness import peaks, trace_reduce
    from ray_tpu.ops.attention import multi_head_attention
    fa = importlib.import_module("ray_tpu.ops.pallas.flash_attention")

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    peak_flops = peaks.peaks_for(dev.device_kind)["bf16_flops"]
    b, s, hq, hkv, d = (args.batch, args.seq, args.heads, args.kv_heads,
                        args.head_dim)
    rep = hq // hkv
    scale = d ** -0.5
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 4)
    q = jax.random.normal(ks[0], (b, s, hq, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, s, hkv, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, s, hkv, d), jnp.bfloat16)
    g = jax.random.normal(ks[3], (b, s, hq, d), jnp.bfloat16)
    qh, kh, vh, gh = (x.transpose(0, 2, 1, 3) for x in (q, k, v, g))
    tiles = [tuple(int(n) for n in t.split("x"))
             for t in args.tiles.split(",") if t]

    cands = {}      # name -> (jitted fn, args, executed FLOPs or None)

    def add(name, fn, fargs, flops=None):
        fn.__name__ = fn.__qualname__ = name
        cands[name] = (jax.jit(fn), fargs, flops)

    def whole(attn):
        def f(q, k, v, g):
            out, vjp = jax.vjp(attn, q, k, v)
            return out, vjp(g)
        return f

    # residuals of the backward kernels, from the kernel under test
    out_h, lse = jax.jit(lambda q, k, v: fa._flash_fwd(
        q, k, v, scale, True, (512, 512), False))(qh, kh, vh)
    delta = jnp.sum(gh.astype(jnp.float32) * out_h.astype(jnp.float32), -1)

    for bq, bk in tiles:
        t = f"{bq}x{bk}"
        fl = {kn: executed_flops(kn, b, hq, s, s, d, bq, bk)
              for kn in ("fwd", "dq", "dkv")}
        add(f"fwd_{t}", lambda q, k, v, blk=(bq, bk): fa._flash_fwd(
            q, k, v, scale, True, blk, False), (qh, kh, vh), fl["fwd"])
        add(f"dq_{t}", lambda *a, blk=(bq, bk): fa._flash_dq(
            *a, scale, True, blk, False),
            (qh, kh, vh, gh, lse, delta), fl["dq"])
        add(f"dkv_{t}", lambda *a, blk=(bq, bk): fa._flash_dkv(
            *a, scale, True, blk, False),
            (qh, kh, vh, gh, lse, delta), fl["dkv"])
    add("whole_pallas_chosen", whole(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True)), (q, k, v, g))
    for impl in ("xla", "dpa"):
        add(f"whole_{impl}", whole(lambda q, k, v, impl=impl:
                                   multi_head_attention(
                                       q, k, v, causal=True, impl=impl)),
            (q, k, v, g))

    if args.parent_dir:
        spec = importlib.util.spec_from_file_location(
            "ray_tpu.ops.pallas._parent_flash", os.path.join(
                args.parent_dir, "ray_tpu/ops/pallas/flash_attention.py"))
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
        add("whole_parent_128x128", whole(
            lambda q, k, v: parent.flash_attention(
                q, k, v, causal=True, block_q=128, block_k=128)),
            (q, k, v, g))

        def flat(x):        # the parent's layout: (B*Hq, S, D), expanded
            x = jnp.repeat(x, hq // x.shape[1], axis=1)
            return x.reshape(b * hq, s, d)
        pq, pk, pv, pg = (flat(x) for x in (qh, kh, vh, gh))
        add("parent_fwd_128x128", lambda q, k, v: parent._flash_fwd(
            q, k, v, scale, True, 128, 128, False), (pq, pk, pv),
            executed_flops("fwd", b, hq, s, s, d, 128, 128))
        add("parent_bwd_128x128", lambda *a: parent._flash_bwd(
            *a, scale, True, 128, 128, False),
            (pq, pk, pv, out_h.reshape(b * hq, s, d),
             lse.reshape(b * hq, s), pg),
            executed_flops("dq", b, hq, s, s, d, 128, 128)
            + executed_flops("dkv", b, hq, s, s, d, 128, 128))

    def heads_major(attn, expand):
        """`attn` over (B, H, S, D) as a caller of (B, S, H, D) would
        have to call it: the transposes (and, for a kernel that wants
        equal head counts, the repeat) are part of its price."""
        def f(q, k, v):
            q, k, v = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
            if expand:
                k, v = (jnp.repeat(x, rep, axis=1) for x in (k, v))
            return attn(q, k, v).transpose(0, 2, 1, 3)
        return f

    # what reading GQA in place saves: the same kernels on expanded K/V
    add("whole_pallas_expanded", whole(lambda q, k, v: fa.flash_attention(
        q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
        causal=True)), (q, k, v, g))
    try:
        shipped = importlib.import_module(
            "jax.experimental.pallas.ops.tpu.flash_attention")
        splash = importlib.import_module(
            "jax.experimental.pallas.ops.tpu.splash_attention")
        for m in sorted({min(512, s), min(1024, s)}):
            sizes = shipped.BlockSizes(
                block_q=m, block_k_major=m, block_k=m, block_b=1,
                block_q_major_dkv=m, block_k_major_dkv=m, block_k_dkv=m,
                block_q_dkv=m, block_k_major_dq=m, block_k_dq=m,
                block_q_dq=m)
            add(f"whole_shipped_flash_{m}", whole(heads_major(
                lambda q, k, v, sizes=sizes: shipped.flash_attention(
                    q, k, v, causal=True, sm_scale=scale,
                    block_sizes=sizes), True)), (q, k, v, g))
            sizes = splash.BlockSizes(
                block_q=m, block_kv=m, block_kv_compute=m, block_q_dkv=m,
                block_kv_dkv=m, block_kv_dkv_compute=m, block_q_dq=m,
                block_kv_dq=m)
            kern = splash.make_splash_mha_single_device(
                splash.MultiHeadMask([splash.CausalMask((s, s))] * hq),
                block_sizes=sizes)
            add(f"whole_splash_{m}", whole(heads_major(
                lambda q, k, v, kern=kern: jax.vmap(kern)(
                    (q * scale).astype(q.dtype), k, v), False)),
                (q, k, v, g))
    except Exception as e:  # noqa: BLE001 — a yardstick, not the subject
        print(f"shipped kernels not timed: {e!r}", flush=True)

    rows, compiled = {}, {}
    for name, (fn, fargs, _f) in cands.items():
        try:
            jax.block_until_ready(fn(*fargs))       # compile, warm
            compiled[name] = fn
        except Exception as e:  # noqa: BLE001
            rows[name] = {"error": repr(e)[:300]}
            print(f"{name}: {rows[name]['error']}", flush=True)
    trace_dir = tempfile.mkdtemp(prefix="flash_mb_")
    wall = {}       # host clock around the same runs: a cross-check only
    jax.profiler.start_trace(trace_dir)
    for name, fn in compiled.items():
        t0 = time.perf_counter()
        for _ in range(args.reps):
            jax.block_until_ready(fn(*cands[name][1]))
        wall[name] = 1e3 * (time.perf_counter() - t0) / args.reps
    jax.profiler.stop_trace()
    modules = trace_reduce.reduce_dir(trace_dir).get("modules") or {}
    for name in compiled:
        hit = [m for n, m in modules.items() if n == f"jit_{name}"]
        if not hit or not hit[0]["count"]:
            rows[name] = {"error": "no program of that name in the trace",
                          "wall_ms": round(wall[name], 4)}
            continue
        ms = 1e3 * hit[0]["seconds"] / hit[0]["count"]
        rows[name] = {"ms": round(ms, 4), "runs": hit[0]["count"],
                      "wall_ms": round(wall[name], 4)}
        if cands[name][2]:
            rows[name]["executed_tflop"] = round(cands[name][2] / 1e12, 4)
            rows[name]["share_of_peak"] = round(
                cands[name][2] / (ms * 1e-3) / peak_flops, 4)

    # the chosen tiles against the XLA route, values and gradients
    ref = compiled["whole_xla"](q, k, v, g)
    got = compiled["whole_pallas_chosen"](q, k, v, g)
    errs = [float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                  - r.astype(jnp.float32))))
            for a, r in zip((got[0], *got[1]), (ref[0], *ref[1]))]
    result = {
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": jax.device_count()},
        "shape": {"batch": b, "seq": s, "heads": hq, "kv_heads": hkv,
                  "head_dim": d, "dtype": "bfloat16", "causal": True},
        "chosen": fa.choose_blocks(s, s, d, jnp.bfloat16)._asdict(),
        "max_abs_err_vs_xla": dict(zip(("out", "dq", "dk", "dv"), errs)),
        "reps": args.reps, "rows": rows, "programs": sorted(modules)}
    for name, row in rows.items():
        print(name, json.dumps(row), flush=True)
    print(json.dumps({k: result[k] for k in
                      ("device", "shape", "chosen", "max_abs_err_vs_xla")}))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
