"""One-chip microbenchmark of the hyper-connection kernels (PERF.md,
PR 48).

One sub-layer's residual path at Xing4.0-29B-A4B's widths (4 streams of
3 584, bf16; 20 Sinkhorn iterations) at the rows of a decode step (129)
and of a 2 x 2 048 prefill: `hc_mix_in` (mapping + mix-in) and
`hc_mix_out` under a list of row tiles and at the tile
`ops/pallas/hyper_connections.py:row_tile` picks, each against XLA's own
fusion of the plain form (ops/hyper_connections.py), and the whole
sub-layer path (read, a stand-in F that is one add, write) both ways.
Every candidate is a jitted function of its own name, run `--reps` times
under one profiler trace; its time is the device time of its program on
the trace's `XLA Modules` line, not a host clock; the share is of the
bytes `benchmarks/harness/costs_xing.py:hc_kernels` counts at the
chip's bandwidth. Needs the chip:

    python -m tools.hc_microbench --out chiprun_out/hc.json
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import tempfile
import time

N, C, ITERS = 4, 3584, 20


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", default="129,4096")
    ap.add_argument("--tiles", default="32,64,128,256")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from benchmarks.harness import costs_xing
    from benchmarks.harness.peaks import peaks_for
    from ray_tpu.ops import hyper_connections as hc
    from ray_tpu.ops.pallas.hyper_connections import (hc_mix_in,
                                                      hc_mix_out, row_tile)
    from tools.gmm_microbench import device_times

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"hc_microbench needs the chip; this is {dev}")
    peak_bw = peaks_for(dev.device_kind)["hbm_bytes_per_s"]
    hp = hc.HCParams(N, ITERS, 1e-6, 1e-6, (-30.0, 30.0))
    m = {"hc_mult": N, "hidden_size": C}
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 5)
    phi = (jax.random.normal(keys[0], (hc.n_maps(N), N * C))
           * (N * C) ** -0.5).astype(jnp.bfloat16)
    spread = jnp.concatenate([jnp.full((2 * N,), 0.5),
                              jnp.full((N * N,), 1.5)])
    b = jax.random.normal(keys[1], (hc.n_maps(N),)) * spread
    a = jnp.ones((3,), jnp.float32)

    cands = {}

    def add(name, fn, fargs, **meta):
        fn.__name__ = name
        cands[name] = (jax.jit(fn), fargs, meta)

    for rows in (int(r) for r in args.rows.split(",")):
        x = jax.random.normal(keys[2], (rows, N * C)).astype(jnp.bfloat16)
        y = jax.random.normal(keys[3], (rows, C)).astype(jnp.bfloat16)
        maps = hc.packed_mappings(x, phi, b, a, hp)
        whole = costs_xing.hc_kernels(m, rows, 1)["bytes"]
        act = 2
        in_bytes = (rows * (N * C + C) * act + rows * (hc.n_maps(N) + 2) * 4
                    + hc.n_maps(N) * N * C * 2)
        out_bytes = whole - in_bytes
        # whole tiles only (a tile that hangs over the end is never made)
        tiles = sorted({t for t in (int(v) for v in args.tiles.split(","))
                        if rows % t == 0} | {row_tile(rows)})
        for t in tiles:
            chosen = t == row_tile(rows)
            add(f"r{rows}_mix_in_t{t}",
                functools.partial(lambda x, t: hc_mix_in(
                    x, phi, b, a, hp, tile=t), t=t), (x,),
                rows=rows, tile=t, chosen=chosen, bytes=in_bytes)
            add(f"r{rows}_mix_out_t{t}",
                functools.partial(lambda x, y, maps, t: hc_mix_out(
                    x, y, maps, N, tile=t), t=t), (x, y, maps),
                rows=rows, tile=t, chosen=chosen, bytes=out_bytes)

        def plain_in(x):
            maps = hc.packed_mappings(x, phi, b, a, hp)
            return hc.mix_in(x, maps[..., :N]), maps

        def plain_out(x, y, maps):
            _, post, res = hc.unpack(maps, N)
            return hc.mix_out(x, y, post, res)
        add(f"r{rows}_mix_in_xla", plain_in, (x,), rows=rows,
            bytes=in_bytes)
        add(f"r{rows}_mix_out_xla", plain_out, (x, y, maps), rows=rows,
            bytes=out_bytes)

        def path_kernels(x):
            h, maps = hc_mix_in(x, phi, b, a, hp)
            return hc_mix_out(x, h + h, maps, N)

        def path_xla(x):
            h, maps = plain_in(x)
            return plain_out(x, h + h, maps)
        add(f"r{rows}_sub_layer_kernels", path_kernels, (x,), rows=rows,
            bytes=whole)
        add(f"r{rows}_sub_layer_xla", path_xla, (x,), rows=rows,
            bytes=whole)

    rows_out, compiled = {}, {}
    for name, (fn, fargs, meta) in cands.items():
        try:
            jax.block_until_ready(fn(*fargs))       # compile, warm
            compiled[name] = fn
        except Exception as e:  # noqa: BLE001: a tile Mosaic refuses
            rows_out[name] = {**meta, "error": repr(e)[-300:]}
            print(f"{name}: {rows_out[name]['error']}", flush=True)
    trace_dir = tempfile.mkdtemp(prefix="hc_mb_")
    wall = {}       # host clock around the same runs: a cross-check only
    jax.profiler.start_trace(trace_dir)
    for name, fn in compiled.items():
        t0 = time.perf_counter()
        for _ in range(args.reps):
            jax.block_until_ready(fn(*cands[name][1]))
        wall[name] = 1e3 * (time.perf_counter() - t0) / args.reps
    jax.profiler.stop_trace()
    times = device_times(trace_dir, r"^%?hc_mix_")
    for name in compiled:
        meta = cands[name][2]
        runs, seconds, kernel_s = times.get(f"jit_{name}", (0, 0.0, 0.0))
        if runs != args.reps:
            rows_out[name] = {**meta, "wall_ms": round(wall[name], 4),
                              "error": f"{runs} runs under this name in "
                              f"the trace, not {args.reps}"}
            continue
        ms = 1e3 * seconds / runs
        rows_out[name] = {
            **meta, "ms": round(ms, 4),
            "kernel_ms": round(1e3 * kernel_s / runs, 4),
            "wall_ms": round(wall[name], 4),
            "gb_per_s": round(meta["bytes"] / (ms * 1e-3) / 1e9, 1),
            "share_of_hbm_peak": round(
                meta["bytes"] / (ms * 1e-3) / peak_bw, 4)}

    # the kernels against the plain form, values
    errs = {}
    for rows in (int(r) for r in args.rows.split(",")):
        k, p = f"r{rows}_sub_layer_kernels", f"r{rows}_sub_layer_xla"
        if k in compiled and p in compiled:
            got, want = (compiled[n](*cands[n][1]).astype(jnp.float32)
                         for n in (k, p))
            errs[k] = float(jnp.max(jnp.abs(got - want))
                            / jnp.max(jnp.abs(want)))
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": jax.device_count()},
              "dtype": "bfloat16", "hbm_bytes_per_s": peak_bw,
              "reps": args.reps, "max_err_rel_vs_plain_form": errs,
              "rows": rows_out}
    for name, row in rows_out.items():
        print(name, json.dumps({k: row[k] for k in (
            "ms", "kernel_ms", "gb_per_s", "share_of_hbm_peak", "tile",
            "chosen", "error") if k in row}), flush=True)
    print(json.dumps({k: result[k] for k in
                      ("device", "max_err_rel_vs_plain_form")}))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
