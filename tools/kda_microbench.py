"""One-chip microbenchmark of the delta rule's decode step kernels
(PERF.md, PR 52).

One layer's one-token step over a slot pool at Solar-Open2-250B's
widths (64 heads of 128 x 128, a decay a key channel, 4 MiB of float32
state a slot, 129 rows) and at Olmo-Hybrid-7B's (30 heads of 96 x 192, a
decay a head, 65 rows): the Pallas kernel (one for both since PR 52,
a head's rate in every key channel at Olmo's shape; its names
`kda_decode_step` and `gdn_decode_step`) against XLA's own fusion of the plain step
(ops/gated_deltanet.py:step), the state donated and updated in place in
both, every row live and with a third of the rows idle (written
through). Every candidate is a jitted function of its own name, run
`--reps` times under one profiler trace; its time is the device time of
its program on the trace's `XLA Modules` line, not a host clock; the
share is of the bytes `benchmarks/harness/costs_solar.py:kda_step`
counts for the LIVE rows at the chip's bandwidth. Needs the chip:

    python -m tools.kda_microbench --out chiprun_out/kda.json
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile
import time

SHAPES = {"solar": dict(rows=129, h=64, dk=128, dv=128, channel=True),
          "olmo": dict(rows=65, h=30, dk=96, dv=192, channel=False)}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="solar,olmo")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from benchmarks.harness.peaks import peaks_for
    from ray_tpu.ops import gated_deltanet as gdn
    from ray_tpu.ops.pallas.gdn_decode import (gdn_decode_step,
                                               kda_decode_step)
    from tools.gmm_microbench import device_times

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"kda_microbench needs the chip; this is {dev}")
    peak_bw = peaks_for(dev.device_kind)["hbm_bytes_per_s"]
    cands, inputs = {}, {}

    def add(name, fn, key, **meta):
        # a number of its own beside the result: the runtime keeps one
        # executable for one HLO whatever its name, and two candidates
        # that differ in their inputs alone would share a name on the
        # trace
        tag = float(len(cands))

        def step(q, k, v, g, beta, state):
            o, new = fn(q, k, v, g, beta, state)
            return o, new, jnp.float32(tag)
        step.__name__ = name
        cands[name] = (jax.jit(step, donate_argnums=(5,)), key, meta)

    for shape in args.shapes.split(","):
        s = SHAPES[shape]
        rows, h, dk, dv = s["rows"], s["h"], s["dk"], s["dv"]
        ks = jax.random.split(jax.random.PRNGKey(args.seed), 6)
        q = gdn.l2norm(jax.random.normal(ks[0], (rows, h, dk))) * dk ** -0.5
        k = gdn.l2norm(jax.random.normal(ks[1], (rows, h, dk)))
        v = jax.random.normal(ks[2], (rows, h, dv))
        g = -jax.random.uniform(ks[3], (rows, h, dk) if s["channel"]
                                else (rows, h))
        beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (rows, h)))
        state0 = jax.random.normal(ks[5], (rows, dk, h * dv))
        kernel = kda_decode_step if s["channel"] else gdn_decode_step
        for live_name, every in (("live", 1), ("third_idle", 3)):
            live = (jnp.arange(rows) % every != every - 1) | (every == 1)
            gl = jnp.where(live.reshape((rows,) + (1,) * (g.ndim - 1)), g, 0.)
            bl = jnp.where(live[:, None], beta, 0.0)
            key = f"{shape}_{live_name}"
            inputs[key] = ((q, k, v, gl, bl), state0)
            state_bytes = 2 * 4 * int(live.sum()) * dk * h * dv
            add(f"{key}_kernel", kernel, key, bytes=state_bytes, rows=rows,
                live_rows=int(live.sum()))
            add(f"{key}_xla", gdn.step, key, bytes=state_bytes, rows=rows,
                live_rows=int(live.sum()))

    rows_out, compiled, finals = {}, {}, {}
    for name, (fn, key, meta) in cands.items():
        try:
            o, st, _tag = fn(*inputs[key][0], inputs[key][1] + 0.0)
            jax.block_until_ready(st)
            compiled[name] = fn
            finals[name] = (o, st)
        except Exception as e:  # noqa: BLE001: what Mosaic refuses
            rows_out[name] = {**meta, "error": repr(e)[-300:]}
            print(f"{name}: {rows_out[name]['error']}", flush=True)
    trace_dir = tempfile.mkdtemp(prefix="kda_mb_")
    wall = {}       # host clock around the same runs: a cross-check only
    states = {name: inputs[cands[name][1]][1] + 0.0 for name in compiled}
    jax.block_until_ready(states)
    jax.profiler.start_trace(trace_dir)
    for name, fn in compiled.items():
        st = states.pop(name)
        t0 = time.perf_counter()
        for _ in range(args.reps):
            _o, st, _tag = fn(*inputs[cands[name][1]][0], st)
            jax.block_until_ready(st)
        wall[name] = 1e3 * (time.perf_counter() - t0) / args.reps
        del st
    jax.profiler.stop_trace()
    times = device_times(trace_dir, r"^%?(kda|gdn)_decode_step")
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
    errs = {}
    for name in compiled:
        if name.endswith("_kernel") and name[:-6] + "xla" in finals:
            (o1, s1), (o2, s2) = finals[name], finals[name[:-6] + "xla"]
            errs[name] = [float(jnp.max(jnp.abs(o1 - o2))),
                          float(jnp.max(jnp.abs(s1 - s2)))]
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": jax.device_count()},
              "hbm_bytes_per_s": peak_bw, "reps": args.reps,
              "seed": args.seed, "max_abs_err_o_state_vs_plain_form": errs,
              "rows": rows_out}
    for name, row in rows_out.items():
        print(name, json.dumps({k: row[k] for k in (
            "ms", "kernel_ms", "gb_per_s", "share_of_hbm_peak", "live_rows",
            "error") if k in row}), flush=True)
    print(json.dumps({k: result[k] for k in
                      ("device", "max_abs_err_o_state_vs_plain_form")}))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
