"""One-chip microbenchmark of the expert layer's grouped matmuls
(PERF.md, PR 41).

One expert layer's three matmuls (gate and up: `d` -> `f`; down: `f`
-> `d`) through megablox `gmm` at a serve cell's shape (LFM2-24B-A2B:
64 experts of 2 048 x 1 536, 4 a token; OLMoE: 64 of 2 048 x 1 024, 8 a
token), bf16, at the rows of a decode step and of a prefill group, the
rows given to experts as a random router gives them (`--skew`: the
spread of the experts' popularity; the draw's load, largest over mean,
is in every row of the output). Each matmul under a list of `k` and `n`
tiles (the cut at 1 024 every side had before PR 41, the tiles of
`--side-tiles` that divide the side, the side whole) and at the tiles
`ops/moe.py:gmm_tiling` picks; the whole layer through
`ops/moe.py:grouped_matmul` against the cut at 1 024 and against XLA's
own lowering of `jax.lax.ragged_dot`.
Every candidate is a jitted function of its own name, run `--reps`
times under one profiler trace; its time is the device time of its
program on the trace's `XLA Modules` line (`gmm_ms`: of the `gmm`
operations inside it on the `XLA Ops` line, what the cells'
`expert_matmul_roofline` reads), not a host clock; the share is of the
touched experts' weights at the chip's bandwidth. Needs the chip:

    python -m tools.gmm_microbench --out chiprun_out/gmm.json
"""
from __future__ import annotations

import argparse
import json
import os
import re
import tempfile
import time

import numpy as np

# experts, d, f, experts a token, rows of a decode step and of a prefill
# call (LFM2: 128 slots, one 2 048-token prompt; OLMoE: PR 26's 65)
SHAPES = {"lfm2": (64, 2048, 1536, 4, (128, 2048)),
          "olmoe": (64, 2048, 1024, 8, (65, 2048)),
          # Nemotron-3-Super (PR 56): 64 of a router's 512 held, experts
          # of TWO matmuls and relu^2 in a latent of 1 024, 22 a token;
          # all rows x 22 assignments are sorted, 7 of 8 to no group
          "nemotron": (64, 1024, 2688, 22, (192, 2048), 512, False)}
CUT = 1024          # every side's cut before PR 41: the yardstick


def draw_group_sizes(rng, rows: int, experts: int, top_k: int,
                     skew: float) -> np.ndarray:
    """Rows an expert gets when each of `rows` tokens takes `top_k`
    distinct experts, an expert's popularity lognormal with sigma
    `skew` (Gumbel top-k over the log popularity)."""
    logp = skew * rng.randn(experts)
    keys = logp[None, :] + rng.gumbel(size=(rows, experts))
    chosen = np.argsort(-keys, axis=1)[:, :top_k]
    return np.bincount(chosen.ravel(), minlength=experts).astype(np.int32)


def candidate_tiles(side: int, side_tiles) -> list:
    """Tiles to try along one side: the cut at 1 024 and the side whole;
    where the cut does not divide the side, the listed tiles that do."""
    tiles = {min(side, CUT), side}
    if side % CUT and side > CUT:
        tiles |= {t for t in side_tiles if side % t == 0}
    return sorted(tiles)


def device_times(trace_dir: str, op_re: str = r"^%?gmm") -> dict:
    """name -> [runs, seconds, seconds of the operations matching
    `op_re`] for every program on the first chip's `XLA Modules` line,
    an operation counted to the program whose run covers its start."""
    from jax.profiler import ProfileData

    from benchmarks.harness import trace_reduce
    data = ProfileData.from_file(trace_reduce.find_xplane(trace_dir))
    plane = next(p for p in data.planes
                 if re.search(r"^/device:TPU:\d+$", p.name))
    lines = {ln.name: ln for ln in plane.lines}
    mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                   re.sub(r"\(.*$", "", ev.name))
                  for ev in lines["XLA Modules"].events)
    ops = sorted((ev.start_ns, ev.duration_ns) for ev in
                 lines["XLA Ops"].events if re.search(op_re, ev.name))
    out, i = {}, 0
    for start, end, name in mods:
        row = out.setdefault(name, [0, 0.0, 0.0])
        row[0] += 1
        row[1] += (end - start) * 1e-9
        while i < len(ops) and ops[i][0] < start:
            i += 1
        while i < len(ops) and ops[i][0] < end:
            row[2] += ops[i][1] * 1e-9
            i += 1
    return out


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="lfm2,olmoe")
    ap.add_argument("--rows", default=None,
                    help="token rows a call, e.g. 128,2048; default: the "
                    "shape's own decode and prefill rows")
    ap.add_argument("--side-tiles", default="512,768,1536",
                    help="k / n tiles tried on a side the cut at 1 024 "
                    "does not divide")
    ap.add_argument("--row-tiles", default="64,256",
                    help="row tiles tried beside 128, at the chosen k and "
                    "n tiles, where they divide the sorted rows")
    ap.add_argument("--skew", type=float, default=0.25)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from benchmarks.harness import peaks
    from ray_tpu.ops import moe
    from ray_tpu.ops.activations import relu2, swiglu

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    peak_bw = peaks.peaks_for(dev.device_kind)["hbm_bytes_per_s"]
    side_tiles = [int(t) for t in args.side_tiles.split(",") if t]
    row_tiles = [int(t) for t in args.row_tiles.split(",") if t]
    rng = np.random.RandomState(args.seed)
    cands = {}      # name -> (jitted fn, args, meta)

    def add(name, fn, fargs, **meta):
        # one executable for one HLO whatever its name (two candidates
        # may lower alike: the chosen tiles and their twin in the list),
        # so each program also returns a number of its own
        def numbered(*a, fn=fn, n=len(cands)):
            return fn(*a), jnp.int32(n)
        numbered.__name__ = numbered.__qualname__ = name
        cands[name] = (jax.jit(numbered), fargs, meta)

    def tiled(tm, tk, tn):
        return lambda xs, w, sizes: gmm(
            xs, w, sizes, preferred_element_type=xs.dtype,
            tiling=(tm, tk, tn))

    def layer(matmul_up, matmul_down, gated=True):
        def run(xs, wg, wu, wd, sizes):
            gate = matmul_up(xs, wg, sizes) if gated else None
            up = matmul_up(xs, wu, sizes)
            return matmul_down(swiglu(gate, up) if gated else relu2(up),
                               wd, sizes)
        return run

    for shape in args.shapes.split(","):
        e, d, f, top_k, own_rows, *share = SHAPES[shape]
        router, gated = share or (e, True)
        ks = jax.random.split(jax.random.PRNGKey(args.seed), 3)
        wg, wu = (jax.random.normal(k, (e, d, f), jnp.bfloat16) * d ** -0.5
                  for k in ks[:2])
        wd = jax.random.normal(ks[2], (e, f, d), jnp.bfloat16) * f ** -0.5
        for rows in ([int(r) for r in args.rows.split(",")] if args.rows
                     else own_rows):
            sizes = draw_group_sizes(rng, rows, router, top_k,
                                     args.skew)[:e]
            m = -(-rows * top_k // moe._ROW_TILE) * moe._ROW_TILE
            touched = int((sizes > 0).sum())
            meta = {"shape": shape, "rows": rows, "sorted_rows": m,
                    "experts_touched": touched,
                    "load_max_over_mean": round(
                        float(sizes.max() / sizes.mean()), 3)}
            xs = jax.random.normal(jax.random.PRNGKey(rows), (m, d),
                                   jnp.bfloat16)
            hs = jax.random.normal(jax.random.PRNGKey(rows + 1), (m, f),
                                   jnp.bfloat16)
            sizes = jnp.asarray(sizes)
            tag = f"{shape}_r{rows}"
            chosen = {}
            for which, lhs, w, k, n in (("up", xs, wg, d, f),
                                        ("down", hs, wd, f, d)):
                mm = {**meta, "matmul": which, "k": k, "n": n,
                      "weight_bytes": touched * k * n * 2}
                tm, ck, cn = chosen[which] = moe.gmm_tiling(k, n)
                for tk in candidate_tiles(k, side_tiles):
                    for tn in candidate_tiles(n, side_tiles):
                        add(f"{tag}_{which}_{tm}x{tk}x{tn}",
                            tiled(tm, tk, tn), (lhs, w, sizes), **mm,
                            tiling=[tm, tk, tn],
                            cut_1024=(tk, tn) == (min(k, CUT), min(n, CUT)),
                            chosen=(tk, tn) == (ck, cn))
                for rt in row_tiles:
                    if m % rt == 0:
                        add(f"{tag}_{which}_{rt}x{ck}x{cn}",
                            tiled(rt, ck, cn), (lhs, w, sizes), **mm,
                            tiling=[rt, ck, cn], cut_1024=False, chosen=False)
            lm = {**meta, "matmul": "layer",
                  "weight_bytes": touched * (3 if gated else 2) * d * f * 2}
            largs = (xs, wg, wu, wd, sizes)
            add(f"{tag}_layer_chosen",
                layer(moe.grouped_matmul, moe.grouped_matmul, gated), largs,
                **lm,
                tiling=[list(chosen["up"]), list(chosen["down"])])
            add(f"{tag}_layer_cut_1024",
                layer(tiled(moe._ROW_TILE, min(d, CUT), min(f, CUT)),
                      tiled(moe._ROW_TILE, min(f, CUT), min(d, CUT)), gated),
                largs, **lm)
            add(f"{tag}_layer_ragged_dot",
                layer(jax.lax.ragged_dot, jax.lax.ragged_dot, gated), largs,
                **lm)

    rows_out, compiled = {}, {}
    for name, (fn, fargs, meta) in cands.items():
        try:
            jax.block_until_ready(fn(*fargs))       # compile, warm
            compiled[name] = fn
        except Exception as e:  # noqa: BLE001 — a tile Mosaic refuses
            rows_out[name] = {**meta, "error": repr(e)[-300:]}
            print(f"{name}: {rows_out[name]['error']}", flush=True)
    trace_dir = tempfile.mkdtemp(prefix="gmm_mb_")
    wall = {}       # host clock around the same runs: a cross-check only
    jax.profiler.start_trace(trace_dir)
    for name, fn in compiled.items():
        t0 = time.perf_counter()
        for _ in range(args.reps):
            jax.block_until_ready(fn(*cands[name][1]))
        wall[name] = 1e3 * (time.perf_counter() - t0) / args.reps
    jax.profiler.stop_trace()
    times = device_times(trace_dir)
    for name in compiled:
        meta = cands[name][2]
        runs, seconds, gmm_s = times.get(f"jit_{name}", (0, 0.0, 0.0))
        if runs != args.reps:
            rows_out[name] = {**meta, "wall_ms": round(wall[name], 4),
                              "error": f"{runs} runs under this name in "
                              f"the trace, not {args.reps}"}
            continue
        ms = 1e3 * seconds / runs
        rows_out[name] = {
            **meta, "ms": round(ms, 4), "gmm_ms": round(1e3 * gmm_s / runs, 4),
            "runs": runs, "wall_ms": round(wall[name], 4),
            "gb_per_s": round(meta["weight_bytes"] / (ms * 1e-3) / 1e9, 1),
            "share_of_hbm_peak": round(
                meta["weight_bytes"] / (ms * 1e-3) / peak_bw, 4)}

    # the chosen tiles against XLA's lowering, values, on the rows that
    # belong to a group
    errs = {}
    for name in compiled:
        ref = name[:-len("chosen")] + "ragged_dot"
        if name.endswith("_layer_chosen") and ref in compiled:
            fargs = cands[name][1]
            live = int(fargs[4].sum())
            got, want = (compiled[n](*fargs)[0][:live].astype(jnp.float32)
                         for n in (name, ref))
            errs[name] = float(jnp.max(jnp.abs(got - want))
                               / jnp.max(jnp.abs(want)))
    result = {
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": jax.device_count()},
        "dtype": "bfloat16", "hbm_bytes_per_s": peak_bw, "reps": args.reps,
        "skew": args.skew, "max_err_rel_vs_ragged_dot": errs,
        "rows": rows_out}
    for name, row in rows_out.items():
        print(name, json.dumps({k: row[k] for k in (
            "ms", "gmm_ms", "gb_per_s", "share_of_hbm_peak", "tiling",
            "cut_1024", "chosen", "experts_touched", "load_max_over_mean",
            "error") if k in row}), flush=True)
    print(json.dumps({k: result[k] for k in
                      ("device", "max_err_rel_vs_ragged_dot")}))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
