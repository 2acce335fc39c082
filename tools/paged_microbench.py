"""One-chip microbenchmark of the paged decode kernel (PERF.md, PR 29).

One layer call of `ops/pallas/paged_attention.py` at a serve cell's
shape (65 rows; Mistral: 32 query / 8 KV heads of 128; OLMoE: 16 / 16),
page 64, under decode windows of 8, 16 and all pages, with contexts
drawn as `decode_sat`'s slots hold them (and one `short_burst`-like draw
with half the rows empty): the kernel over a list of pages a block and
at the block `choose_pages_per_block` picks, against the gather path
(`ops/attention.py:_attend_cached` over gathered pages), the kernels JAX
ships (`jax.experimental.pallas.ops.tpu.paged_attention`,
`ragged_paged_attention`, on pools in their own layouts) and, with
`--parent-dir`, another checkout's kernel file. Every candidate is a
jitted function of its own name, run `--reps` times under one profiler
trace; its time is the device time of its program on the trace's
`XLA Modules` line, not a host clock. Needs the chip:

    python -m tools.paged_microbench --out chiprun_out/paged.json
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import tempfile
import time

import numpy as np

SHAPES = {"mistral": (32, 8, 128, 130), "olmoe": (16, 16, 128, 64)}
ROWS, PAGE, POOL_TOKENS = 65, 64, 49152


def draw_contexts(rng, rows: int, empty_share: float) -> np.ndarray:
    """Tokens each row holds, as `decode_sat`'s slots do in steady state
    (prompt 64-512 median 200; answer 128-512 median 256, a slot found
    part-way through it and the likelier the longer it is). The last
    row is the engine's scratch row: empty."""
    def lognormal(median, sigma, lo, hi, n):
        return np.clip(median * np.exp(sigma * rng.randn(n)), lo, hi)
    prompt = lognormal(200, 0.55, 64, 512, 4 * rows)
    answer = lognormal(256, 0.35, 128, 512, 4 * rows)
    pick = rng.choice(4 * rows, size=rows, p=answer / answer.sum())
    ctx = (prompt[pick] + rng.rand(rows) * answer[pick]).astype(np.int32)
    ctx[rng.rand(rows) < empty_share] = 0
    ctx[-1] = 0
    return ctx


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="mistral,olmoe")
    ap.add_argument("--windows", default="8,16,0",
                    help="decode windows in pages; 0 = every page a slot has")
    ap.add_argument("--blocks", default="1,2,4,8,16")
    ap.add_argument("--parent-dir", default=None)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from benchmarks.harness import peaks, trace_reduce
    from ray_tpu.ops.attention import _attend_cached
    pa = importlib.import_module("ray_tpu.ops.pallas.paged_attention")

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    peak_bw = peaks.peaks_for(dev.device_kind)["hbm_bytes_per_s"]
    parent = None
    if args.parent_dir:
        spec = importlib.util.spec_from_file_location(
            "ray_tpu.ops.pallas._parent_paged", os.path.join(
                args.parent_dir, "ray_tpu/ops/pallas/paged_attention.py"))
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)
    try:
        shipped = importlib.import_module(
            "jax.experimental.pallas.ops.tpu.paged_attention")
        ragged = importlib.import_module(
            "jax.experimental.pallas.ops.tpu.ragged_paged_attention")
    except Exception as e:  # noqa: BLE001 — a yardstick, not the subject
        print(f"shipped kernels not timed: {e!r}", flush=True)
        shipped = ragged = None

    n_pages = POOL_TOKENS // PAGE + 1          # the last page is trash
    rng = np.random.RandomState(args.seed)
    draws = {"sat": draw_contexts(rng, ROWS, 0.0),
             "burst": draw_contexts(rng, ROWS, 0.5)}
    cands = {}      # name -> (jitted fn, args, meta)

    def add(name, fn, fargs, **meta):
        # the runtime keeps one executable for one HLO whatever its name:
        # two candidates of the same shapes (the chosen block and its twin
        # in the list, the two draws of a window) would share a name on
        # the trace, so each program also returns its own number
        def numbered(*a, fn=fn, n=len(cands)):
            return fn(*a), jnp.int32(n)
        numbered.__name__ = numbered.__qualname__ = name
        cands[name] = (jax.jit(numbered), fargs, meta)

    for shape in args.shapes.split(","):
        hq, hkv, d, full = SHAPES[shape]
        scale = d ** -0.5
        ks = jax.random.split(jax.random.PRNGKey(args.seed), 3)
        k_flat = jax.random.normal(ks[0], (n_pages * PAGE, hkv, d),
                                   jnp.bfloat16)
        v_flat = jax.random.normal(ks[1], (n_pages * PAGE, hkv, d),
                                   jnp.bfloat16)
        q = jax.random.normal(ks[2], (ROWS, hq, d), jnp.bfloat16)
        # the shipped kernels' layouts: heads first; K and V interleaved
        k_heads = k_flat.reshape(n_pages, PAGE, hkv, d).transpose(2, 0, 1, 3)
        v_heads = v_flat.reshape(n_pages, PAGE, hkv, d).transpose(2, 0, 1, 3)
        kv_pages = jnp.stack([k_flat, v_flat], axis=2).reshape(
            n_pages, PAGE, 2 * hkv, d)
        for window in (int(w) for w in args.windows.split(",")):
            for draw in ("sat", "burst") if window == 16 else ("sat",):
                w = window or full
                ctx = np.minimum(draws[draw], w * PAGE - 1)
                held = -(-ctx // PAGE)
                table = np.full((ROWS, w), n_pages - 1, np.int32)
                free = iter(rng.permutation(n_pages - 1))
                for r in range(ROWS):
                    table[r, :held[r]] = [next(free) for _ in range(held[r])]
                table, lengths = jnp.asarray(table), jnp.asarray(ctx)
                meta = {"shape": shape, "window": w, "draw": draw,
                        "mean_context": float(ctx[ctx > 0].mean()),
                        "live_rows": int((ctx > 0).sum()),
                        "live_pages": int(held.sum()),
                        "live_bytes": int(held.sum()) * 2 * PAGE * hkv * d * 2}
                tag = f"{shape}_w{w}_{draw}"
                kargs = (q, k_flat, v_flat, table, lengths)
                chosen = pa.choose_pages_per_block(w, PAGE, hq, hkv, d,
                                                   jnp.bfloat16)
                for n in [int(b) for b in args.blocks.split(",")
                          if int(b) <= w] + [None]:
                    add(f"{tag}_new_{'chosen' if n is None else n}",
                        lambda *a, n=n: pa.paged_decode_attention(
                            *a, PAGE, pages_per_block=n), kargs, **meta,
                        pages_per_block=n or chosen, grid_steps=ROWS,
                        blocks=int((-(-ctx // ((n or chosen) * PAGE))).sum()))
                if parent is not None:
                    add(f"{tag}_parent", lambda *a: parent.
                        paged_decode_attention(*a, PAGE), kargs, **meta,
                        grid_steps=ROWS * w)

                def gather(q, k_flat, v_flat, table, lengths, w=w):
                    idx = (table[:, :, None] * PAGE
                           + jnp.arange(PAGE)[None, None, :]).reshape(
                               ROWS, w * PAGE)
                    return _attend_cached(
                        q[:, None], k_flat[idx], v_flat[idx],
                        (lengths - 1)[:, None], lengths, scale)[:, 0]
                add(f"{tag}_gather", gather, kargs, **meta)
                if shipped is None:
                    continue
                for n in (4, 8):
                    if w % n:
                        continue
                    add(f"{tag}_shipped_paged_{n}",
                        lambda q, k, v, ln, tb, n=n: shipped.paged_attention(
                            (q * scale).astype(q.dtype), k, v, ln, tb,
                            pages_per_compute_block=n),
                        (q, k_heads, v_heads, lengths, table), **meta,
                        pages_per_block=n, grid_steps=ROWS * hkv)
                add(f"{tag}_shipped_ragged",
                    lambda q, kv, ln, tb: ragged.ragged_paged_attention(
                        q, kv, ln, tb, jnp.arange(ROWS + 1, dtype=jnp.int32),
                        jnp.asarray([ROWS], jnp.int32), sm_scale=scale),
                    (q, kv_pages, jnp.maximum(lengths, 1), table), **meta)

    rows, compiled = {}, {}
    for name, (fn, fargs, meta) in cands.items():
        try:
            jax.block_until_ready(fn(*fargs))       # compile, warm
            compiled[name] = fn
        except Exception as e:  # noqa: BLE001
            rows[name] = {**meta, "error": repr(e)[:300]}
            print(f"{name}: {rows[name]['error']}", flush=True)
    trace_dir = tempfile.mkdtemp(prefix="paged_mb_")
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
        meta = cands[name][2]
        hit = modules.get(f"jit_{name}")
        if not hit or not hit["count"]:
            rows[name] = {**meta, "wall_ms": round(wall[name], 4),
                          "error": "no program of that name in the trace"}
            continue
        if hit["count"] != args.reps:
            rows[name] = {**meta, "error": f"{hit['count']} runs under this "
                          f"name, not {args.reps}: a shared executable"}
            continue
        ms = 1e3 * hit["seconds"] / hit["count"]
        rows[name] = {**meta, "ms": round(ms, 4), "runs": hit["count"],
                      "wall_ms": round(wall[name], 4),
                      "share_of_hbm_peak": round(
                          meta["live_bytes"] / (ms * 1e-3) / peak_bw, 4)}

    # the chosen block against the gather path, values
    errs = {}
    for name in compiled:
        if name.endswith("_new_chosen"):
            ref = name[:-len("new_chosen")] + "gather"
            if ref in compiled:     # rows with no key: zeros here only
                got, want = (compiled[n](*cands[n][1])[0].astype(jnp.float32)
                             for n in (name, ref))
                live = (cands[name][1][4] > 0)[:, None, None]
                errs[name] = float(jnp.max(jnp.abs(
                    jnp.where(live, got - want, 0.0))))
    result = {
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": jax.device_count()},
        "rows_per_call": ROWS, "page_size": PAGE, "dtype": "bfloat16",
        "hbm_bytes_per_s": peak_bw, "reps": args.reps,
        "max_abs_err_vs_gather": errs, "rows": rows}
    for name, row in rows.items():
        print(name, json.dumps({k: row[k] for k in (
            "ms", "share_of_hbm_peak", "pages_per_block", "grid_steps",
            "blocks", "live_pages", "error") if k in row}), flush=True)
    print(json.dumps({k: result[k] for k in
                      ("device", "max_abs_err_vs_gather")}))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
