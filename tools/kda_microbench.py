"""One-chip microbenchmark of the delta rule's kernels: the decode step
(PERF.md, PR 52) and prefill's chunk scan with a decay a key channel
(PERF.md, PR 53); and of the short convolution in front of them
(PERF.md, PR 55).

One layer's one-token step over a slot pool at Solar-Open2-250B's
widths (64 heads of 128 x 128, a decay a key channel, 4 MiB of float32
state a slot, 129 rows) and at Olmo-Hybrid-7B's (30 heads of 96 x 192, a
decay a head, 65 rows): the Pallas kernel (one for both since PR 52,
a head's rate in every key channel at Olmo's shape; its names
`kda_decode_step` and `gdn_decode_step`) against XLA's own fusion of the plain step
(ops/gated_deltanet.py:step), the state donated and updated in place in
both, every row live and with a third of the rows idle (written
through). `--shapes nemotron` (PR 56): the kernel's state-space arm
(`ssm_decode_step`, no correction pass, B and C a group's) at
Nemotron-3-Super's widths and 193 rows against XLA's fusion of
ops/ssm.py:step; `--rows N` runs every shape at N rows, and
`--parent-dir <checkout>` adds the parent's kernel file as a candidate
of its own at the delta rule's shapes (`_parent_kernel`) and says whether
the two kernels' results are equal to the bit. Every candidate is a jitted function of its own name, run
`--reps` times under one profiler trace; its time is the device time of
its program on the trace's `XLA Modules` line, not a host clock; the
share is of the bytes `benchmarks/harness/costs_solar.py:kda_step`
counts for the LIVE rows at the chip's bandwidth.

`--what scan`: one layer's chunkwise form over a prefill group at
Solar-Open2's widths with a carried state, 1 x 512, 1 x 1 024, 2 x 1 024
and 2 x 2 048 tokens: the plain form (`_chunk_scan_channel`, a
`lax.scan` over every chunk of the bucket) against the fused kernel
(ops/pallas/kda_prefill.py, bounded by the rows' true lengths), the
rows' lengths drawn as `solar250b_decode_sat` draws them inside that
bucket and, `_full`, every row as long as the bucket. ms a call, us a
chunk a row over the window's chunks and over the live ones (the whole program and the kernel alone:
a stand-alone program pays for the head-major layout of q, k and g,
which inside a model their own fusions write), and beside them what
`chunk_floor`
counts for a chunk of 64 heads: `exp`s, float32 vector operations and
bfloat16 FLOPs of the six-pass products, the last as a share of the
chip's matrix peak.

`--what conv`: one delta-rule layer's q | k | v projection, convolution
and tail update over the engine's own `SlotState`, at Solar-Open2's
decode shape (193 rows, C 24 576, K 4) and Olmo-Hybrid's (65 rows,
C 11 520), a third of the rows idle, and at each cell's 1 024-token
prefill group (2 rows and 1): `ops/gated_deltanet.py:causal_conv` (the
tail a row a slot, one token on (rows, channels)) against the form it
had until PR 55 (`tests/test_gated_deltanet.py:token_axis_conv`, the
tests' reference: the tail K - 1 tokens deep, concatenated in front of
the input on the token axis), each over a pool
of its own shape, donated, and the projection alone (`_proj`). ms a
call; `conv_ms`, the call less the projection's alone (a compiler fuses
the matmul into other operations, so no operation's name is the
matmul's in every candidate), beside `conv_floor_ms`, the time the
tail's rows in and out and the projection's result take at the chip's
bandwidth; the largest operations by name; and
whether the two forms' results and tails are equal to the bit on the
chip. Needs the chip:

    python -m tools.kda_microbench --out chiprun_out/kda.json
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import tempfile
import time

SHAPES = {"solar": dict(rows=129, h=64, dk=128, dv=128, channel=True),
          "olmo": dict(rows=65, h=30, dk=96, dv=192, channel=False),
          # Nemotron-3-Super's Mamba-2 layer: the state-space arm of the
          # same kernel (PR 56), 128 heads of 64 over 8 groups of 128
          # state channels, the cell's 193 rows
          "nemotron": dict(rows=193, h=128, dk=128, dv=64, groups=8)}
# the convolution: the cells' slot pools (scratch row and all), model
# widths, kernel taps and 1 024-token prefill groups
CONV_SHAPES = {"solar": dict(rows=193, d_model=4096, k=4, group=2),
               "olmo": dict(rows=65, d_model=3840, k=4, group=1)}
# prefill groups of the chunk scan: (rows, bucket)
SCAN_GROUPS = ((1, 512), (1, 1024), (2, 1024), (2, 2048))
CHUNK = 64


def chunk_floor(h: int, dk: int, dv: int, c: int = CHUNK,
                sc: int = 16) -> dict:
    """What one chunk of one row costs the kernel by arithmetic, all
    `h` heads: `exp`s, float32 vector operations (the diagonal blocks'
    elementwise work and lane sums, the rank-one updates of the
    substitution, the rest at ~30 passes over a (c, d_k) tile) and the
    FLOPs of its products times the six bfloat16 passes of a float32
    product at the highest precision."""
    ns = c // sc
    exps = c * sc * dk + (ns + 2) * c * dk
    vector = 9 * c * sc * dk + 30 * c * dk + 2 * ns * sc * sc * dv
    flops = 2 * (2 * c * dk * dv                    # the state's share
                 + (ns - 1) * 2 * sc * dk * c       # blocks under the diagonal
                 + (ns - 1) * sc * c * dv           # the substitution
                 + c * c * dv + dk * c * dv)        # P W, the state's update
    return {"exps": h * exps, "vector_ops": h * vector,
            "bf16_flops_six_pass": 6 * h * flops}


def op_times(trace_dir: str) -> dict:
    """program -> {operation: seconds} on the first chip's `XLA Ops`
    line, an operation counted to the program whose run on the `XLA
    Modules` line covers its start."""
    import re

    from jax.profiler import ProfileData

    from benchmarks.harness import trace_reduce
    data = ProfileData.from_file(trace_reduce.find_xplane(trace_dir))
    plane = next(p for p in data.planes
                 if re.search(r"^/device:TPU:\d+$", p.name))
    lines = {ln.name: ln for ln in plane.lines}
    mods = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                   re.sub(r"\(.*$", "", ev.name))
                  for ev in lines["XLA Modules"].events)
    ops = sorted((ev.start_ns, ev.duration_ns, ev.name)
                 for ev in lines["XLA Ops"].events)
    out, i = {}, 0
    for start, end, name in mods:
        row = out.setdefault(name, {})
        while i < len(ops) and ops[i][0] < start:
            i += 1
        while i < len(ops) and ops[i][0] < end:
            op = trace_reduce.category(ops[i][2])
            row[op] = row.get(op, 0.0) + ops[i][1] * 1e-9
            i += 1
    return out


def cell_lengths(rng, rows: int, bucket: int) -> list:
    """`rows` prompt lengths of those `solar250b_decode_sat` sends (the
    traffic file's distribution at the quantiles a block of its
    schedule draws from) that land in `bucket`."""
    from benchmarks.harness.schedule import length_multiset
    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmarks", "traffic",
            "decode_sat_solar.json")) as f:
        lengths = length_multiset(json.load(f)["prompt_len"], 256)
    return rng.choices([n for n in lengths if bucket // 2 < n <= bucket],
                       k=rows)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--what", default="step,scan,conv")
    ap.add_argument("--shapes", default="solar,olmo")
    ap.add_argument("--rows", type=int, default=None)
    ap.add_argument("--parent-dir", default=None)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import random

    import jax
    import jax.numpy as jnp
    from benchmarks.harness.peaks import peaks_for
    from ray_tpu.ops import gated_deltanet as gdn
    from ray_tpu.ops.attention import SlotState
    from ray_tpu.ops import ssm
    from ray_tpu.ops.pallas.gdn_decode import (gdn_decode_step,
                                               kda_decode_step,
                                               ssm_decode_step)
    from ray_tpu.ops.pallas.kda_prefill import kda_chunk_scan
    from tools.gmm_microbench import device_times

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"kda_microbench needs the chip; this is {dev}")
    peaks = peaks_for(dev.device_kind)
    peak_bw = peaks["hbm_bytes_per_s"]
    cands, inputs = {}, {}

    def add(name, fn, key, donate=5, **meta):
        # a number of its own beside the result: the runtime keeps one
        # executable for one HLO whatever its name, and two candidates
        # that differ in their inputs alone would share a name on the
        # trace
        tag = float(len(cands))

        def step(*args):
            o, new = fn(*args)
            return o, new, jnp.float32(tag)
        step.__name__ = name
        cands[name] = (jax.jit(step, donate_argnums=(donate,)), key, meta)

    def draw(key, rows, h, dk, dv, channel, lead=()):
        ks = jax.random.split(key, 6)
        q = gdn.l2norm(jax.random.normal(ks[0], (*lead, rows, h, dk))) \
            * dk ** -0.5
        k = gdn.l2norm(jax.random.normal(ks[1], (*lead, rows, h, dk)))
        v = jax.random.normal(ks[2], (*lead, rows, h, dv))
        g = -jax.random.uniform(ks[3], (*lead, rows, h, dk) if channel
                                else (*lead, rows, h))
        beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (*lead, rows, h)))
        return q, k, v, g, beta, ks[5]

    parent = None
    if args.parent_dir:
        import importlib.util
        spec = importlib.util.spec_from_file_location(
            "parent_gdn_decode", os.path.join(
                args.parent_dir, "ray_tpu/ops/pallas/gdn_decode.py"))
        parent = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(parent)

    what = args.what.split(",")
    for shape in args.shapes.split(",") if "step" in what else ():
        s = SHAPES[shape]
        rows, h, dk, dv = args.rows or s["rows"], s["h"], s["dk"], s["dv"]
        q, k, v, g, beta, key5 = draw(jax.random.PRNGKey(args.seed), rows,
                                      h, dk, dv, s.get("channel", False))
        state0 = jax.random.normal(key5, (rows, dk, h * dv))
        if "groups" in s:       # the state-space arm: B, C a group's,
            #                     beta = dt in (0, 0.1], g = dt A
            q, k = (x[:, :s["groups"]] for x in (q, k))
            beta = 0.05 * beta
            g = -16.0 * jax.random.uniform(key5, (h,)) * beta
            kernel, plain = ssm_decode_step, ssm.step
        else:
            kernel = kda_decode_step if s["channel"] else gdn_decode_step
            plain = gdn.step
        for live_name, every in (("live", 1), ("third_idle", 3)):
            live = (jnp.arange(rows) % every != every - 1) | (every == 1)
            gl = jnp.where(live.reshape((rows,) + (1,) * (g.ndim - 1)), g, 0.)
            bl = jnp.where(live[:, None], beta, 0.0)
            key = f"{shape}_{live_name}"
            inputs[key] = ((q, k, v, gl, bl), state0, ())
            state_bytes = 2 * 4 * int(live.sum()) * dk * h * dv
            add(f"{key}_kernel", kernel, key, bytes=state_bytes, rows=rows,
                live_rows=int(live.sum()))
            add(f"{key}_xla", plain, key, bytes=state_bytes, rows=rows,
                live_rows=int(live.sum()))
            if parent is not None and "groups" not in s:
                add(f"{key}_parent_kernel", getattr(parent, kernel.__name__),
                    key, bytes=state_bytes, rows=rows,
                    live_rows=int(live.sum()))

    if "scan" in what:
        s = SHAPES["solar"]
        h, dk, dv = s["h"], s["dk"], s["dv"]
        rng = random.Random(args.seed)

        def plain(q, k, v, g, beta, state, n_new):
            return gdn.chunk_scan(q, k, v, g, beta, state, chunk=CHUNK)
        for rows, bucket in SCAN_GROUPS:
            q, k, v, g, beta, key5 = draw(
                jax.random.PRNGKey(args.seed + bucket + rows), bucket, h, dk,
                dv, True, lead=(rows,))
            state0 = jax.random.normal(key5, (rows, dk, h * dv))
            for tail, lens in (("", cell_lengths(rng, rows, bucket)),
                               ("_full", [bucket] * rows)):
                n_new = jnp.asarray(lens, jnp.int32)
                gf, bf = gdn.freeze(
                    g, beta, jnp.arange(bucket)[None, :] < n_new[:, None])
                key = f"scan_{rows}x{bucket}{tail}"
                inputs[key] = ((q, k, v.astype(jnp.bfloat16), gf, bf),
                               state0, (n_new,))
                meta = dict(
                    rows=rows, bucket=bucket, lengths=lens,
                    chunks_window=rows * bucket // CHUNK,
                    chunks_live=sum(-(-n // CHUNK) for n in lens))
                add(f"{key}_plain", plain, key, **meta)
                add(f"{key}_kernel", functools.partial(
                    kda_chunk_scan, chunk=CHUNK), key, **meta)

    for shape in args.shapes.split(",") if "conv" in what else ():
        c = CONV_SHAPES[shape]
        rows, d, taps, group = c["rows"], c["d_model"], c["k"], c["group"]
        h, dk, dv = (SHAPES[shape][x] for x in ("h", "dk", "dv"))
        width = h * (2 * dk + dv)
        ks = jax.random.split(jax.random.PRNGKey(args.seed + 55), 5)
        w_proj = (jax.random.normal(ks[0], (d, width)) * d ** -0.5
                  ).astype(jnp.bfloat16)
        conv_w = jax.random.uniform(ks[1], (taps, width), jnp.float32,
                                    -0.5, 0.5)
        tails = jax.random.normal(ks[2], (rows, taps - 1, width)
                                  ).astype(jnp.bfloat16)

        def layer(conv, lead, h=h, dk=dk):
            """x -> q, k, v a head and the pool with its new tail rows,
            as models/hybrid.py:_delta_rule has them before the norms."""
            def run(x, w_proj, conv_w, slots, n_new, pool):
                entry = SlotState(pool, slots, n_new, None)
                u = jnp.einsum("bsd,dc->bsc", x, w_proj)
                (tail,) = entry.read()
                qkv, tail = conv(u, conv_w, tail, n_new, jax.nn.silu)
                if lead == 1:
                    qkv = qkv[:, 0]
                q, k, v = jnp.split(qkv, [h * dk, 2 * h * dk], axis=-1)
                heads = tuple(a.reshape(*a.shape[:lead], h, -1)
                              for a in (q, k, v))
                return heads, entry.write(tail).arrays[0]
            return run
        # the form until PR 55 is kept where its tests are
        from tests.test_gated_deltanet import (  # noqa: PLC0415
            token_axis_conv)
        forms = {"token_axis": (token_axis_conv, tails),
                 "rows": (gdn.causal_conv, tails.reshape(rows, -1))}
        for call, b, s_new, slots, n_new in (
                ("decode", rows, 1, None,
                 (jnp.arange(rows) % 3 != 2).astype(jnp.int32)),
                ("prefill", group, 1024, jnp.arange(group, dtype=jnp.int32),
                 jnp.asarray([1000, 700][:group], jnp.int32))):
            x = jax.random.normal(ks[3 + (call == "prefill")],
                                  (b, s_new, d)).astype(jnp.bfloat16)
            # the projection alone (its result written out): what both
            # forms' `conv_ms` leave out of their call
            key = f"conv_{shape}_{call}_proj"
            inputs[key] = ((x, w_proj, conv_w, slots, n_new),
                           jnp.zeros((8, 128), jnp.bfloat16), ())
            add(key, lambda x, w_proj, conv_w, slots, n_new, pool: (
                jnp.einsum("bsd,dc->bsc", x, w_proj), pool), key,
                rows=b, tokens=s_new, channels=width)
            for form, (conv, pool) in forms.items():
                key = f"conv_{shape}_{call}_{form}"
                inputs[key] = ((x, w_proj, conv_w, slots, n_new), pool, ())
                add(key, layer(conv, 1 if call == "decode" else 2), key,
                    rows=b, tokens=s_new, channels=width, taps=taps,
                    # what the call has to move: the tail's rows in and
                    # out and the projection's result once
                    bytes=2 * (2 * b * (taps - 1) + b * s_new) * width)

    rows_out, compiled, finals = {}, {}, {}
    for name, (fn, key, meta) in cands.items():
        args_, state0, rest = inputs[key]
        try:
            t0 = time.perf_counter()
            o, st, _tag = fn(*args_, state0 + 0.0, *rest)
            jax.block_until_ready(st)
            meta["first_call_s"] = round(time.perf_counter() - t0, 2)
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
        args_, _state0, rest = inputs[cands[name][1]]
        st = states.pop(name)
        t0 = time.perf_counter()
        for _ in range(args.reps):
            _o, st, _tag = fn(*args_, st, *rest)
            jax.block_until_ready(st)
        wall[name] = 1e3 * (time.perf_counter() - t0) / args.reps
        del st
    jax.profiler.stop_trace()
    times = device_times(trace_dir, r"^%?(kda|gdn|ssm)_(decode_step|chunk_scan)")
    by_op = op_times(trace_dir) if "conv" in what else {}
    floor = chunk_floor(64, 128, 128)
    proj_ms = {name[:-len("proj")]: 1e3 * times[f"jit_{name}"][1] / args.reps
               for name in compiled
               if name.endswith("_proj") and f"jit_{name}" in times}
    for name in compiled:
        meta = cands[name][2]
        runs, seconds, kernel_s = times.get(f"jit_{name}", (0, 0.0, 0.0))
        if runs != args.reps:
            rows_out[name] = {**meta, "wall_ms": round(wall[name], 4),
                              "error": f"{runs} runs under this name in "
                              f"the trace, not {args.reps}"}
            continue
        ms = 1e3 * seconds / runs
        row = {**meta, "ms": round(ms, 4),
               "kernel_ms": round(1e3 * kernel_s / runs, 4),
               "wall_ms": round(wall[name], 4)}
        if name.startswith("conv_"):
            ops = {k: 1e3 * v / runs for k, v in by_op[f"jit_{name}"].items()}
            row["ops_ms"] = {k: round(v, 4) for k, v in sorted(
                ops.items(), key=lambda kv: -kv[1])[:8]}
            # the call less the `_proj` candidate's, the projection run
            # alone at this shape: a compiler fuses the matmul into
            # operations of other names in either form, so no name in
            # `ops_ms` is the matmul's in every candidate. Beside it
            # the time the call's bytes take at the chip's bandwidth:
            # where `conv_ms` is under that floor (Olmo-Hybrid's decode
            # shape) the projection alone costs more than the one fused
            # into the call (it waits for its weights' prefetch), and
            # the difference counts the convolution too low
            form = next(f for f in ("token_axis", "rows", "proj")
                        if name.endswith(f))
            alone = proj_ms.get(name[:-len(form)])
            if alone is not None and form != "proj":
                row.update(
                    proj_ms=round(alone, 4), conv_ms=round(ms - alone, 4),
                    conv_floor_ms=round(1e3 * meta["bytes"] / peak_bw, 4))
        elif "bytes" in meta:
            row.update(gb_per_s=round(meta["bytes"] / (ms * 1e-3) / 1e9, 1),
                       share_of_hbm_peak=round(
                           meta["bytes"] / (ms * 1e-3) / peak_bw, 4))
        else:
            per_live = 1e3 * ms / meta["chunks_live"]
            row.update(
                us_per_chunk_row_window=round(
                    1e3 * ms / meta["chunks_window"], 2),
                us_per_chunk_row_live=round(per_live, 2),
                # the kernel alone: the program around it also lays q, k,
                # g out a head at a time, which the layer's own fusions
                # do for nothing
                kernel_us_per_chunk_row_live=round(
                    1e6 * kernel_s / runs / meta["chunks_live"], 2),
                share_of_matrix_peak_live=round(
                    floor["bf16_flops_six_pass"] / (per_live * 1e-6)
                    / peaks["bf16_flops"], 4))
        rows_out[name] = row
    errs = {}
    for name in compiled:
        if not name.endswith("_kernel"):
            continue
        for other in ("xla", "plain"):
            ref = name[:-len("kernel")] + other
            if ref not in finals:
                continue
            (o1, s1), (o2, s2) = finals[name], finals[ref]
            for n_new in inputs[cands[name][1]][2]:     # real positions
                real = (jnp.arange(o1.shape[1])[None, :]
                        < n_new[:, None])[..., None, None]
                o1, o2 = o1 * real, o2 * real
            errs[name] = [float(jnp.max(jnp.abs(o1 - o2))),
                          float(jnp.max(jnp.abs(s1 - s2)))]
    # this tree's step kernel and the parent's: result and state, to the bit
    as_parent = {}
    for name in compiled:
        other = name[:-len("kernel")] + "parent_kernel"
        if name.endswith("_kernel") and other in finals \
                and not name.endswith("_parent_kernel"):
            (o1, s1), (o2, s2) = finals[name], finals[other]
            as_parent[name] = bool((o1 == o2).all() and (s1 == s2).all())
    # the two forms of the convolution: q, k, v and the pool, to the bit
    same = {}
    for name in compiled:
        other = name[:-len("rows")] + "token_axis"
        if name.endswith("_rows") and other in finals:
            (o1, t1), (o2, t2) = finals[name], finals[other]
            same[name] = bool(all(
                (a == b.reshape(a.shape)).all()
                for a, b in zip((*o1, t1), (*o2, t2))))
    result = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": jax.device_count()},
              "hbm_bytes_per_s": peak_bw, "reps": args.reps,
              "seed": args.seed, "chunk_floor_64_heads": floor,
              "max_abs_err_o_state_vs_plain_form": errs,
              "conv_rows_form_equals_token_axis_form": same,
              "step_kernel_equals_parents_to_the_bit": as_parent,
              "rows": rows_out}
    for name, row in rows_out.items():
        print(name, json.dumps({k: row[k] for k in (
            "ms", "kernel_ms", "gb_per_s", "share_of_hbm_peak", "live_rows",
            "lengths", "us_per_chunk_row_window", "us_per_chunk_row_live",
            "kernel_us_per_chunk_row_live",
            "share_of_matrix_peak_live", "proj_ms", "conv_ms",
            "conv_floor_ms", "ops_ms", "first_call_s", "error")
            if k in row}), flush=True)
    print(json.dumps({k: result[k] for k in (
        "device", "chunk_floor_64_heads",
        "max_abs_err_o_state_vs_plain_form",
        "conv_rows_form_equals_token_axis_form",
        "step_kernel_equals_parents_to_the_bit")}))
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)


if __name__ == "__main__":
    main()
