#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell's configuration and traffic files by name, hands them to
the runner the configuration names (benchmarks/runners/<runner>.py), and
prints as its last line one JSON object: `correct`, `attempted`,
`failed`, `metrics`, `device` and, traced, `breakdown`. With `--trace 0`
the metrics are the cell's end-to-end metrics, with `--trace 1` its
per-layer metrics; every metric is read by the reader its file names
(benchmarks/metrics/<metric>.json -> benchmarks/readers/<reader>.py).

    --rehearse   tiny widths on whatever platform JAX has: a dry run of
                 the control flow that prints no device metric
    --sweep R,R  (serve cells) one set-up, then each offered rate for
                 --seconds: finds a mix's knee; prints a curve, no result
    --out DIR    also write the run's details there as JSON

It never falls back: without the TPU the cell asks for it exits with
another code than 0 and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse          # noqa: E402
import importlib         # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import sys               # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_metrics(manifest: dict, cell: str, section: str) -> list:
    """The metrics of `section` that this cell reports."""
    return [m for m in manifest[section]
            if "workloads" not in m or cell in m["workloads"]]


def read_metric(bench_dir: str, name: str, run: dict):
    """The value of one metric by its own file and reader; None where
    the reader finds nothing to read."""
    with open(os.path.join(bench_dir, "metrics", name + ".json")) as f:
        spec = json.load(f)
    spec_dir = os.path.join(bench_dir, "readers")
    if spec_dir not in sys.path:
        sys.path.insert(0, spec_dir)
    reader = importlib.import_module(spec["reader"])
    return reader.read(run, **spec.get("args", {}))


def resolve(manifest: dict, cell: str, bench_dir: str = HERE) -> dict:
    by_name = {w["name"]: w for w in manifest["workloads"]}
    if cell not in by_name:
        raise SystemExit(f"benchmark: no workload {cell!r} in "
                         f"BENCHMARK.json")
    w = by_name[cell]
    config = next(c for c in manifest["configs"] if c["name"] == w["config"])
    root = os.path.dirname(bench_dir)
    return {"cell": w, "config_entry": config,
            "config_path": os.path.join(root, config["file"]),
            "traffic_path": os.path.join(bench_dir, "traffic",
                                         w["traffic"] + ".json")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--sweep", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from benchmarks.harness import modelcfg
    manifest = load_manifest()
    found = resolve(manifest, args.workload)
    cfg = modelcfg.load(found["config_path"], args.rehearse)
    with open(found["traffic_path"]) as f:
        traffic = json.load(f)
    if args.rehearse:
        traffic = modelcfg.overlay(traffic, traffic.get("rehearse", {}))
    seconds = args.seconds if args.seconds is not None \
        else float(manifest["run_seconds"])
    ctx = {"root": ROOT, "bench_dir": HERE, "cell": found["cell"],
           "config": cfg, "traffic": traffic, "seed": args.seed,
           "seconds": seconds, "trace": bool(args.trace),
           "rehearse": args.rehearse, "t_start": T_PROCESS_START,
           "sweep": ([float(r) for r in args.sweep.split(",")]
                     if args.sweep else None),
           "out": args.out}
    runner = importlib.import_module(
        "benchmarks.runners." + cfg["runner"])
    run = runner.run(ctx)
    if run is None:                 # a sweep: its curve is its output
        return 0

    section = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(manifest, args.workload, section):
        value = read_metric(HERE, m["name"], run)
        if value is None:
            continue
        # a rehearsal shows that the reader found its inputs and never
        # prints a number under a device metric's name
        metrics[m["name"]] = {"value": None if args.rehearse else value,
                              "unit": m["unit"]}
    device = dict(run["device"])
    traced = args.trace and run.get("trace") and run["trace"].get("busy_s")
    if args.trace and not traced and not args.rehearse:
        raise SystemExit("benchmark: the trace holds no device operation")
    if traced:
        device["busy_s"] = run["trace"]["busy_s"]
        device["window_s"] = run["trace"]["window_s"]
    line = {"correct": bool(run["correct"]), "attempted": run["attempted"],
            "failed": run["failed"], "metrics": metrics, "device": device,
            "workload": args.workload, "seed": args.seed,
            "seconds": seconds, "rehearsal": args.rehearse,
            "checks": run.get("checks"), "setup_phases": run.get("phases")}
    if traced:
        line["breakdown"] = {"device_ops": run["trace"]["device_ops"],
                             "idle_gaps": run["trace"]["idle_gaps"]}
        line["traced_s"] = run["trace"].get("traced_s")
    if args.out:
        os.makedirs(args.out, exist_ok=True)
        name = (f"{args.workload}.seed{args.seed}.trace{args.trace}."
                f"{int(time.time())}.json")
        with open(os.path.join(args.out, name), "w") as f:
            json.dump({"line": line, "detail": run.get("detail")}, f)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    # skip interpreter teardown: XLA and runtime threads may abort at
    # exit after the result is out; every owned process has been waited for
    os._exit(rc)
