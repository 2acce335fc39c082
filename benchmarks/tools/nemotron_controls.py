#!/usr/bin/env python3
"""A builder's tool, never a benchmark run: the measured controls of the
Nemotron-3-Super cell's comparison. Builds the configuration's model and engine in
this process (it needs the chip to itself), answers the cell's check
prompt, and compares the system's logits with the plain reference and
with each of `reference_nemotron`'s deliberately wrong models: every one
of those must fail by at least one tolerance of the file's `check`.

    python3 benchmarks/tools/nemotron_controls.py --seed N [--rehearse]
        [--out FILE]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import numpy as np
    from benchmarks.harness import modelcfg, replica_nemotron
    from benchmarks.harness.reference_nemotron import CONTROLS
    from benchmarks.run import load_manifest, resolve
    from ray_tpu.serve.llm.engine import LLMEngine, LLMEngineConfig
    found = resolve(load_manifest(), "nemotron120b_decode_sat")
    cfg = modelcfg.load(found["config_path"], args.rehearse)
    with open(found["traffic_path"]) as f:
        traffic = json.load(f)
    if args.rehearse:
        traffic = modelcfg.overlay(traffic, traffic["rehearse"])
    model, params = replica_nemotron.model_factory(cfg, args.seed)
    engine = LLMEngine(model, params, LLMEngineConfig(**{
        k: tuple(v) if isinstance(v, list) else v
        for k, v in cfg["engine"].items()}))
    try:
        rng = np.random.default_rng([int(args.seed), 99])
        prompt = rng.integers(1, cfg["vocab_size"],
                              traffic["check"]["prompt_len"])
        answer = engine.generate_sync(
            prompt, max_new_tokens=traffic["check"]["new_tokens"])
        out = replica_nemotron.serve_check(engine, {
            "model": replica_nemotron.model_section(cfg),
            "check": cfg["check"], "prompt": prompt.tolist(),
            "generated": answer, "controls": list(CONTROLS)})
    finally:
        engine.shutdown()
    out["device"] = engine.device
    out["seed"] = args.seed
    passed = [name for name, c in out["controls"].items() if c["ok"]]
    out["controls_that_passed"] = passed
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f)
    # every position's error goes to the file only
    for c in [out, *out["controls"].values()]:
        c.pop("err_positions", None)
    print(json.dumps(out), flush=True)
    return 0 if out["ok"] and not passed else 1


if __name__ == "__main__":
    rc = main()
    sys.stdout.flush()
    os._exit(rc)
