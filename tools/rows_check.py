"""Whose tokens does a latent-family cell's reference check judge?

The sarvam cell's comparison (benchmarks/harness/replica_sarvam.py)
holds the ENGINE's greedy tokens, which its decode programs make over
all slots and the scratch row (129 rows), to a float32 reference that
follows the experts a 1-ROW program of the same model code chose. Where
`argmax_gap_rel` reads high, this tells the two causes apart. For one
configuration file, seed and row count it answers the cell's check
prompt three ways:

  engine   LLMEngine, as the benchmark's replica builds it;
  rows=N   `model.apply` itself, greedy: the prefill the engine makes for
           a lone prompt (1 row of its bucket), then decode steps of N
           rows against a pool of the engine's shape, the prompt in the
           row of the engine's slot, every other row dead (no tokens,
           the trash page, masked);
  rows=1   the same with 1 row, at the engine's decode window;

and prints each one's tokens, the share of (expert layer, position)
pairs at which the N-row and the 1-row program chose the same experts
along the engine's sequence, and what `replica_sarvam.compare` makes of
each: the engine's tokens as the benchmark itself checks them
(`serve_check`: a reference that follows the harness's own 1-row
program, whose page table is sized to the prompt), under a reference
that follows this tool's 1-row program, and under one that follows the
N-row program (like with like); and each program's own tokens under its
own experts. `engine == rows=N` and a gap only where a 1-row program is
followed: the engine computes what the model computes at its row count
and the gap is the check's. `engine != rows=N`: a step program departs
from the model at that shape.

It imports the harness's model factory, comparison and reference and
edits none of them; no cell runs it. On the chip it needs the chip to
itself (PERF.md, PR 39):

    python -m tools.rows_check --config benchmarks/configs/\\
sarvam-105b-serve-ep4-l6.json --seed N --rows 129 [--rehearse] [--out F]
"""
from __future__ import annotations

import argparse
import functools
import gc
import json
import os

import numpy as np


def apply_rows(model, params, ecfg, prompt, new_tokens: int, rows: int,
               forced=None, row: int = 0):
    """`new_tokens` greedy tokens of `model.apply` after `prompt` (fed
    `forced`'s tokens instead of its own where given), the logits of all
    p + new_tokens - 1 positions and, per expert layer, the experts
    chosen at each: one prefill row, then decode steps of `rows` rows
    (the prompt in row `row`) over the windows the engine would take."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.attention import PagedLatent
    # the engine's own rounding of a decode window
    from ray_tpu.serve.llm.engine import _next_pow2
    mc = model.cfg
    p, ps = prompt.size, ecfg.kv_page_size
    pad = next(b for b in ecfg.prefill_buckets if b >= p)
    per_slot = -(-ecfg.max_seq_len // ps)
    trash = -(-ecfg.kv_pool_tokens // ps)
    table = np.full((rows, per_slot), trash, np.int32)
    own = -(-(p + new_tokens) // ps)
    table[row, :own] = np.arange(own)
    table = jnp.asarray(table)
    pools = [jnp.zeros(((trash + 1) * ps, mc.cache_width), mc.dtype)
             for _ in range(mc.n_layers)]
    live = (jnp.arange(rows) == row)

    def run(params, pools, tokens, entries, positions, row_mask):
        (logits, new), sown = model.apply(
            {"params": params}, tokens, cache=entries, positions=positions,
            row_mask=row_mask, mutable=["step_stats", "routing"])
        chose = [sown["routing"][f"layer_{i}"]["moe"]["top_idx"][0]
                 for i in range(mc.first_dense, mc.n_layers)]
        return logits, [e.flat for e in new], chose

    @functools.partial(jax.jit, donate_argnums=(1,))
    def prefill(params, pools, tokens):
        entries = [PagedLatent(c, table[row:row + 1, :-(-pad // ps)],
                               jnp.zeros((1,), jnp.int32), ps, fresh=True)
                   for c in pools]
        pos = jnp.arange(pad)[None, :]
        return run(params, pools, tokens, entries, pos, pos < p)

    @functools.partial(jax.jit, static_argnums=(4,), donate_argnums=(1,))
    def decode(params, pools, tokens, lengths, window):
        entries = [PagedLatent(c, table[:, :window], lengths, ps)
                   for c in pools]
        return run(params, pools, tokens[:, None], entries,
                   lengths[:, None], live[:, None])

    padded = np.zeros((1, pad), np.int32)
    padded[0, :p] = prompt
    block, pools, chose = prefill(params, pools, jnp.asarray(padded))
    logits = [np.asarray(block[0, :p], np.float32)]
    chose = [np.asarray(c[0, :p]) for c in chose]
    tokens = [int(logits[0][-1].argmax())]
    for j in range(1, new_tokens):
        length = p + j - 1
        step, pools, c = decode(
            params, pools,
            live * jnp.int32(tokens[-1] if forced is None
                             else forced[j - 1]),
            live * jnp.int32(length),
            min(_next_pow2(-(-(length + 1) // ps)), per_slot))
        logits.append(np.asarray(step[row], np.float32))
        chose = [np.concatenate([a, np.asarray(b[row])])
                 for a, b in zip(chose, c)]
        tokens.append(int(logits[-1][0].argmax()))
    return tokens, np.concatenate(logits), chose


def answer(engine, prompt, new_tokens: int):
    """The engine's greedy answer and the slot it was decoded in."""
    rid = engine.submit(prompt, max_new_tokens=new_tokens)
    slot, tokens = None, []
    for tok in engine.stream(rid):
        if slot is None:
            slot = int(engine._requests[rid].slot)
        tokens.append(int(tok))
    return tokens, slot


def check(cfg: dict, seed: int, rows: int, prompt_len: int,
          new_tokens: int) -> dict:
    from benchmarks.harness import replica_sarvam
    from ray_tpu.serve.llm.engine import LLMEngine, LLMEngineConfig
    model, params = replica_sarvam.model_factory(cfg, seed)
    ecfg = LLMEngineConfig(**{
        k: tuple(v) if isinstance(v, list) else v
        for k, v in cfg["engine"].items()})
    # the prompt the benchmark's check draws (runners/serve_http.py)
    prompt = np.random.default_rng([int(seed), 99]).integers(
        1, cfg["vocab_size"], prompt_len).astype(np.int32)
    section = replica_sarvam.model_section(cfg)
    judged = ("argmax_gap_rel", "logit_err_rel", "logit_err_rel_decode",
              "not_followed", "same_experts_pair_share", "ok")
    engine = LLMEngine(model, params, ecfg)
    try:
        said, slot = answer(engine, prompt, new_tokens)
        again, _ = answer(engine, prompt, new_tokens)
        # the benchmark's own comparison, on this engine
        bench = replica_sarvam.serve_check(engine, {
            "model": section, "check": cfg["check"],
            "prompt": prompt.tolist(), "generated": said})
        device = engine.device
    finally:
        engine.shutdown()
    del engine          # its pools, before this tool makes its own
    gc.collect()

    seen = {}           # a float32 reference pass a comparison

    def compared(run, gen):
        key = (id(run), tuple(gen))
        if key not in seen:
            _tokens, logits, chose = run
            gen = np.asarray(gen, np.int32)
            out = replica_sarvam.compare(
                logits, chose, params, np.concatenate([prompt, gen])[:-1],
                gen, prompt.size, section, cfg["check"])
            seen[key] = {k: out[k] for k in judged}
        return seen[key]

    def along_the_answer(free, n):
        """The n-row program fed the engine's tokens: its free run where
        that made them anyway."""
        if free[0] == said:
            return free
        return apply_rows(model, params, ecfg, prompt, new_tokens, n,
                          forced=said, row=slot if n > 1 else 0)

    free_n = apply_rows(model, params, ecfg, prompt, new_tokens, rows,
                        row=slot)
    free_1 = apply_rows(model, params, ecfg, prompt, new_tokens, 1)
    fed_n = along_the_answer(free_n, rows)
    fed_1 = along_the_answer(free_1, 1)
    agree = float(np.mean([
        (np.sort(a, -1) == np.sort(b, -1)).all(-1)
        for a, b in zip(fed_n[2], fed_1[2])]))
    return {
        "seed": seed, "rows": rows, "device": device,
        "prompt_len": int(prompt.size), "new_tokens": new_tokens,
        "tokens": {"engine": said, f"rows={rows}": free_n[0],
                   "rows=1": free_1[0]},
        "engine_slot": slot,
        "engine_repeatable": said == again,
        "engine_is_rows_n": said == free_n[0],
        "rows_n_is_rows_1": free_n[0] == free_1[0],
        # along the engine's tokens, the N-row against the 1-row program
        "same_experts_pair_share_rows_n_vs_1": agree,
        "argmax_tol_rel": cfg["check"]["argmax_tol_rel"],
        "engine_tokens_following_the_benchmarks_row": {
            k: bench[k] for k in judged},
        "engine_tokens_following_rows_1": compared(fed_1, said),
        "engine_tokens_following_rows_n": compared(fed_n, said),
        "rows_n_tokens_following_rows_n": compared(free_n, free_n[0]),
        "rows_1_tokens_following_rows_1": compared(free_1, free_1[0]),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rows", type=int, required=True,
                    help="rows of a decode step: the engine's slots and "
                         "its scratch row")
    ap.add_argument("--prompt-len", type=int, default=512)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    from benchmarks.harness import modelcfg
    cfg = modelcfg.load(args.config, args.rehearse)
    out = check(cfg, args.seed, args.rows, args.prompt_len,
                args.new_tokens)
    text = json.dumps(out)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text + "\n")
    for name, toks in out["tokens"].items():
        print(f"{name:>10}: {toks}")
    for key in [k for k in out if "_following_" in k]:
        print(f"{key}: argmax_gap_rel "
              f"{out[key]['argmax_gap_rel']:.4f} (limit "
              f"{out['argmax_tol_rel']}), logit_err_rel "
              f"{out[key]['logit_err_rel']:.4f}")
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
