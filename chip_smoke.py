#!/usr/bin/env python3
"""The quickest proof that ray_tpu's two main paths still run on the chip.

    python chip_smoke.py             # one chip: kernels, serve, train
    python chip_smoke.py --chips 4   # four chips: sharded train only
    python chip_smoke.py --rehearse  # tiny sizes, any platform (CPU dry run)

The parent never imports JAX (one owner per chip, ray_tpu/util/jaxenv.py).
Each phase is a child process of its own, pinned to the TPU, and the next
starts only when every process that held the chip has exited:

  kernels  flash attention fwd+bwd at S=2048 and the paged-decode kernel at
           Llama-3.2-1B widths, compiled (never interpreted), each against
           the XLA path of ops/attention.py.
  serve    ray_tpu.init() -> serve.run(build_openai_deployment(...)) with a
           replica that was granted the chip -> concurrent POST
           /v1/completions over HTTP. The driver holds no backend.
  train    SpmdTrainer(...).fit() in a fresh process, Llama-1B at sequence
           2048 (the auto route takes the Pallas flash kernel both ways).
  train4   (--chips 4 only) the same step on MeshSpec(fsdp=2, tp=2) against
           the same seed and batch on one of the four devices.

Model: LlamaConfig.llama3_1b at every preset width, no layer cut, bf16
parameters from --seed. Every phase prints one JSON line; the last line of
stdout is {"ok": true, "device": {"platform", "kind", "count"}} with the
device as JAX reported it to the children, and the exit code is 0 only
then. Without a TPU the first child fails before any model is built.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

TOTAL_BUDGET_S = 1150      # the contract allows 1200 s, compiles included
SEQ = 2048
# Train batch at sequence 2048, sized in the sandbox from
# compiled.memory_analysis() of the one-chip train step compiled for a
# described v5e: 5.7 GiB at 2 rows, 7.8 GiB at 4 (of 15.75 GiB; the
# float32 logits at vocab 128 256 are the large term, ~1 GiB a row).
TRAIN_BATCH = 4
KERNEL_TOL = 0.05          # bf16: max abs (fwd) / relative (bwd) error


def _say(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def _seq(args) -> int:
    return 256 if args.rehearse else SEQ


# --------------------------------------------------------------------------
# children
# --------------------------------------------------------------------------

def _model_cfg(rehearse: bool):
    import jax.numpy as jnp
    from ray_tpu.models import LlamaConfig
    if rehearse:
        return LlamaConfig(vocab_size=512, d_model=64, n_layers=2,
                           n_heads=4, n_kv_heads=2, d_ff=128,
                           max_seq_len=256, remat=True,
                           param_dtype=jnp.bfloat16)
    return LlamaConfig.llama3_1b(param_dtype=jnp.bfloat16, remat=True,
                                 max_seq_len=SEQ)


def _device_or_die(rehearse: bool, want_count: int) -> dict:
    """First thing a JAX-owning child does: name the device, and fail
    before any model is built unless it is the chip(s) asked for."""
    import jax
    from ray_tpu.util.jaxenv import enable_compile_cache
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not rehearse and (device["platform"] != "tpu"
                         or device["count"] != want_count):
        raise SystemExit(f"chip_smoke: need {want_count} TPU device(s), "
                         f"jax reports {device}")
    enable_compile_cache()
    return device


def _peak_bytes() -> "int | None":
    import jax
    stats = jax.devices()[0].memory_stats()   # None on the CPU backend
    return stats.get("peak_bytes_in_use") if stats else None


def phase_kernels(args) -> dict:
    device = _device_or_die(args.rehearse, 1)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from ray_tpu.ops.attention import (PagedKV, multi_head_attention,
                                       paged_cached_attention)

    cfg = _model_cfg(args.rehearse)
    seq = _seq(args)
    hq, hkv, d = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(jax.random.PRNGKey(args.seed), 3)
    q = jax.random.normal(ks[0], (1, seq, hq, d), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, seq, hkv, d), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, seq, hkv, d), jnp.bfloat16)

    def err(a, b):
        return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                     - b.astype(jnp.float32))))

    def fwd_and_grads(impl):
        def loss(q, k, v):
            out = multi_head_attention(q, k, v, causal=True, impl=impl)
            return (out.astype(jnp.float32) ** 2).sum(), out
        (_, out), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return out, grads

    t0 = time.time()
    # impl="pallas" is flash_attention with interpret chosen by the
    # backend alone: compiled by Mosaic wherever the platform is the TPU
    out_p, g_p = fwd_and_grads("pallas")
    out_x, g_x = fwd_and_grads("xla")
    flash_fwd = err(out_p, out_x)
    flash_bwd = max(err(a, b) / max(1.0, err(b, jnp.zeros_like(b)))
                    for a, b in zip(g_p, g_x))

    # paged decode: 8 slots, pages of 64, mixed lengths, pool shuffled
    slots, ps = 8, 64
    pages = seq // ps
    rng = np.random.RandomState(args.seed)
    n_pages = slots * pages

    def pool():
        return jnp.asarray(rng.randn((n_pages + 1) * ps, hkv, d),
                           jnp.bfloat16)

    cache = PagedKV(pool(), pool(),
                    jnp.asarray(rng.permutation(n_pages)
                                .reshape(slots, pages), jnp.int32),
                    jnp.asarray(rng.randint(ps, pages * ps - 1, (slots,)),
                                jnp.int32), ps)
    new = [jnp.asarray(rng.randn(slots, 1, h, d), jnp.bfloat16)
           for h in (hq, hkv, hkv)]
    outs = {}
    for impl in ("pallas", "gather"):
        # the knob is read while tracing: a fresh function per setting,
        # so that jit traces again
        os.environ["RAY_TPU_PAGED_ATTN_IMPL"] = impl
        outs[impl], _ = jax.jit(
            lambda *a: paged_cached_attention(*a))(
            *new, cache, cache.lengths[:, None])
    os.environ.pop("RAY_TPU_PAGED_ATTN_IMPL")
    paged = err(outs["pallas"], outs["gather"])
    seconds = time.time() - t0

    worst = max(flash_fwd, flash_bwd, paged)
    return {"ok": worst < KERNEL_TOL, "device": device,
            "interpret": jax.default_backend() == "cpu",
            "shape": {"seq": seq, "heads": hq, "kv_heads": hkv,
                      "head_dim": d, "page_size": ps, "slots": slots},
            "flash_fwd_max_err": flash_fwd, "flash_bwd_rel_err": flash_bwd,
            "paged_decode_max_err": paged, "max_kernel_err": worst,
            "seconds": round(seconds, 2),
            "peak_device_bytes": _peak_bytes()}


def _llama_factory(seed: int, rehearse: bool):
    """Runs inside the replica: (model, params) with seeded bf16 weights
    made on the replica's own device."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models import Llama
    model = Llama(_model_cfg(rehearse))
    params = jax.jit(lambda rng: model.init(
        rng, jnp.zeros((1, 8), jnp.int32))["params"])(
        jax.random.PRNGKey(seed))
    return model, params


def _worker_pids() -> list:
    """Live ray_tpu worker processes started by this driver process."""
    me, out = os.getpid(), []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmd = f.read()
            with open(f"/proc/{pid}/stat") as f:
                fields = f.read().rsplit(")", 1)[1].split()
        except OSError:
            continue          # exited while we looked
        state, ppid = fields[0], int(fields[1])
        if (b"ray_tpu.core.worker" in cmd and ppid == me
                and state != "Z"):
            out.append(int(pid))
    return out


def _shm_segments() -> set:
    return {n for n in os.listdir("/dev/shm") if "rtpu_" in n}


def phase_serve(args) -> dict:
    """The driver of the serve phase. It never touches a JAX backend: the
    replica's worker was granted the chip and is its only owner."""
    import functools
    import threading
    import urllib.request

    import numpy as np

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.serve.http_proxy import start_proxy
    from ray_tpu.serve.llm import build_openai_deployment

    shm_before = _shm_segments()
    t_start = time.time()
    rt = ray_tpu.init()
    store = type(rt.store).__name__
    chips = int(ray_tpu.cluster_resources().get("TPU", 0))
    if not args.rehearse and chips != 1:
        raise SystemExit(f"chip_smoke: the runtime counted {chips} chips "
                         f"without opening a backend; expected 1")
    vocab = _model_cfg(args.rehearse).vocab_size
    new_tokens = 8 if args.rehearse else 64
    buckets = (128,) if args.rehearse else (64, 256, 1024)
    lens = ([8, 20, 32, 60, 100, 128, 100] if args.rehearse
            else [32, 48, 200, 256, 600, 1000, 1024])
    app = build_openai_deployment(
        functools.partial(_llama_factory, args.seed, args.rehearse),
        engine_config={"max_slots": 8, "kv_page_size": 64,
                       "max_seq_len": _seq(args),
                       "prefill_buckets": buckets,
                       "max_new_tokens_default": new_tokens},
        model_name="llama3-1b-seeded",
        # the replica asks for the chip; a rehearsal on a host without
        # one leaves the replica's worker pinned to the CPU
        ray_actor_options={"num_tpus": 1} if chips else None)
    handle = serve.run(app, name="chip-smoke", route_prefix="/v1",
                       wait_for_ready_timeout_s=600)
    _proxy, port = start_proxy(port=0)
    ready_s = time.time() - t_start

    rng = np.random.RandomState(args.seed)
    prompts = [rng.randint(1, vocab, (n,)).tolist() for n in lens]
    # one greedy prompt sent twice; one request streamed; the rest sampled
    bodies = [{"prompt": p, "max_tokens": new_tokens,
               "temperature": 0.0 if i < 2 else 0.8}
              for i, p in enumerate([prompts[2], prompts[2], *prompts])]
    bodies[2]["stream"] = True
    answers: list = [None] * len(bodies)
    first_answer = []

    def post(i, body):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/completions",
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json",
                     "X-Serve-Timeout-S": "850"})
        with urllib.request.urlopen(req, timeout=860) as r:
            raw = r.read().decode()
            status = r.status
        first_answer.append(time.time())
        if body.get("stream"):
            events = [json.loads(line[5:]) for line in raw.splitlines()
                      if line.startswith("data:")
                      and line[5:].strip() != "[DONE]"]
            text = "".join(e["choices"][0]["text"] for e in events)
            answers[i] = {"status": status, "text": text,
                          "n": len(text.split())}
        else:
            out = json.loads(raw)
            answers[i] = {"status": status,
                          "text": out["choices"][0]["text"],
                          "n": out["usage"]["completion_tokens"]}

    t_req = time.time()
    threads = [threading.Thread(target=post, args=(i, b))
               for i, b in enumerate(bodies)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    requests_s = time.time() - t_req
    stats = handle.stats.remote().result(timeout_s=60)

    jax_mod = sys.modules.get("jax")
    driver_backend = bool(
        jax_mod is not None
        and jax_mod._src.xla_bridge.backends_are_initialized())
    workers = _worker_pids()
    serve.shutdown()
    ray_tpu.shutdown()
    deadline = time.time() + 60
    while _worker_pids() and time.time() < deadline:
        time.sleep(0.2)
    left_workers = _worker_pids()
    left_shm = sorted(_shm_segments() - shm_before)

    device = stats["device"]
    checks = {
        "all_answered": all(a is not None and a["status"] == 200
                            for a in answers),
        "all_full_length": all(a is not None and a["n"] == new_tokens
                               for a in answers),
        "greedy_repeatable": (answers[0] is not None
                              and answers[0]["text"]
                              == answers[1]["text"]),
        "replica_on_chip": device["platform"] == "tpu",
        "driver_holds_no_backend": not driver_backend,
        "no_worker_left": not left_workers,
        "no_shm_left": not left_shm,
    }
    if args.rehearse:
        del checks["replica_on_chip"]     # a dry run has no chip to be on
    return {"ok": all(checks.values()), "device": device, "checks": checks,
            "store": store, "chips_counted": chips,
            "requests": len(bodies), "prompt_tokens": [len(b["prompt"])
                                                       for b in bodies],
            "new_tokens": new_tokens, "workers_seen": len(workers),
            "ready_s": round(ready_s, 2),
            "time_to_first_answer_s": round(min(first_answer) - t_req, 2)
            if first_answer else None,
            "requests_s": round(requests_s, 2),
            "prefill_compile_ms": stats.get("prefill_compile_ms"),
            "decode_steps": stats.get("decode_steps"),
            "kv_pages": stats.get("kv_pages"),
            "peak_device_bytes": stats.get("peak_device_bytes"),
            "seconds": round(time.time() - t_start, 2)}


def _train_batch(cfg, batch: int, seq: int, seed: int) -> dict:
    import numpy as np
    rng = np.random.RandomState(seed)
    return {"tokens": rng.randint(0, cfg.vocab_size, (batch, seq + 1),
                                  dtype=np.int32)}


def _trainer(cfg, mesh_spec, batch: dict, steps: int, seed: int, losses):
    from ray_tpu.models import Llama
    from ray_tpu.train import SpmdTrainer, SpmdTrainerConfig

    def data():
        while True:
            yield batch

    return SpmdTrainer(
        SpmdTrainerConfig(model=Llama(cfg), mesh=mesh_spec,
                          optimizer="adafactor", learning_rate=1e-2,
                          warmup_steps=1, total_steps=steps, log_every=1,
                          seed=seed),
        data, report_fn=lambda m: losses.append(m["loss"]))


def phase_train(args) -> dict:
    device = _device_or_die(args.rehearse, 1)
    import math

    from ray_tpu.parallel import MeshSpec

    cfg = _model_cfg(args.rehearse)
    batch = _train_batch(cfg, TRAIN_BATCH, _seq(args), args.seed)
    losses: list = []
    t0 = time.time()
    _trainer(cfg, MeshSpec(), batch, 6, args.seed, losses).fit()
    seconds = time.time() - t0
    return {"ok": (len(losses) == 6 and all(map(math.isfinite, losses))
                   and losses[-1] < losses[0]),
            "device": device, "losses": losses, "batch": TRAIN_BATCH,
            "seq": _seq(args), "optimizer": "adafactor",
            "remat": cfg.remat,
            "seconds": round(seconds, 2),
            "peak_device_bytes": _peak_bytes()}


def phase_train4(args) -> dict:
    """Sharded training across four chips against one of them, same seed
    and batch, in one process."""
    device = _device_or_die(args.rehearse, 4)
    import re

    import jax
    import numpy as np

    from ray_tpu.models import Llama
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.train import make_optimizer, make_train_step

    cfg = _model_cfg(args.rehearse)
    batch = _train_batch(cfg, TRAIN_BATCH, _seq(args), args.seed)
    losses4: list = []
    t0 = time.time()
    trainer = _trainer(cfg, MeshSpec(fsdp=2, tp=2), batch, 2, args.seed,
                       losses4)
    trainer.fit()
    sharded_s = time.time() - t0

    # where the parameters live: bytes per device from addressable_shards
    per_device = {d.id: 0 for d in jax.devices()}
    total = 0
    for leaf in jax.tree_util.tree_leaves(trainer.state.params):
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            per_device[shard.device.id] += shard.data.nbytes
    shares = [b / total for b in per_device.values()]
    # what the compiler put in for the fsdp and tp axes, and whether the
    # flash kernel is still there
    t0 = time.time()
    dev_batch = jax.device_put(batch, trainer.step.batch_shardings)
    text = trainer.step.step_fn.lower(trainer.state,
                                      dev_batch).compile().as_text()
    text_s = time.time() - t0     # a second compile of the same step
    collectives = {op: len(re.findall(rf"\b{op}(?:-start)?\(", text))
                   for op in ("all-gather", "all-reduce", "reduce-scatter",
                              "all-to-all", "collective-permute")}
    pallas_calls = text.count("tpu_custom_call")
    del trainer, dev_batch

    # the reference: same seed, same batch, a mesh over one device
    t0 = time.time()
    mesh1 = build_mesh(MeshSpec(), devices=jax.devices()[:1])
    tx = make_optimizer("adafactor", learning_rate=1e-2)
    state, step = make_train_step(Llama(cfg), tx, mesh1)(
        jax.random.PRNGKey(args.seed), batch)
    _, metrics = step(state, jax.device_put(batch, step.batch_shardings))
    loss1 = float(metrics["loss"])
    single_s = time.time() - t0

    checks = {
        "loss_matches": bool(np.isfinite(loss1)
                             and abs(losses4[0] - loss1) < 0.05),
        "params_spread": max(shares) < 0.35,
        "has_all_gather": collectives["all-gather"] > 0,
        "has_reduce": (collectives["all-reduce"]
                       + collectives["reduce-scatter"]) > 0,
        "flash_kernel_present": args.rehearse or pallas_calls > 0,
    }
    return {"ok": all(checks.values()), "device": device, "checks": checks,
            "mesh": {"fsdp": 2, "tp": 2}, "batch": TRAIN_BATCH,
            "seq": _seq(args), "loss_sharded": losses4[0],
            "loss_one_device": loss1,
            "param_share_per_device": [round(s, 4) for s in shares],
            "collectives": collectives, "pallas_calls": pallas_calls,
            "sharded_s": round(sharded_s, 2),
            "compiled_text_s": round(text_s, 2),
            "one_device_s": round(single_s, 2),
            "peak_device_bytes": _peak_bytes()}


PHASES = {"kernels": phase_kernels, "serve": phase_serve,
          "train": phase_train, "train4": phase_train4}


# --------------------------------------------------------------------------
# parent
# --------------------------------------------------------------------------

def _cache_entries(path: str) -> set:
    """Compiled programs in jax's persistent cache (one `<key>-cache` file
    each, next to its `-atime` stamp and a lock file)."""
    try:
        return {n for n in os.listdir(path) if n.endswith("-cache")}
    except FileNotFoundError:
        return set()


_CHILD: "subprocess.Popen | None" = None


def _kill_child_group(*_sig) -> None:
    """Kill the running phase with everything it started (a replica's
    worker holds the chip). As a signal handler it also ends the run."""
    if _CHILD is not None:
        try:
            os.killpg(_CHILD.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
    if _sig:
        os._exit(143)


def run_phase(name: str, args, timeout_s: float) -> dict:
    """One phase in one child, in a process group of its own; returns
    what the child reported. The group is gone (and the chip free) when
    this returns."""
    global _CHILD
    from ray_tpu.util.jaxenv import subprocess_env_tpu
    env = dict(os.environ)
    if not args.rehearse:
        subprocess_env_tpu(env)
    cache_dir = env.get("JAX_COMPILATION_CACHE_DIR", "")
    before = _cache_entries(cache_dir)
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", name,
           "--seed", str(args.seed)] + (["--rehearse"] * args.rehearse)
    t0 = time.time()
    _CHILD = proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                                     text=True, start_new_session=True)
    try:
        out, _ = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        _kill_child_group()
        proc.communicate()
        return {"phase": name, "ok": False,
                "error": f"timed out after {timeout_s:.0f}s"}
    finally:
        _kill_child_group()       # no straggler outlives its phase
        _CHILD = None
    lines = out.strip().splitlines()
    report = None
    if proc.returncode == 0 and lines:
        try:
            report = json.loads(lines[-1])
        except json.JSONDecodeError:
            pass
    if not isinstance(report, dict):
        return {"phase": name, "ok": False, "rc": proc.returncode,
                "error": "no report from the phase",
                "tail": lines[-3:]}
    new = _cache_entries(cache_dir) - before
    # jax persists only compiles that took over a second. warm: the phase
    # added nothing to a cache that had entries; mixed: it found entries
    # and still added some (a kernel compile near the one-second bar, or
    # the engine batching concurrent prompts differently this time)
    report.update(phase=name, wall_s=round(time.time() - t0, 2),
                  cache={"dir": cache_dir, "entries_before": len(before),
                         "new_entries": len(new),
                         "state": "off" if not cache_dir else
                         "cold" if not before else
                         "mixed" if new else "warm"})
    return report


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on whatever platform JAX has: a dry "
                         "run of the control flow, never a chip result")
    ap.add_argument("--phase", choices=sorted(PHASES),
                    help=argparse.SUPPRESS)       # child mode
    args = ap.parse_args()

    if args.phase:
        _say(PHASES[args.phase](args))
        sys.stdout.flush()
        # skip interpreter teardown: engine/XLA threads may abort at exit
        # after the report is out; every owned process is already gone
        os._exit(0)

    signal.signal(signal.SIGTERM, _kill_child_group)
    phases = ["train4"] if args.chips == 4 else ["kernels", "serve", "train"]
    deadline = time.time() + TOTAL_BUDGET_S
    device = None
    for name in phases:
        report = run_phase(name, args, deadline - time.time())
        _say(report)
        if not report.get("ok"):
            _say({"ok": False, "failed": name})
            return 1
        if device not in (None, report["device"]):
            _say({"ok": False, "failed": name,
                  "error": f"phases disagree on the device: {device} vs "
                           f"{report['device']}"})
            return 1
        device = report["device"]
    if not args.rehearse and (device["platform"] != "tpu"
                              or device["count"] != args.chips):
        _say({"ok": False, "error": f"not the chip asked for: {device}"})
        return 1
    _say({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
