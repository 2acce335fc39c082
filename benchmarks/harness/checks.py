"""The comparisons that decide `correct`, outside the measured window.

Serving: the system's own model code and kernels (prefill into pages,
then decode through the paged cache, exactly as the engine's step
programs call them) against the float32 reference's full forward pass on
a seeded prompt and the tokens the engine itself answered with over
HTTP. Logits are compared, not tokens: with random weights the largest
logit changes on rounding. The engine's own greedy tokens are held to
the reference too: each must be within a small margin of the reference's
largest logit. The tolerances are the configuration file's (`check`), with
the reason for them: they follow from its widths and types.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

from . import reference



def serve_check(engine, spec: Dict[str, Any]) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp
    from ray_tpu.ops.attention import PagedKV

    m = spec["model"]
    logit_tol = spec["check"]["logit_tol_rel"]
    argmax_tol = spec["check"]["argmax_tol_rel"]
    prompt = np.asarray(spec["prompt"], np.int32)
    gen = np.asarray(spec["generated"], np.int32)
    p, g = prompt.size, gen.size
    seq = np.concatenate([prompt, gen])
    model, params = engine.model, engine.params
    ps = engine.cfg.kv_page_size
    pad = engine._bucket(p)
    mc = model.cfg

    ref = reference.forward_logits(params, jnp.asarray(seq[:-1]), m, last=g)
    ref = np.asarray(ref, np.float32)                    # (g, vocab)

    n_pages = -(-(pad + g + 1) // ps)
    rows = (n_pages + 1) * ps
    pools = [(jnp.zeros((rows, mc.n_kv_heads, mc.head_dim), mc.dtype),
              jnp.zeros((rows, mc.n_kv_heads, mc.head_dim), mc.dtype))
             for _ in range(mc.n_layers)]
    table = jnp.arange(n_pages, dtype=jnp.int32)[None, :]

    @jax.jit
    def prefill(params, pools, tokens):
        entries = [PagedKV(k, v, table[:, :-(-pad // ps)],
                           jnp.zeros((1,), jnp.int32), ps, fresh=True)
                   for k, v in pools]
        pos = jnp.arange(pad)[None, :]
        logits, new = model.apply({"params": params}, tokens,
                                  cache=entries, positions=pos)
        return logits[0, p - 1], [(e.k_flat, e.v_flat) for e in new]

    @jax.jit
    def decode(params, pools, token, length):
        entries = [PagedKV(k, v, table, length, ps) for k, v in pools]
        logits, new = model.apply({"params": params}, token[:, None],
                                  cache=entries, positions=length[:, None])
        return logits[0, 0], [(e.k_flat, e.v_flat) for e in new]

    toks = np.zeros((1, pad), np.int32)
    toks[0, :p] = prompt
    out = []
    row, pools = prefill(params, pools, jnp.asarray(toks))
    out.append(np.asarray(row, np.float32))
    for j in range(1, g):
        row, pools = decode(params, pools, jnp.asarray(gen[j - 1:j]),
                            jnp.asarray([p + j - 1], jnp.int32))
        out.append(np.asarray(row, np.float32))
    got = np.stack(out)
    del pools

    scale = float(ref.std())
    err = float(np.abs(got - ref).max()) / scale
    gap = float((ref.max(-1) - ref[np.arange(g), gen]).max()) / scale
    return {"logit_err_rel": err, "logit_tol_rel": logit_tol,
            "argmax_gap_rel": gap, "argmax_tol_rel": argmax_tol,
            "prompt_len": int(p), "new_tokens": int(g),
            "prefill_bucket": int(pad), "logit_std": scale,
            "ok": bool(err <= logit_tol and gap <= argmax_tol
                       and np.isfinite(got).all())}
