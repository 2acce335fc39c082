"""From a configuration file (Hugging Face key names, as published) to
the program's own LlamaConfig. `--rehearse` lays the file's `rehearse`
group over it: tiny widths for a CPU dry run, never a measurement."""
from __future__ import annotations

import copy
import json
import os

MODEL_KEYS = ("hidden_size", "num_hidden_layers", "num_attention_heads",
              "num_key_value_heads", "head_dim", "intermediate_size",
              "vocab_size", "rope_theta", "rms_norm_eps",
              "max_position_embeddings", "tie_word_embeddings")


def overlay(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = overlay(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


def load(path: str, rehearse: bool) -> dict:
    with open(path) as f:
        cfg = json.load(f)
    if rehearse:
        cfg = overlay(cfg, cfg.get("rehearse", {}))
    missing = [k for k in MODEL_KEYS if k not in cfg]
    if missing:
        raise SystemExit(f"benchmark: {os.path.basename(path)} lacks "
                         f"{missing}")
    if cfg["head_dim"] * cfg["num_attention_heads"] != cfg["hidden_size"]:
        raise SystemExit("benchmark: models/llama.py derives head_dim as "
                         "hidden_size / heads; this file disagrees")
    return cfg


def model_section(cfg: dict) -> dict:
    return {k: cfg[k] for k in MODEL_KEYS}


def llama_config(cfg: dict, *, param_dtype, remat: bool = False,
                 max_seq_len: int | None = None):
    from ray_tpu.models import LlamaConfig
    return LlamaConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"],
        max_seq_len=max_seq_len or cfg["max_position_embeddings"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        remat=remat, param_dtype=param_dtype)
