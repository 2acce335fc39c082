"""Runner of the serve cells whose model is LFM2-24B-A2B (gated short
convolutions with a two-token state a slot, full attention over paged K
and V of 64-wide heads, routed experts, through models/hybrid.py):
`serve_http.run` with the server class, the model factory, the model
section and the preset probe of `harness/replica_lfm2moe.py`.
"""
from __future__ import annotations

from . import serve_http


def lfm2moe_family() -> dict:
    from ..harness.replica_lfm2moe import (Lfm2MoeBenchServer, lfm2_preset,
                                           model_factory, model_section)
    return {"server_cls": Lfm2MoeBenchServer,
            "model_factory": model_factory, "model_section": model_section,
            "probe": lfm2_preset}


def run(ctx: dict):
    return serve_http.run(ctx, lfm2moe_family)
