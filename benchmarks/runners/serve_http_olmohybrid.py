"""Runner of the serve cells whose model is Olmo-Hybrid-7B (a mixer a
layer: Gated DeltaNet with a recurrent state a slot, or full attention
over paged K and V, through models/hybrid.py): `serve_http.run` with the
server class, the model factory, the model section and the preset probe
of `harness/replica_olmohybrid.py`.
"""
from __future__ import annotations

from . import serve_http


def olmohybrid_family() -> dict:
    from ..harness.replica_olmohybrid import (OlmoHybridBenchServer,
                                              hybrid_preset, model_factory,
                                              model_section)
    return {"server_cls": OlmoHybridBenchServer,
            "model_factory": model_factory, "model_section": model_section,
            "probe": hybrid_preset}


def run(ctx: dict):
    return serve_http.run(ctx, olmohybrid_family)
