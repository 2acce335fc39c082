"""Runner of the serve cells whose model is Nemotron-3-Super-120B-A12B
(Mamba-2 layers with a state a slot, one NoPE grouped-query layer over
paged K and V, a share of relu^2 experts computed in a latent beside a
shared expert, through models/hybrid.py): `serve_http.run` with the
server class, the model factory, the model section and the preset probe
of `harness/replica_nemotron.py`.
"""
from __future__ import annotations

from . import serve_http


def nemotron_family() -> dict:
    from ..harness.replica_nemotron import (NemotronBenchServer,
                                            model_factory, model_section,
                                            nemotron_preset)
    return {"server_cls": NemotronBenchServer,
            "model_factory": model_factory, "model_section": model_section,
            "probe": nemotron_preset}


def run(ctx: dict):
    return serve_http.run(ctx, nemotron_family)
