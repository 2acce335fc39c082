"""Runner of the serve cells whose model is sarvam-105b (latent
attention, an expert share, a shared expert, through
models/latent_moe.py): `serve_http.run` with the server class, the model
factory, the model section and the preset probe of
`harness/replica_sarvam.py`.
"""
from __future__ import annotations

from . import serve_http


def sarvam_family() -> dict:
    from ..harness.replica_sarvam import (SarvamBenchServer, model_factory,
                                          model_section, sarvam_preset)
    return {"server_cls": SarvamBenchServer, "model_factory": model_factory,
            "model_section": model_section, "probe": sarvam_preset}


def run(ctx: dict):
    return serve_http.run(ctx, sarvam_family)
