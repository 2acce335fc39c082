"""Runner of the serve cells whose model is Xing4.0-29B-A4B (four
residual streams mixed by manifold-constrained hyper-connections, latent
attention with a low-rank query, routed experts beside a shared one,
through models/latent_moe.py): `serve_http.run` with the server class,
the model factory, the model section and the preset probe of
`harness/replica_xing.py`.
"""
from __future__ import annotations

from . import serve_http


def xing_family() -> dict:
    from ..harness.replica_xing import (XingBenchServer, model_factory,
                                        model_section, xing_preset)
    return {"server_cls": XingBenchServer, "model_factory": model_factory,
            "model_section": model_section, "probe": xing_preset}


def run(ctx: dict):
    return serve_http.run(ctx, xing_family)
