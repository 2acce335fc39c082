"""Runner of the serve cells whose model is Solar-Open2-250B (delta-rule
layers with a decay a key channel and a state a slot, gated NoPE
grouped-query layers over paged K and V, an expert share beside a shared
expert in every block, through models/hybrid.py): `serve_http.run` with
the server class, the model factory, the model section and the preset
probe of `harness/replica_solar.py`.
"""
from __future__ import annotations

from . import serve_http


def solar_family() -> dict:
    from ..harness.replica_solar import (SolarBenchServer, model_factory,
                                         model_section, solar_preset)
    return {"server_cls": SolarBenchServer, "model_factory": model_factory,
            "model_section": model_section, "probe": solar_preset}


def run(ctx: dict):
    return serve_http.run(ctx, solar_family)
