"""What the service path adds before the engine: the client's median
time to first token less the engine's own median from submit to first
token (`ttft_breakdown_p50_ms.total_ms`, its last 512 requests)."""
from benchmarks.harness import window


def read(run, **_):
    if run.get("kind") != "serve":
        return None
    inner = (run["stats1"].get("ttft_breakdown_p50_ms") or {}).get("total_ms")
    ttft = [v for v in window.ttft_ms(run["streams"], run["t0"], run["t1"])
            if v != float("inf")]
    if inner is None or not ttft:
        return None
    return window.percentile(ttft, 50) - inner
