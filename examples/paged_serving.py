"""Paged-KV serving: more concurrent sequences in the same HBM budget.

The engine keeps KV in a shared page pool (cfg.kv_page_size tokens a
page; vLLM's PagedAttention re-designed TPU-first — static shapes, one
decode program per page window, a Pallas kernel reads pages directly on
real TPU). Requests reserve only ceil((prompt + budget) / page_size)
pages, so a short request strands no max_seq_len of HBM, and a registered
prefix is pinned SHARED pages: adopters reference its full pages for
free and copy only the partial tail page.

Run (CPU):
  env JAX_PLATFORMS=cpu python examples/paged_serving.py
"""
import threading
import time

import numpy as np
import jax

from ray_tpu.models import Llama, LlamaConfig
from ray_tpu.serve.llm import LLMEngine, LLMEngineConfig


def main():
    cfg = LlamaConfig(vocab_size=512, d_model=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, d_ff=128, max_seq_len=256)
    model = Llama(cfg)
    params = model.init_params(jax.random.PRNGKey(0))

    engine = LLMEngine(model, params, LLMEngineConfig(
        max_slots=16,              # the pool bounds HBM, not the slots
        max_seq_len=256,
        prefill_buckets=(16, 32, 64),
        kv_page_size=16,           # pages of 16 tokens
        kv_pool_tokens=1024,       # total KV budget: 64 pages
        max_prefixes=2,
        prefill_chunk=32,
    ))

    # a shared system prompt, prefilled once, pinned as shared pages
    system = np.arange(7, 7 + 45) % 512
    pid = engine.register_prefix(system)
    print(f"registered 45-token prefix -> "
          f"{engine.get_stats()['kv_pages']['pinned_prefix']} pinned pages")

    # 12 concurrent short requests in a budget of only
    # 1024/256 = 4 sequences of max_seq_len
    results = {}

    def one(i):
        rid = engine.submit(np.arange(2, 10 + i) % 512,
                            max_new_tokens=12,
                            prefix_id=pid if i % 2 == 0 else None)
        results[i] = list(engine.stream(rid))

    threads = [threading.Thread(target=one, args=(i,)) for i in range(12)]
    t0 = time.time()
    for t in threads:
        t.start()
    peak = 0
    while any(t.is_alive() for t in threads):
        peak = max(peak, engine.get_stats()["active"])
        time.sleep(0.01)
    for t in threads:
        t.join()
    stats = engine.get_stats()
    print(f"12 requests in {time.time() - t0:.2f}s, "
          f"peak concurrency {peak}")
    print("page pool:", stats["kv_pages"])
    print("prefix tokens saved:", stats["prefix_tokens_saved"])
    assert all(len(toks) == 12 for toks in results.values())
    engine.shutdown()
    print("OK")


if __name__ == "__main__":
    main()
