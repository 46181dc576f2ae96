"""Serving through the full paged stack: real attention over policy-managed
KV pages.

PagedTinyLM computes every decode step with ``kernels.paged_attention``
(Mosaic on TPU, the jnp oracle elsewhere) reading K/V through the page tables
that the ServingEngine + PagePool manage: prefix sharing, PBM preemption,
host spill — the kernel never sees a contiguous cache.

  PYTHONPATH=src python examples/serve_paged.py
"""

import sys

sys.path.insert(0, "src")

import numpy as np

from repro.serving import PagePool, Request, ServingEngine
from repro.serving.model import PagedTinyLM, TinyConfig


def main():
    cfg = TinyConfig(n_pages=96, page_size=16)
    lm = PagedTinyLM(cfg, seed=0)
    pool = PagePool(n_pages=cfg.n_pages, page_size=cfg.page_size,
                    page_bytes=cfg.page_size * cfg.n_kv_heads * cfg.head_dim * 2 * 2)
    eng = ServingEngine(pool, lm.step_fn, policy="pbm", max_batch=4)

    rng = np.random.default_rng(0)
    system_prompt = list(rng.integers(0, cfg.vocab, 32))  # 2 shared pages
    for _ in range(6):
        eng.submit(Request(
            prompt=system_prompt + list(rng.integers(0, cfg.vocab, 4)),
            max_new_tokens=8,
        ))
    st = eng.run_to_completion(max_steps=500)
    print(f"served {len(eng.finished)} requests in {st.steps} engine steps")
    print(f"prefix pages shared: {st.shared_prefix_pages}  "
          f"preemptions: {st.preemptions}")
    for r in eng.finished[:3]:
        print(f"  req {r.rid}: generated {r.generated}")
    # determinism check: same prompts, same tokens
    lm2 = PagedTinyLM(cfg, seed=0)
    pool2 = PagePool(n_pages=cfg.n_pages, page_size=cfg.page_size,
                     page_bytes=pool.page_bytes)
    eng2 = ServingEngine(pool2, lm2.step_fn, policy="opt", max_batch=4)
    rng = np.random.default_rng(0)
    system_prompt = list(rng.integers(0, cfg.vocab, 32))
    for _ in range(6):
        eng2.submit(Request(
            prompt=system_prompt + list(rng.integers(0, cfg.vocab, 4)),
            max_new_tokens=8,
        ))
    eng2.run_to_completion(max_steps=500)
    same = all(
        a.generated == b.generated
        for a, b in zip(
            sorted(eng.finished, key=lambda r: r.rid),
            sorted(eng2.finished, key=lambda r: r.rid),
        )
    )
    print(f"tokens identical under a different eviction policy: {same} "
          f"(paging must never change results)")


if __name__ == "__main__":
    main()
