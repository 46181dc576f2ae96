"""Serving driver: batched requests through the paged-KV engine.

  PYTHONPATH=src python -m repro.launch.serve --requests 16 --policy pbm

Runs the continuous-batching engine over an oversubscribed HBM page pool
with a shared system prompt; ``--real-model`` decodes through
``kernels.ops.paged_attention`` (the Mosaic kernel on a TPU, the jnp
oracle elsewhere) instead of the fast stub.
"""

from __future__ import annotations

import argparse

import numpy as np

from repro.serving import PagePool, Request, ServingEngine


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--pool-pages", type=int, default=48)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=8)
    from repro.core import policy_registry
    ap.add_argument("--policy", default="pbm",
                    choices=policy_registry.names(backend="serving"))
    ap.add_argument("--prefix-len", type=int, default=64)
    ap.add_argument("--real-model", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    rng = np.random.default_rng(args.seed)
    if args.real_model:
        from repro.serving.model import PagedTinyLM, TinyConfig

        cfg = TinyConfig(n_pages=args.pool_pages, page_size=args.page_size)
        lm = PagedTinyLM(cfg, seed=args.seed)
        step_fn = lm.step_fn
        page_bytes = args.page_size * cfg.n_kv_heads * cfg.head_dim * 2 * 2
    else:
        step_fn = lambda reqs: [int((r.kv.length * 0x9E3779B1) % 50000)
                                for r in reqs]
        page_bytes = args.page_size * 8 * 128 * 2 * 2

    pool = PagePool(args.pool_pages, args.page_size, page_bytes)
    eng = ServingEngine(pool, step_fn, policy=args.policy,
                        max_batch=args.max_batch)
    prefix = list(rng.integers(0, 100, args.prefix_len))
    for _ in range(args.requests):
        eng.submit(Request(
            prompt=prefix + list(rng.integers(0, 100, 8)),
            max_new_tokens=int(rng.integers(16, 96)),
        ))
    st = eng.run_to_completion(max_steps=50_000)
    print(f"policy={args.policy} served={len(eng.finished)} steps={st.steps} "
          f"tokens={st.tokens_generated} tok/step={st.tokens_generated/max(1,st.steps):.2f}")
    print(f"prefix_shared_pages={st.shared_prefix_pages} "
          f"preemptions={st.preemptions} "
          f"swap={(st.swap_out_bytes + st.swap_in_bytes)/1e6:.2f}MB")


if __name__ == "__main__":
    main()
