"""End-to-end driver (the paper's kind is scheduling/serving): serve a small
model with batched requests through the continuous-batching engine, comparing
schedulers under a straggling replica.

    PYTHONPATH=src python examples/serve_cluster.py [--requests 24]

A 4-replica / 2-pod fleet serves real greedy decoding; replica 1 is 5x slow
and the routers only learn it through observed service times (blind
estimation).  Balanced-PANDAS keeps latency flat; FIFO (Hadoop default)
pays the full straggler cost.
"""

import argparse
import time

import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--new-tokens", type=int, default=6)
    args = ap.parse_args()

    import jax
    from repro.configs import registry
    from repro.models import params as P
    from repro.serve.engine import EngineConfig, Request, ServingEngine

    cfg = registry.get_smoke_config("chatglm3_6b")
    prm = P.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, 12).astype(np.int32)
               for _ in range(args.requests)]

    print(f"serving {args.requests} requests x {args.new_tokens} new tokens "
          f"on 4 replicas (2 pods), replica 1 is 5x slow\n")
    results = {}
    for scheduler in ("balanced_pandas", "pandas_po2", "jsq_maxweight",
                      "fifo"):
        ecfg = EngineConfig(num_replicas=4, replicas_per_pod=2,
                            slots_per_replica=2, max_len=64,
                            prefill_buckets=(16,), scheduler=scheduler)
        eng = ServingEngine(cfg, prm, ecfg, slow_replicas={1: 5.0})
        reqs = [Request(rid=i, prompt=p, max_new_tokens=args.new_tokens,
                        prefix_id=i % 6) for i, p in enumerate(prompts)]
        t0 = time.monotonic()
        out = eng.run_until_drained(reqs, max_steps=1500)
        wall = time.monotonic() - t0
        lat = np.mean([r.finish_time - r.arrival for r in out])
        spread = np.bincount([r.replica for r in out], minlength=4)
        results[scheduler] = eng.steps
        print(f"{scheduler:16s} engine_steps={eng.steps:4d} "
              f"wall={wall:5.1f}s mean_latency={lat * 1e3:7.0f}ms "
              f"replica spread={spread.tolist()} "
              f"tier mix={eng.assign_tiers}")
    print("\n(sample output tokens, request 0:",
          out[0].generated[:8], ")")


if __name__ == "__main__":
    from repro.utils.cache import enable_persistent_cache

    enable_persistent_cache()
    main()
