"""Serving launcher: ``python -m repro.launch.serve --arch <id>``.

Runs the continuous-batching engine with the Balanced-PANDAS request router
over N replica groups (smoke config on CPU; production mesh on a fleet).
"""

from __future__ import annotations

import argparse


def build_engine(arch: str, scheduler: str = "balanced_pandas",
                 replicas: int = 4):
    """(model config, engine): the arch's smoke config with random weights
    from seed 0, behind `replicas` replica groups routed by `scheduler`."""
    import jax
    from repro.configs import registry
    from repro.models import params as P
    from repro.serve.engine import EngineConfig, ServingEngine

    cfg = registry.get_smoke_config(arch)
    prm = P.init_params(cfg, jax.random.PRNGKey(0))
    ecfg = EngineConfig(num_replicas=replicas,
                        replicas_per_pod=max(replicas // 2, 1),
                        slots_per_replica=2, max_len=64,
                        prefill_buckets=(16,), scheduler=scheduler)
    return cfg, ServingEngine(cfg, prm, ecfg)


def make_requests(cfg, n: int, seed: int = 0):
    """n seeded requests: 12-token prompts, 6 new tokens, 5 shared prefixes."""
    import numpy as np
    from repro.serve.engine import Request

    rng = np.random.default_rng(seed)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, 12).astype(np.int32),
                    max_new_tokens=6, prefix_id=i % 5)
            for i in range(n)]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="chatglm3_6b")
    ap.add_argument("--requests", type=int, default=16)
    from repro.core.policy import available_routers
    ap.add_argument("--scheduler", default="balanced_pandas",
                    choices=list(available_routers()))
    ap.add_argument("--replicas", type=int, default=4)
    args = ap.parse_args()

    import numpy as np

    cfg, eng = build_engine(args.arch, args.scheduler, args.replicas)
    out = eng.run_until_drained(make_requests(cfg, args.requests))
    lat = [r.finish_time - r.arrival for r in out]
    print(f"scheduler={args.scheduler} drained {len(out)} requests in "
          f"{eng.steps} engine steps; mean latency {np.mean(lat) * 1e3:.0f}ms; "
          f"tier mix {eng.assign_tiers}")


if __name__ == "__main__":
    main()
