"""Quickstart: the paper's result in 60 seconds, on all three layers.

1. Queueing layer — Balanced-PANDAS vs JSQ-MaxWeight under rate
   mis-estimation (the paper's core experiment, reduced horizon).
2. Kernel layer — the batched routing kernel vs its oracle.
3. Framework layer — 20 training steps of a small LM fed by the
   locality-aware data pipeline.  The pipeline synthesizes Zipf-skewed
   tokens (`token_skew`) and the optimizer warms up within the run, so
   the loss drop is a real signal, not noise: uniform tokens have no
   learnable statistics (cross-entropy is already at ln(V)), which is
   why the original uniform-token assertion flaked.

    PYTHONPATH=src python examples/quickstart.py [--fast]

``--fast`` is the CI examples-smoke setting: reduced horizons, 12
training steps, same assertions.
"""

import argparse

import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke: reduced horizons, same assertions")
    args = ap.parse_args()

    # --- 1. the paper's robustness experiment (reduced) --------------------
    from repro.core import locality as loc, simulator as sim
    horizon, warmup = (2500, 600) if args.fast else (8000, 2000)
    cfg = sim.default_config(horizon=horizon, warmup=warmup)
    cap = loc.capacity_hot_rack(cfg.topo, cfg.true_rates, cfg.p_hot)
    lam = 0.95 * cap
    print(f"== queueing: M={cfg.topo.num_servers}, capacity={cap:.1f} "
          f"tasks/slot, load=0.95 ==")
    for algo in ("balanced_pandas", "pandas_po2", "jsq_maxweight"):
        row = [algo]
        for mode, eps, sign in (("network", 0.0, -1),
                                ("per_server", 0.3, -1),
                                ("per_server", 0.3, +1)):
            est = sim.make_estimates(cfg, mode, eps, sign, seed=7)
            out = sim.simulate(algo, cfg, lam, est, seed=0)
            row.append(f"{out['mean_delay']:6.2f}")
        print(f"  {row[0]:16s} delay: exact={row[1]} -30%={row[2]} "
              f"+30%={row[3]}  (slots)")
    print("  -> Balanced-PANDAS holds its delay under mis-estimated rates.")

    # --- 2. the routing kernel ----------------------------------------------
    import jax.numpy as jnp
    from repro.kernels import ops, ref
    rng = np.random.default_rng(0)
    m, b = 1024, 128
    q = jnp.asarray(rng.integers(0, 20, (m, 3)), jnp.int32)
    serving = jnp.asarray(rng.integers(0, 4, m), jnp.int32)
    er = jnp.asarray(np.tile([0.5, 0.45, 0.25], (m, 1)), jnp.float32)
    sr = jnp.asarray(np.arange(m) // 32, jnp.int32)
    tl = jnp.sort(jnp.asarray(rng.integers(0, m, (b, 3)), jnp.int32), 1)
    s_k, t_k, _ = ops.fleet_route(q, serving, er, sr, tl)
    s_r, t_r, _ = ref.fleet_route(q, serving, er, sr, tl)
    assert (np.asarray(s_k) == np.asarray(s_r)).all()
    print(f"== kernel: fleet_route({b} tasks x {m} servers) matches oracle; "
          f"locality mix {np.bincount(np.asarray(t_k), minlength=3)} ==")

    # --- 3. training through the locality-aware pipeline --------------------
    import dataclasses
    from repro.configs import registry, runtime
    from repro.data.pipeline import DataPipeline, PipelineConfig
    from repro.launch import mesh as mesh_lib
    from repro.train.trainer import Trainer, TrainerConfig
    cfg_m = registry.get_smoke_config("granite_moe_1b")
    mesh = mesh_lib.make_test_mesh((1, 1), ("data", "model"))
    plan = runtime.plan_for(cfg_m, "train_4k", "train", dp_axes=("data",))
    # quickstart-sized optimizer: the production plan warms up over 100
    # steps, which would leave the LR (and the loss) flat for this run
    plan = dataclasses.replace(plan, opt=dataclasses.replace(
        plan.opt, warmup_steps=5, decay_steps=200))
    steps = 12 if args.fast else 20
    pipe = DataPipeline(PipelineConfig(vocab_size=cfg_m.vocab_size,
                                       seq_len=64, global_batch=4, seed=0,
                                       token_skew=1.2))
    tr = Trainer(cfg_m, TrainerConfig(seq_len=64, global_batch=4,
                                      steps=steps, log_every=5), mesh, plan,
                 pipeline=pipe)
    hist = tr.run()
    print("== training (granite-moe smoke config, locality-aware pipeline) ==")
    for h in hist:
        print(f"  step {h['step']:3d} loss {h['loss']:.3f} "
              f"locality(l/r/rem)={tuple(round(x, 2) for x in h['data_locality'])}")
    assert hist[-1]["loss"] < hist[0]["loss"] - 0.2, \
        (hist[0]["loss"], hist[-1]["loss"])
    print("done.")


if __name__ == "__main__":
    from repro.utils.cache import enable_persistent_cache

    enable_persistent_cache()
    main()
