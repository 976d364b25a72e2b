"""Fault tolerance demo: train, 'lose' half the data axis, replan the mesh,
restore the atomic checkpoint with new shardings, and keep training.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/elastic_restart.py
"""

import os

if "xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import tempfile


def main() -> None:
    import numpy as np
    from repro.configs import registry, runtime
    from repro.launch import mesh as mesh_lib
    from repro.launch.elastic import plan_elastic_mesh
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = registry.get_smoke_config("mamba2_13b")
    plan = runtime.plan_for(cfg, "train_4k", "train", dp_axes=("data",))
    ckpt = tempfile.mkdtemp(prefix="elastic_ckpt_")

    mesh1 = mesh_lib.make_test_mesh((4, 2), ("data", "model"))
    print(f"phase 1: mesh {dict(mesh1.shape)} — 6 steps, checkpoint every 3")
    tr1 = Trainer(cfg, TrainerConfig(seq_len=64, global_batch=8, steps=6,
                                     ckpt_dir=ckpt, ckpt_every=3,
                                     log_every=2), mesh1, plan)
    h1 = tr1.run()
    print(f"  loss {h1[0]['loss']:.3f} -> {h1[-1]['loss']:.3f}")

    # --- simulate losing 4 of 8 chips --------------------------------------
    surviving = 4
    shape, names = plan_elastic_mesh(surviving, model_axis=2,
                                     pod_size=10**9)
    print(f"phase 2: lost 4 chips; replanned mesh {shape} axes {names}")
    mesh2 = mesh_lib.make_test_mesh(shape, names)
    tr2 = Trainer(cfg, TrainerConfig(seq_len=64, global_batch=8, steps=4,
                                     ckpt_dir=ckpt, log_every=2),
                  mesh2, plan)
    start = tr2.restore_or_init()
    print(f"  restored step {start} from the atomic checkpoint, resuming")
    h2 = tr2.run()
    print(f"  loss continues {h2[0]['loss']:.3f} -> {h2[-1]['loss']:.3f}")
    assert h2[-1]["loss"] < h1[0]["loss"]
    print("elastic restart OK")


if __name__ == "__main__":
    from repro.utils.cache import enable_persistent_cache

    enable_persistent_cache()
    main()
