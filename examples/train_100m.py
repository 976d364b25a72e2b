"""Train a ~100M-parameter dense LM for a few hundred steps through the full
stack (locality-aware pipeline -> FSDP/TP sharded train step -> atomic
checkpoints).

    PYTHONPATH=src python examples/train_100m.py --steps 300      # full run
    PYTHONPATH=src python examples/train_100m.py --steps 20       # smoke

On this 1-core CPU container a full 300-step run takes hours; the default is
sized to finish in minutes while exercising every component.  On a TPU fleet
the same script runs the production mesh via --mesh.
"""

import argparse
import dataclasses


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--ckpt-dir", default="experiments/train_100m_ckpt")
    args = ap.parse_args()

    from repro.configs import runtime
    from repro.launch import mesh as mesh_lib
    from repro.models.config import (LayerSpec, ModelConfig, param_count,
                                     uniform_stages)
    from repro.train.trainer import Trainer, TrainerConfig

    # ~100M params: 12L, d=768, 12 heads, ff=2048, 32k vocab.
    cfg = ModelConfig(
        name="lm-100m", family="dense", d_model=768, num_heads=12,
        num_kv_heads=12, head_dim=64, d_ff=2048, vocab_size=32_000,
        stages=uniform_stages(12, LayerSpec(kind="attn")),
        tie_embeddings=True, dtype="float32")
    print(f"model: {param_count(cfg) / 1e6:.1f}M parameters")

    shape = tuple(int(x) for x in args.mesh.split("x"))
    mesh = mesh_lib.make_test_mesh(shape, ("data", "model"))
    plan = runtime.plan_for(cfg, "train_4k", "train",
                            dp_axes=mesh_lib.dp_axes(mesh))
    tr = Trainer(cfg, TrainerConfig(
        seq_len=args.seq_len, global_batch=args.global_batch,
        steps=args.steps, ckpt_dir=args.ckpt_dir,
        ckpt_every=max(args.steps // 3, 10), log_every=5), mesh, plan)
    hist = tr.run()
    for h in hist:
        print(f"step {h['step']:4d} loss {h['loss']:.4f} "
              f"gnorm {h['grad_norm']:.2f} {h['wall_s']:.1f}s/step")
    print(f"final loss {hist[-1]['loss']:.4f} "
          f"(start {hist[0]['loss']:.4f}); checkpoints in {args.ckpt_dir}")


if __name__ == "__main__":
    from repro.utils.cache import enable_persistent_cache

    enable_persistent_cache()
    main()
