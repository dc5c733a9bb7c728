"""Training launcher CLI.

Laptop-scale end-to-end (real data pipeline + trainer):
  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m \
      --reduced --steps 50

Production lowering check for one cell (no execution):
  PYTHONPATH=src python -m repro.launch.train --arch mistral-large-123b \
      --lower-only
"""
from __future__ import annotations

import argparse


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized reduced config")
    ap.add_argument("--lower-only", action="store_true",
                    help="lower+compile the production train cell and exit")
    ap.add_argument("--seq_len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--checkpoint_dir", default="/tmp/repro_train")
    args = ap.parse_args()

    if args.lower_only:
        # in this process: the dry-run driver sets up its forced host
        # devices before JAX first initializes a backend
        from repro.launch import dryrun
        dryrun.main(["--arch", args.arch, "--shape", "train_4k",
                     "--mesh", "single"])
        return

    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.models import build_model, param_count
    from repro.train.optimizer import adamw
    from repro.train.schedule import warmup_cosine
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    print(f"{cfg.name}: {param_count(model.init(0))/1e6:.1f}M params")

    def batch_fn(step):
        r = np.random.default_rng(step)
        b = {"tokens": jnp.asarray(
            r.integers(0, cfg.vocab_size, (args.batch, args.seq_len)),
            jnp.int32)}
        b["labels"] = jnp.asarray(
            r.integers(0, cfg.vocab_size, (args.batch, args.seq_len)),
            jnp.int32)
        if cfg.encoder_layers:
            b["frames"] = jnp.asarray(r.standard_normal(
                (args.batch, cfg.default_encoder_len, cfg.d_model)),
                jnp.float32)
        if cfg.num_vision_tokens:
            b["vision"] = jnp.asarray(r.standard_normal(
                (args.batch, cfg.num_vision_tokens, cfg.d_model)),
                jnp.float32)
        return b

    opt = adamw(warmup_cosine(3e-4, 10, args.steps))
    trainer = Trainer(model, opt, TrainerConfig(
        total_steps=args.steps, checkpoint_every=max(args.steps // 2, 1),
        checkpoint_dir=args.checkpoint_dir, log_every=10), batch_fn)
    out = trainer.run()
    for h in out["history"]:
        print(f"step {h['step']:>5} loss {h['loss']:.4f} "
              f"({h['sec_per_step']:.2f}s)")


if __name__ == "__main__":
    main()
