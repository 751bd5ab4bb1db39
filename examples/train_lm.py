"""End-to-end training driver: train an LM with the full stack — Chronos
speculative input pipeline, StepGovernor, masked backup-shard aggregation,
async checkpointing, and restart-after-failure.

Presets:
  tiny  (default) — ~1M params, 60 steps: seconds on CPU; CI-friendly.
  100m            — ~100M params, a few hundred steps (use on a real machine:
                    PYTHONPATH=src python examples/train_lm.py --preset 100m
                    --steps 300).

Also demonstrates fault tolerance: pass --fail-at N to kill the run mid-way,
then re-run the same command — it restores the latest checkpoint and the
loss curve continues exactly where it left off.
"""
import argparse
import dataclasses

import jax

from repro import compile_cache
from repro.configs import get_config
from repro.configs.base import ArchConfig
from repro.train import Trainer, TrainerConfig


def preset_cfg(name: str) -> ArchConfig:
    base = get_config("mistral-nemo-12b")
    if name == "tiny":
        return base.reduced()
    if name == "100m":
        return dataclasses.replace(
            base, name="mistral-100m", n_layers=10, d_model=640, n_heads=10,
            n_kv_heads=5, head_dim=64, d_ff=2560, vocab_size=32000)
    raise SystemExit(f"unknown preset {name}")


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="tiny", choices=["tiny", "100m"])
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--fail-at", type=int, default=None)
    ap.add_argument("--no-speculation", action="store_true")
    args = ap.parse_args()

    cfg = preset_cfg(args.preset)
    print(f"arch={cfg.name}  params~{cfg.param_count()/1e6:.1f}M")

    tcfg = TrainerConfig(
        n_steps=args.steps, global_batch=args.batch, seq_len=args.seq,
        n_micro=2, lr=3e-3, ckpt_every=10, ckpt_dir=args.ckpt_dir,
        step_deadline=5.0, n_data_shards=4, data_cycle=8,
        speculative_input=not args.no_speculation, log_every=10)
    trainer = Trainer(cfg, tcfg, key=jax.random.PRNGKey(0))

    resumed = trainer.maybe_restore()
    if resumed:
        print(f"restored checkpoint at step {resumed}; resuming")

    hist = trainer.run(fail_at=args.fail_at)
    print(f"\nfinal loss: {hist[-1]['loss']:.4f} over {len(hist)} steps")
    if trainer.governor.last is not None:
        sol = trainer.governor.last
        print(f"governor: strategy={sol.strategy} r*={sol.r_opt} "
              f"(fit={trainer.governor.last_params})")


if __name__ == "__main__":
    main()
