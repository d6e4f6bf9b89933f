"""Training launcher: ``python -m repro.launch.train --arch <id>``.

On this CPU container it trains the reduced (smoke) config of the chosen
architecture end-to-end with the full substrate: synthetic data, AdamW,
async atomic checkpoints, SIGTERM-preemption safety and resume. On real
hardware the same driver takes ``--full`` to use the assigned config with
the mesh/sharding rules exercised by the dry-run.
"""
from __future__ import annotations

import argparse
import signal


from repro.configs import ARCH_IDS, canonical, get_config, get_smoke_config
from repro.data.synthetic import DataConfig
from repro.launch import compile_cache
from repro.launch.specs import dryrun_config
from repro.optim.adamw import OptimizerConfig
from repro.train.step import default_optimizer_kind
from repro.train.trainer import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2_1_8b",
                    help=f"one of {ARCH_IDS}")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--full", action="store_true",
                    help="use the full assigned config (requires a pod)")
    args = ap.parse_args()

    compile_cache.enable()
    cfg = (dryrun_config(get_config(args.arch))
           if args.full else get_smoke_config(args.arch))
    print(f"arch={canonical(args.arch)} layers={cfg.n_layers} "
          f"d={cfg.d_model} optimizer={default_optimizer_kind(cfg)}")

    trainer = Trainer(
        cfg,
        OptimizerConfig(kind=default_optimizer_kind(cfg), lr=1e-3,
                        warmup_steps=10, total_steps=args.steps),
        DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq_len,
                   global_batch=args.global_batch),
        TrainerConfig(steps=args.steps, ckpt_every=max(args.steps // 4, 1),
                      ckpt_dir=args.ckpt_dir,
                      grad_compression=args.compress_grads))

    # preemption safety: SIGTERM checkpoints at the next step boundary
    signal.signal(signal.SIGTERM, lambda *_: trainer.request_stop())
    if trainer.maybe_resume():
        print(f"resumed at step {trainer.step}")

    out = trainer.run()
    print(f"loss {out['first_loss']:.4f} -> {out['final_loss']:.4f} in "
          f"{out['steps']} steps "
          f"({out['median_step_s']*1e3:.0f} ms/step median, "
          f"{out['straggler_steps']} stragglers)")


if __name__ == "__main__":
    main()
