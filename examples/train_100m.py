"""Train a ~100M-parameter model (whisper-base scale) on the synthetic
pipeline with checkpoint/restart enabled.

CPU-friendly default runs a reduced config for a quick loss-curve check;
pass --full --steps 300 for the real ~110M whisper-base (slow on CPU, the
same command scales on a mesh).

  PYTHONPATH=src python examples/train_100m.py --steps 40
"""
import argparse

import jax

from repro import configs
from repro.data.pipeline import DataConfig
from repro.fault.runner import ResilientTrainer
from repro.models import costs
from repro.optim import adamw
from repro.train.step import init_state, make_train_step
from repro.compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=40)
    ap.add_argument("--full", action="store_true",
                    help="full whisper-base (~110M params; slow on CPU)")
    ap.add_argument("--ckpt-dir", default="/tmp/train100m_ckpt")
    args = ap.parse_args()

    cfg = (configs.get("whisper-base") if args.full
           else configs.get_smoke("whisper-base"))
    n = costs.param_breakdown(cfg)["total"]
    print(f"training {cfg.name}: {n / 1e6:.1f}M params")

    dcfg = DataConfig(seed=0, batch=4, seq_len=256 if args.full else 64)
    step = jax.jit(make_train_step(cfg, adamw.AdamWConfig(lr=3e-3)),
                   donate_argnums=(0,))
    trainer = ResilientTrainer(
        cfg, dcfg, step,
        lambda: init_state(cfg, jax.random.PRNGKey(0))[0],
        args.ckpt_dir, ckpt_every=20)
    report = trainer.run(args.steps)
    print(f"loss {report.losses[0]:.4f} -> {report.losses[-1]:.4f} "
          f"over {report.final_step} steps "
          f"(restarts={report.restarts}, "
          f"stragglers={len(report.straggler_steps)})")
    assert report.losses[-1] < report.losses[0], "loss did not improve"
    print("checkpoints in", args.ckpt_dir)


if __name__ == "__main__":
    enable_compile_cache()
    main()
