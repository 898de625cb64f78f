"""End-to-end training driver: train an assigned-architecture LM with the
full runtime (sharded data pipeline, AdamW, checkpointing, fault
tolerance).

CPU-friendly default: the ~100M-class xlstm-125m at reduced width for a
few hundred steps.  Any registered arch works at its smoke scale:

    PYTHONPATH=src python examples/train_lm.py --arch llama3.2-3b \
        --steps 50 --checkpoint-every 20 --ckpt /tmp/ck
    # full-size configs (for real TPU meshes):
    PYTHONPATH=src python examples/train_lm.py --arch xlstm-125m --full
"""
import argparse

import jax

from repro import configs
from repro.configs.base import ParallelCfg, ShapeCfg
from repro.runtime.train_loop import Trainer
from repro.device import configure_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m",
                    choices=configs.list_archs())
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--full", action="store_true",
                    help="use the full-size config (needs a real mesh)")
    args = ap.parse_args()

    cfg = (configs.get_config(args.arch) if args.full
           else configs.get_smoke_config(args.arch))
    shape = ShapeCfg("train", args.seq, args.batch, "train")
    n_dev = len(jax.devices())
    mesh = jax.make_mesh((n_dev, 1), ("data", "model"))

    trainer = Trainer(cfg, shape, mesh, ckpt_dir=args.ckpt, seed=0,
                      pcfg=ParallelCfg(grad_accum=1, remat=True))
    resumed = trainer.maybe_restore()
    from repro.models.registry import param_count
    print(f"arch={cfg.name} params={param_count(cfg)/1e6:.1f}M  "
          + ("resumed at step %d" % trainer.step if resumed else "fresh"))

    done = 0
    while done < args.steps:
        chunk = min(20, args.steps - done)
        rep = trainer.run(chunk,
                          checkpoint_every=args.checkpoint_every)
        done += rep.steps_run
        print(f"step {trainer.step:5d}  loss {rep.losses[-1]:.4f}  "
              f"(stragglers={rep.straggler_events})")
    if args.ckpt:
        trainer.save_checkpoint()
        print("final checkpoint at step", trainer.step)


if __name__ == "__main__":
    configure_compile_cache()
    main()
