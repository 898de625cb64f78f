"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b \
        --shape train_4k --steps 1000 --ckpt /path/ck [--multi-pod]

On a CPU backend only smoke-scale runs are practical; on a TPU slice the
same entry point drives the production mesh (the mesh shape is the only
difference — the model/runtime code is mesh-agnostic).
"""
import argparse

import jax

from repro import configs
from repro.configs.base import SHAPES, ShapeCfg, default_parallel
from repro.launch.mesh import auto_mesh, make_production_mesh
from repro.runtime.train_loop import Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.list_archs())
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config + tiny mesh (CPU debugging)")
    args = ap.parse_args()

    if args.smoke:
        cfg = configs.get_smoke_config(args.arch)
        shape = ShapeCfg("smoke", 64, 4, "train")
        n = len(jax.devices())
        mesh = auto_mesh((n, 1), ("data", "model"))
    else:
        cfg = configs.get_config(args.arch)
        shape = SHAPES[args.shape]
        mesh = make_production_mesh(multi_pod=args.multi_pod)

    pcfg = default_parallel(cfg, shape)
    trainer = Trainer(cfg, shape, mesh, pcfg=pcfg, ckpt_dir=args.ckpt)
    trainer.maybe_restore()
    rep = trainer.run(args.steps,
                      checkpoint_every=args.checkpoint_every)
    print(f"ran {rep.steps_run} steps; final loss "
          f"{rep.losses[-1] if rep.losses else float('nan'):.4f}; "
          f"checkpoints at {rep.checkpoints}")


if __name__ == "__main__":
    main()
