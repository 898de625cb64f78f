"""Live migration at both scales the paper cares about:

(1) kernel scale — the paper's §6.3 case study: an iterative tiled matmul
    migrated across backends mid-run (H100 -> 9070 XT -> Tenstorrent
    becomes vectorized -> pallas -> interp);
(2) job scale — a training run checkpointed topology-neutrally and resumed
    on a *different* mesh layout (elastic restart).

    PYTHONPATH=src python examples/live_migration.py
"""
import time

import jax
import numpy as np

from repro.core import Engine, Snapshot, get_backend
from repro.core import kernels_suite as suite
from repro.device import configure_compile_cache


def kernel_migration():
    print("== kernel-scale migration (paper §6.3) ==")
    M, K, N, TK = 16, 64, 32, 8
    rng = np.random.default_rng(1)
    A = rng.normal(size=(M, K)).astype(np.float32)
    B = rng.normal(size=(K, N)).astype(np.float32)
    args = {"A": A.reshape(-1), "B": B.reshape(-1),
            "C": np.zeros(M * N, np.float32),
            "K": K, "N": N, "ktiles": K // TK}
    prog, oracle = suite.matmul_tiled(TK)

    chain = ["vectorized", "pallas", "interp"]
    eng = Engine(prog, get_backend(chain[0]), M, N, dict(args))
    eng.run(max_segments=5)
    for dst in chain[1:]:
        t0 = time.perf_counter()
        blob = eng.snapshot().to_bytes()
        eng = Engine.resume(prog, get_backend(dst),
                            Snapshot.from_bytes(blob))
        downtime = (time.perf_counter() - t0) * 1e3
        print(f"  migrated to {dst:11s} downtime={downtime:6.1f} ms "
              f"payload={len(blob)/1024:.1f} kB")
        eng.run(max_segments=4)
    eng.run()
    ok = np.allclose(eng.result("C"), oracle(dict(args))["C"], atol=1e-4)
    print(f"  final result correct across 2 migrations: {ok}")


def job_migration():
    print("\n== job-scale migration (topology-neutral checkpoint) ==")
    from repro import configs
    from repro.configs.base import ShapeCfg
    from repro.runtime.train_loop import Trainer

    cfg = configs.get_smoke_config("llama3.2-3b")
    shape = ShapeCfg("tiny", 32, 4, "train")
    n = len(jax.devices())
    mesh_a = jax.make_mesh((n, 1), ("data", "model"))
    mesh_b = jax.make_mesh((1, n), ("data", "model"))

    tr = Trainer(cfg, shape, mesh_a, seed=7)
    rep = tr.run(3)
    print(f"  mesh A {mesh_a.devices.shape}: losses {['%.3f' % l for l in rep.losses]}")
    t0 = time.perf_counter()
    tr.resize(mesh_b)   # live migration: snapshot -> re-fit specs -> reshard
    print(f"  resized to mesh B {mesh_b.devices.shape} in "
          f"{(time.perf_counter()-t0)*1e3:.0f} ms")
    rep2 = tr.run(3)
    print(f"  mesh B continues: losses {['%.3f' % l for l in rep2.losses]}")


if __name__ == "__main__":
    configure_compile_cache()
    kernel_migration()
    job_migration()
