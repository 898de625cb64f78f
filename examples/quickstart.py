"""Quickstart: one hetIR binary, three backends, live migration.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import Engine, HetSession, get_backend, migrate
from repro.core import kernels_suite as suite
from repro.device import configure_compile_cache


def main():
    # --- write once ------------------------------------------------------
    prog, oracle = suite.vadd()
    print("hetIR assembly for vadd:\n")
    print(prog.to_text())

    rng = np.random.default_rng(0)
    args = {"A": rng.normal(size=256).astype(np.float32),
            "B": rng.normal(size=256).astype(np.float32),
            "C": np.zeros(256, np.float32), "n": 256}

    # --- run anywhere ------------------------------------------------------
    print("\nrunning the same binary on every backend:")
    for backend in ("interp", "vectorized", "pallas"):
        eng = Engine(prog, get_backend(backend), 8, 32, dict(args))
        eng.run()
        ok = np.allclose(eng.result("C"), args["A"] + args["B"])
        print(f"  {backend:12s} correct={ok}")

    # --- migrate mid-kernel ------------------------------------------------
    # driver-style API: load -> Function, alloc -> DeviceBuffer (mutated
    # in place), launch_async -> LaunchRecord on a Stream.  See
    # examples/driver_api_demo.py and docs/API.md for the full surface.
    print("\nlive migration of a persistent kernel "
          "(vectorized -> pallas at iteration barrier):")
    prog2, oracle2 = suite.persistent_counter()
    init = rng.normal(size=64).astype(np.float32)
    src, dst = HetSession("vectorized"), HetSession("pallas")
    counter = src.load(prog2).function()
    dst.load(prog2)
    state = src.alloc(64).copy_from_host(init)
    rec = counter.launch_async(grid=2, block=32,
                               args={"State": state, "iters": 6})
    src.step(3)                             # drive 3 segments, pause mid-loop
    new = migrate(rec, src, dst, "persistent_counter")
    dst.synchronize()
    expect = oracle2({"State": init.copy(), "iters": 6})
    print("  migrated result correct:",
          np.allclose(new.buffer("State").copy_to_host(),
                      expect["State"], atol=1e-4))
    print("  migration stats:", dst.stats["last_migration"])


if __name__ == "__main__":
    configure_compile_cache()
    main()
