"""Self-healing fleet demo — the paper's live-migration claim as an
*operational* property, not an API call:

(1) a heterogeneous 3-worker fleet (interp + vectorized) serves a batch
    of launches; one worker is SIGKILLed mid-kernel by the fault
    injector, and the coordinator detects, requeues, and replays the
    lost launches on the survivors — bit-identical to a fault-free run;
(2) policy-driven migration: drain a worker for "maintenance" (its
    in-flight launches move live, via checkpoint/restore, across
    backends) and rebalance the survivors;
(3) the coordinator itself "crashes" and a fresh one recovers every
    unacked launch from the durable retry queue.

    PYTHONPATH=src python examples/fleet_demo.py
"""
import tempfile
from pathlib import Path

import numpy as np

from repro.core.fleet import MID_KERNEL, FleetCoordinator
from repro.core.kernels_suite import example_launch
from repro.device import configure_compile_cache


def main():
    prog, _oracle, grid, block, args, outs = example_launch("dyn_matmul")
    with tempfile.TemporaryDirectory() as td:
        qdir = Path(td) / "queue"

        # -- fault-free reference results --------------------------------
        with FleetCoordinator(backends=("interp",), queue_dir=None,
                              fault_plan=[]) as ref:
            ref.register(prog)
            t = ref.submit(prog.name, grid, block, args)
            ref.wait_all(timeout=120)
            reference = {n: t.result(n) for n in outs}
        print("reference computed on a 1-worker fleet")

        # -- (1) chaos: kill -9 a worker mid-kernel ----------------------
        plan = [{"point": MID_KERNEL, "worker": 0,
                 "kernel": prog.name, "nth": 1, "after_segments": 2}]
        with FleetCoordinator(backends=("interp", "vectorized", "interp"),
                              queue_dir=qdir, fault_plan=plan,
                              fault_seed=42) as fleet:
            fleet.register(prog)
            tickets = [fleet.submit(prog.name, grid, block, args)
                       for _ in range(6)]
            fleet.wait_all(timeout=180)
            st = fleet.fleet_stats()
            print(f"chaos run: workers_lost={st['workers_lost']} "
                  f"evacuated={st['evacuated']} retried={st['retried']} "
                  f"completed={st['completed']} "
                  f"recovery_ms_max={st.get('recovery_ms_max', 0):.0f}")
            assert all(np.array_equal(t.result(n), reference[n])
                       for t in tickets for n in outs)
            print("every result bit-identical to the reference")

            # -- (2) policy-driven migration -----------------------------
            more = [fleet.submit(prog.name, grid, block, args)
                    for _ in range(4)]
            fleet.pump()
            alive = [w.wid for w in fleet.workers.values() if w.alive]
            moved = fleet.drain(alive[0])
            print(f"drained worker {alive[0]}: {moved} launch(es) "
                  "migrated live (checkpoint/restore)")
            fleet.rebalance()
            fleet.wait_all(timeout=180)
            assert all(np.array_equal(t.result(n), reference[n])
                       for t in more for n in outs)
            print(f"after drain+rebalance: migrated="
                  f"{fleet.fleet_stats()['migrated']}, still bit-identical")

        # -- (3) coordinator crash + recovery ----------------------------
        fleet = FleetCoordinator(backends=("interp",), queue_dir=qdir,
                                 slice_segments=1, fault_plan=[])
        fleet.register(prog)
        victim = fleet.submit(prog.name, grid, block, args)
        fleet.pump()                     # dispatched, mid-flight
        fleet.shutdown()                 # "crash": queue dir survives
        print(f"coordinator died with {victim.launch_id} in flight")

        with FleetCoordinator(backends=("interp",), queue_dir=qdir,
                              fault_plan=[]) as fleet2:
            recovered = fleet2.recover()
            fleet2.register(prog)
            fleet2.wait_all(timeout=120)
            assert len(recovered) == 1 and recovered[0].finished
            assert all(np.array_equal(recovered[0].result(n), reference[n])
                       for n in outs)
            print(f"new coordinator replayed {recovered[0].launch_id} "
                  f"(attempt {recovered[0].attempts}) — bit-identical")
    print("fleet demo OK")


if __name__ == "__main__":
    configure_compile_cache()
    main()
