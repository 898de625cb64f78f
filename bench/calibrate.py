#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/calibrate.py --workload <name> --seconds <s> --seeds 1,2,3

For each seed, in one process (set-up is paid once; later seeds revive
the translations from memory): a run of the cell as the benchmark makes
it, at the cell's own load for ``--seconds``, with the program's reading
and the control's reading over the same queries, with the verdict
``correct`` gives each.  One JSON line per seed.
The benchmark's own runs never compute the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import run as runmod


def main(argv=None) -> int:
    started = runmod.process_age_s()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    opts = ap.parse_args(argv)
    bench = json.loads((runmod.ROOT / "BENCHMARK.json").read_text())
    workload, spec = runmod.cell_spec(bench, opts.workload)
    runmod.configure_caches(opts.workload)
    import harness

    for seed in (int(s) for s in opts.seeds.split(",")):
        res = harness.run_cell(spec, workload, seed, opts.seconds, False,
                               started, control=True)
        started = 0.0
        gc.collect()    # the last run's sessions hold reference cycles
        limited = [name for name in res["compared"] if name in res["control"]]
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "attempted": res["attempted"],
                          **{name: res["compared"][name]["value"]
                             for name in limited},
                          **{f"control_{name}": res["control"][name]
                             for name in limited},
                          "control_correct": res["control"]["correct"],
                          "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
