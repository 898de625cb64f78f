"""One function per paper table. Prints ``bench,key=value,...`` CSV rows.

``--json PATH`` additionally writes every row (all sections, including the
roofline rows) as one JSON document — the machine-readable artifact CI
uploads on every run.
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import List, Optional


def _emit(rows, sink: Optional[List[dict]] = None) -> None:
    for r in rows:
        if sink is not None:
            sink.append(dict(r))
        r = dict(r)
        bench = r.pop("bench")
        kv = ",".join(f"{k}={v}" for k, v in r.items())
        print(f"{bench},{kv}")


def main(argv: Optional[List[str]] = None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--json", metavar="PATH", default=None,
                        help="also write all rows as one JSON document")
    parser.add_argument("--cluster", action="store_true",
                        help="also run the multi-process cluster cache "
                             "fabric scenario (spawns fresh interpreters; "
                             "slow, so opt-in)")
    opts = parser.parse_args(argv)

    from repro.device import configure_compile_cache
    configure_compile_cache()

    from benchmarks import (bench_fleet, bench_kernels, bench_migration,
                            bench_overhead, bench_portability,
                            bench_serving, bench_streams,
                            bench_translation, roofline)

    sink: Optional[List[dict]] = [] if opts.json else None
    print("# hetGPU reproduction benchmarks (one per paper table)")
    print("# -- paper 6.1: portability matrix --")
    _emit(bench_portability.run(), sink)
    print("# -- paper 6.2: overhead vs native --")
    _emit(bench_overhead.run(), sink)
    print("# -- paper 6.2: translation/JIT cost --")
    _emit(bench_translation.run(), sink)
    print("# -- paper 4.2: pass pipeline (per-pass stats, interp steps) --")
    _emit(bench_translation.run_pass_pipeline(), sink)
    print("# -- paper 4.2: launch-time specialization (generic vs bound) --")
    _emit(bench_translation.run_specialization(), sink)
    print("# -- paper 4.2: persistent cache, cold vs warm start --")
    _emit(bench_translation.run_cold_warm(), sink)
    if opts.cluster:
        print("# -- paper 4.2: cluster cache fabric (translate once "
              "per fleet) --")
        _emit(bench_translation.run_cluster(), sink)
    print("# -- paper 6.3: live migration downtime --")
    _emit(bench_migration.run(), sink)
    print("# -- paper 4.3: stream scheduler (async overlap + overhead) --")
    _emit(bench_streams.run(), sink)
    print("# -- paper 4.3: multi-tenant serving tier (fair share, pool, "
          "shedding) --")
    _emit(bench_serving.run(), sink)
    print("# -- paper 6.3: self-healing fleet (kill -9 recovery latency) --")
    _emit(bench_fleet.run(), sink)
    print("# -- kernel structural benchmarks --")
    _emit(bench_kernels.run(), sink)
    print("# -- block-tiled vs scalar-per-thread codegen --")
    _emit(bench_kernels.run_het_block(), sink)
    print("# -- model zoo: attention/MoE/recurrent kernels (structural) --")
    _emit(bench_kernels.run_zoo(), sink)
    print("# -- roofline (measured het kernels + dry-run artifacts) --")
    _emit(roofline.run(), sink)

    if opts.json:
        with open(opts.json, "w") as fh:
            json.dump({"rows": sink}, fh, indent=1)
        print(f"# wrote {len(sink)} rows to {opts.json}", file=sys.stderr)


if __name__ == '__main__':
    main()
