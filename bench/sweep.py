#!/usr/bin/env python3
"""Find the highest rate a serving cell sustains, on the chip.

    python3 bench/sweep.py --workload <serve cell> --seconds 25 --rates 0.3,1,1.5

One process: for each offered rate, the cell's open loop for
``--seconds`` with the mix's own arrival trace scaled to that rate.  One
JSON line per rate: requests, failures, p50 and p90 latency, and whether
the backlog grew (mean latency of the window's last quarter of requests
over its first quarter).  The cell's rate is then fixed at four fifths of
the highest rate with p90 within the mix's latency limit and a backlog
that does not grow; the lowest rate gives the unloaded latency.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run as runmod


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=7)
    opts = ap.parse_args(argv)
    bench = json.loads((runmod.ROOT / "BENCHMARK.json").read_text())
    workload, _spec = runmod.cell_spec(bench, opts.workload)
    runmod.configure_caches(opts.workload)
    import harness
    import loadgen

    device = harness.require_accelerator(int(workload["chips"]))
    config = harness.load_json("configs", workload["config"])
    traffic = harness.load_json("traffic", workload["traffic"])
    kernel, ref = harness.kernel_modules(config)
    shape = kernel.shape(config)
    print(f"device: {device}", flush=True)
    for rate in (float(r) for r in opts.rates.split(",")):
        path = loadgen.path_for(config, dict(traffic, rate_per_s=rate),
                                kernel, opts.seed)
        n_events = path.plan(opts.seconds)
        inputs = ref.draw(opts.seed, shape, path.n_units, n_events)
        path.prepare(inputs)
        del inputs
        path.warm()
        recs, window = path.window(opts.seconds)
        path.release()
        lat = [r["latency_ms"] for r in recs if r["ok"]]
        quarter = max(1, len(recs) // 4)
        head = [r.get("latency_ms", np.inf) for r in recs[:quarter]]
        tail = [r.get("latency_ms", np.inf) for r in recs[-quarter:]]
        print(json.dumps({
            "rate_per_s": rate, "requests": len(recs),
            "failed": sum(not r["ok"] for r in recs),
            "p50_ms": loadgen.percentile(lat, 50),
            "p90_ms": loadgen.percentile(
                [r.get("latency_ms", np.inf) for r in recs], 90),
            "backlog_growth": float(np.mean(tail) / np.mean(head)),
            "inflight_max": max(path.inflight, default=0),
            "drain_s": window["t1"] - opts.seconds}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
