#!/usr/bin/env python3
"""Run one cell of the benchmark once, on the chip this process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  The run sets
up (inputs drawn on the device from the seed, the program's translations
revived from its store or translated, one warm-up event of each kind),
measures for ``--seconds``, compares every output of the window with the
plain reference, and prints one JSON object as the last line of standard
output: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics read from
a profiler trace of the window) and ``device``, then ``breakdown`` in a
traced run and the numbers compared with their limits.  Without a TPU, or
with fewer chips than the cell asks for, it exits 3 and prints no result.

Caches live in the checkout: JAX's compilation cache in
``.jax_cache/jax`` unless ``JAX_COMPILATION_CACHE_DIR`` is set, and the
runtime's translation store (``HETGPU_CACHE_DIR``) in
``.jax_cache/hetgpu-<hash of src/repro>/<workload>``, so a tree whose
sources differ never revives another tree's translations.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def process_age_s() -> float:
    """Seconds since this process started (Linux), so set-up counts the
    interpreter's start and every import."""
    try:
        fields = Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()
        started = int(fields[19]) / os.sysconf("SC_CLK_TCK")
        return time.clock_gettime(time.CLOCK_BOOTTIME) - started
    except (OSError, ValueError, IndexError):
        return time.process_time()


def source_hash(src: Path) -> str:
    h = hashlib.sha256()
    for p in sorted(src.rglob("*.py")):
        h.update(str(p.relative_to(src)).encode())
        h.update(p.read_bytes())
    return h.hexdigest()[:16]


def cell_spec(bench: dict, name: str):
    """The workload entry and the metric lists it reports."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{', '.join(sorted(cells))}")
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    layer = {m["name"]: m for m in bench["per_layer"]}
    mine = [m for m, e in e2e.items()
            if "workloads" not in e or name in e["workloads"]]
    mine_layer = [m for m, e in layer.items()
                  if (name in e["workloads"] if "workloads" in e
                      else e["moves"] in mine)]
    return cells[name], {"end_to_end": e2e, "per_layer": layer,
                         "cell_end_to_end": mine,
                         "cell_per_layer": mine_layer}


def configure_caches(workload: str) -> None:
    """Fix the caches' directories in the checkout and put the program and
    the benchmark on the import path."""
    src = ROOT / "src"
    cache = ROOT / ".jax_cache"
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(cache / "jax"))
    os.environ["HETGPU_CACHE_DIR"] = str(
        cache / f"hetgpu-{source_hash(src / 'repro')}" / workload)
    for p in (str(src), str(BENCH)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from repro.device import configure_compile_cache

    configure_compile_cache()
    print(f"caches: jax={os.environ['JAX_COMPILATION_CACHE_DIR']} "
          f"translations={os.environ['HETGPU_CACHE_DIR']}", flush=True)


def main(argv=None) -> int:
    started = process_age_s()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    opts = ap.parse_args(argv)

    src = ROOT / "src"
    if not (src / "repro" / "core").is_dir():
        print(f"bench: no program sources under {src}", file=sys.stderr)
        return 2
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    workload, spec = cell_spec(bench, opts.workload)

    configure_caches(opts.workload)
    import harness

    try:
        result = harness.run_cell(spec, workload, opts.seed, opts.seconds,
                                  bool(opts.trace), started)
    except harness.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
