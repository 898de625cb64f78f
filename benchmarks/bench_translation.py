"""Paper §6.2 — translation/JIT cost per backend (first launch vs cached),
plus the cluster-lifetime cold-start vs warm-start scenario.

The paper reports 10-200 ms per kernel for PTX/SPIR-V/Metalium paths; here
translation = staging hetIR segments through the pass pipeline plus
jax.export tracing (vectorized / pallas) or plan staging (interp).
Each (kernel, backend) pair gets a fresh shared
:class:`~repro.core.cache.TranslationCache` and launches twice: the first
launch pays translation (all misses), the relaunch must run entirely from
cache (hit_rate > 0).  Rows also carry the pass-pipeline op reduction so
the optimize-then-translate pipeline is visible in one table.

``run_cold_warm`` measures what persistence buys (paper §4.2: JIT cost is
per *cluster lifetime*, not per process): a **cold** start translates the
suite into a fresh :class:`~repro.core.cache.DiskStore`; a **warm** start
rebuilds the in-memory cache from scratch against the now-populated store,
so every segment is a disk restore.  "Translation time" is the cache's own
accounting — ``translate_ms`` (wall-time inside translation factories:
staging + jax.export tracing) for cold, ``translate_ms + restore_ms``
(any fresh translation plus deserialize/revive time) for warm — and the
table also reports end-to-end launch wall time for both phases.  Warm
timing is further split into what kind of work the restart actually paid:
``warm_trace_ms`` (Python re-trace — must be ~0, that is what PR 3's
StableHLO persistence bought) vs ``warm_compile_ms`` (XLA compile paid
during restores — must be ~0 *only* because store format v2 persists the
AOT-compiled executable; conflating the two made the AOT win invisible).

``run_cluster`` is the fleet version of the same claim: N fresh
*processes* over one :class:`~repro.core.cache.SharedStore` fabric,
exactly one translation per (kernel, backend) fleet-wide, everyone else
fetch-and-warm-starts with ~0 compile, bit-identical to a cold
single-process oracle.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from repro.core import DiskStore, Engine, OPT_MAX, SharedStore, \
    TranslationCache, get_backend
from repro.core import kernels_suite as suite


def _case(name, rng):
    if name == "vadd":
        return ({"A": rng.normal(size=128).astype(np.float32),
                 "B": rng.normal(size=128).astype(np.float32),
                 "C": np.zeros(128, np.float32), "n": 128}, 4, 32)
    if name == "reduction":
        return ({"A": rng.normal(size=128).astype(np.float32),
                 "Out": np.zeros(1, np.float32), "n": 128,
                 "log2t": 5}, 4, 32)
    if name == "matmul_tiled":
        return ({"A": np.ones(8 * 16, np.float32),
                 "B": np.ones(16 * 16, np.float32),
                 "C": np.zeros(8 * 16, np.float32),
                 "K": 16, "N": 16, "ktiles": 2}, 8, 16)
    if name == "poly_eval":
        return ({"X": rng.normal(size=128).astype(np.float32),
                 "Coef": rng.normal(size=7).astype(np.float32),
                 "Out": np.zeros(128, np.float32), "n": 128}, 4, 32)
    if name == "swizzle_copy":
        return ({"A": rng.normal(size=128).astype(np.float32),
                 "Out": np.zeros(128, np.float32)}, 4, 32)
    if name == "tap_filter":
        return ({"A": rng.normal(size=64).astype(np.float32),
                 "W": rng.normal(size=4).astype(np.float32),
                 "Tmp": np.zeros(64, np.float32),
                 "Out": np.zeros(64, np.float32)}, 2, 32)
    if name == "dyn_matmul":
        return ({"A": rng.normal(size=4 * 32).astype(np.float32),
                 "B": rng.normal(size=32 * 16).astype(np.float32),
                 "C": np.zeros(4 * 16, np.float32),
                 "K": 32, "N": 16, "ktiles": 4, "tk": 8}, 4, 16)
    if name == "dyn_fir":
        return ({"A": rng.normal(size=64).astype(np.float32),
                 "W": rng.normal(size=4).astype(np.float32),
                 "Out": np.zeros(64, np.float32), "taps": 4}, 2, 32)
    return ({"Count": np.zeros(1, np.float32)}, 2, 32)


def run() -> list:
    rows = []
    rng = np.random.default_rng(1)
    for name in ("vadd", "reduction", "matmul_tiled", "montecarlo_pi"):
        prog_fn = suite.SUITE[name]
        for backend in ("interp", "vectorized", "pallas"):
            prog, _ = prog_fn()
            args, grid, block = _case(name, rng)
            cache = TranslationCache()
            be = get_backend(backend, cache=cache)

            t0 = time.perf_counter()
            eng = Engine(prog, be, grid, block, dict(args))
            eng.run()
            first_ms = (time.perf_counter() - t0) * 1e3
            misses_after_first = cache.stats()["misses"]

            t0 = time.perf_counter()
            eng2 = Engine(prog, be, grid, block, dict(args))
            eng2.run()
            cached_ms = (time.perf_counter() - t0) * 1e3

            st = cache.stats()
            opt = eng.opt_stats
            rows.append({
                "bench": "translation", "kernel": name, "backend": backend,
                "first_ms": round(first_ms, 1),
                "cached_ms": round(cached_ms, 1),
                "cache_entries": be.translation_cache_size(),
                "hits": st["hits"], "misses": st["misses"],
                "hit_rate": round(st["hit_rate"], 3),
                "relaunch_misses": st["misses"] - misses_after_first,
                "ops_before": opt.ops_before, "ops_after": opt.ops_after,
            })
    return rows


# ---------------------------------------------------------------------------
# pass-pipeline table: per-pass stats + interp executed-step deltas
# ---------------------------------------------------------------------------

PIPELINE_KERNELS = ("poly_eval", "tap_filter", "matmul_tiled",
                    "swizzle_copy", "montecarlo_pi")


def run_pass_pipeline(kernels=PIPELINE_KERNELS) -> list:
    """What the phase-2 pipeline buys, per kernel: static op delta,
    executed-op-schedule delta, the interp backend's *true* dynamically
    counted per-thread step delta (O0 vs OPT_MAX), and which passes fired.
    The CI smoke asserts ``ops_removed > 0`` in aggregate and a strict
    interp step reduction on the loop-heavy kernels."""
    rows = []
    rng = np.random.default_rng(1)
    for name in kernels:
        args, grid, block = _case(name, rng)
        steps = {}
        sched = {}
        stats = None
        for level in (0, OPT_MAX):
            prog, _ = suite.SUITE[name]()
            be = get_backend("interp", cache=TranslationCache())
            eng = Engine(prog, be, grid, block, dict(args),
                         opt_level=level)
            eng.run()
            steps[level] = be.steps_executed
            sched[level] = eng.executed_ops
            if level:
                stats = eng.opt_stats
        fired = {k: v for k, v in stats.per_pass.items() if v}
        rows.append({
            "bench": "pass_pipeline", "kernel": name, "level": OPT_MAX,
            "ops_before": stats.ops_before, "ops_after": stats.ops_after,
            "ops_removed": stats.ops_removed,
            "sched_o0": sched[0], "sched_omax": sched[OPT_MAX],
            "interp_steps_o0": steps[0],
            "interp_steps_omax": steps[OPT_MAX],
            "interp_step_cut": round(
                1 - steps[OPT_MAX] / max(steps[0], 1), 3),
            "opt_ms": round(sum(stats.per_pass_ms.values()), 2),
            "passes": "+".join(sorted(fired)),
        })
    return rows


# ---------------------------------------------------------------------------
# launch-time specialization table: generic vs specialized executed work
# ---------------------------------------------------------------------------

SPECIALIZATION_KERNELS = ("dyn_matmul", "dyn_fir")


def run_specialization(kernels=SPECIALIZATION_KERNELS) -> list:
    """What binding the launch scalars buys on the dynamic-trip kernels:
    the same program is launched generic (``specialize=False``) and
    specialized (``specialize=True``) on the interp backend, and the table
    reports the *executed* deltas — the per-thread op schedule
    (``Engine.executed_ops``, whose reduction is the dynamic
    ``ops_removed``) and the interp backend's true divergence-aware step
    count — plus how many scalars were bound and whether the outputs were
    bit-identical (they must be; the CI smoke asserts it)."""
    rows = []
    rng = np.random.default_rng(1)
    for name in kernels:
        prog, _ = suite.SUITE[name]()
        args, grid, block = _case(name, rng)
        outs = prog.buffers()
        steps, sched, results = {}, {}, {}
        bound = 0
        for spec in (False, True):
            be = get_backend("interp", cache=TranslationCache())
            eng = Engine(prog, be, grid, block, dict(args),
                         opt_level=OPT_MAX, specialize=spec)
            eng.run()
            steps[spec] = be.steps_executed
            sched[spec] = eng.executed_ops
            results[spec] = [np.asarray(eng.result(p.name)) for p in outs]
            if spec:
                bound = eng.opt_stats.per_pass.get(
                    "bind_launch_scalars", 0)
        rows.append({
            "bench": "specialization", "kernel": name,
            "scalars_bound": bound,
            "sched_generic": sched[False], "sched_spec": sched[True],
            "ops_removed": sched[False] - sched[True],
            "interp_steps_generic": steps[False],
            "interp_steps_spec": steps[True],
            "interp_step_cut": round(
                1 - steps[True] / max(steps[False], 1), 3),
            "bit_identical": all(
                np.array_equal(a, b)
                for a, b in zip(results[False], results[True])),
        })
    return rows


DEFAULT_COLD_WARM_KERNELS = ("vadd", "reduction", "matmul_tiled",
                             "montecarlo_pi")


def _launch_suite(cache: TranslationCache, backend: str,
                  kernels) -> float:
    """Launch every kernel once against ``cache``; returns wall ms."""
    rng = np.random.default_rng(1)
    be = get_backend(backend, cache=cache)
    t0 = time.perf_counter()
    for name in kernels:
        prog, _ = suite.SUITE[name]()
        args, grid, block = _case(name, rng)
        eng = Engine(prog, be, grid, block, dict(args))
        eng.run()
    return (time.perf_counter() - t0) * 1e3


def run_cold_warm(kernels=DEFAULT_COLD_WARM_KERNELS,
                  backends=("interp", "vectorized", "pallas"),
                  store_dir=None) -> list:
    """Cold-start vs warm-start translation cost over a shared DiskStore.

    Cold: empty store, fresh cache — every segment is translated and
    persisted.  Warm: a *new* cache instance (simulating a process restart
    or a migration-destination node) against the same store — every
    segment must be a disk restore, never a re-translation.
    """
    tmp = store_dir or tempfile.mkdtemp(prefix="hetgpu-bench-store-")
    rows = []
    total_cold = total_warm = 0.0
    try:
        for backend in backends:
            cold = TranslationCache(store=DiskStore(tmp))
            cold_wall = _launch_suite(cold, backend, kernels)
            cst = cold.stats()

            warm = TranslationCache(store=DiskStore(tmp))
            warm_wall = _launch_suite(warm, backend, kernels)
            wst = warm.stats()

            cold_translation = cst["translate_ms"]
            warm_translation = wst["translate_ms"] + wst["restore_ms"]
            total_cold += cold_translation
            total_warm += warm_translation
            rows.append({
                "bench": "translation_cold_warm", "backend": backend,
                "kernels": len(kernels),
                "cold_translation_ms": round(cold_translation, 1),
                "warm_translation_ms": round(warm_translation, 1),
                "cold_wall_ms": round(cold_wall, 1),
                "warm_wall_ms": round(warm_wall, 1),
                "cold_translated": cst["translated"],
                "warm_translated": wst["translated"],
                "warm_restored": wst["restored"],
                # the split: what kind of work each phase actually paid.
                # trace = Python trace + jax.export; compile = XLA compile
                # (translate-side for cold, restore-side recompiles for
                # warm — ~0 when the persisted AOT executable revives).
                "cold_trace_ms": round(cst["trace_ms"], 1),
                "cold_compile_ms": round(cst["compile_ms"], 1),
                "warm_trace_ms": round(wst["trace_ms"], 1),
                "warm_compile_ms": round(
                    wst["compile_ms"] + wst["restore_compile_ms"], 1),
                "warm_aot_restored": wst["aot_restored"],
                "warm_aot_fallbacks": wst["aot_fallback_restores"],
                "speedup": round(
                    cold_translation / max(warm_translation, 1e-6), 1),
            })
        rows.append({
            "bench": "translation_cold_warm", "backend": "ALL",
            "kernels": len(kernels),
            "cold_translation_ms": round(total_cold, 1),
            "warm_translation_ms": round(total_warm, 1),
            "speedup": round(total_cold / max(total_warm, 1e-6), 1),
        })
    finally:
        if store_dir is None:
            shutil.rmtree(tmp, ignore_errors=True)
    return rows


# ---------------------------------------------------------------------------
# cluster scenario: N fresh processes, one translation fleet-wide
# ---------------------------------------------------------------------------

DEFAULT_CLUSTER_KERNELS = ("vadd", "reduction")

# Runs in a *fresh interpreter* (spawned, never forked — jax is
# fork-unsafe): one cluster node coming up cold against the shared
# fabric.  argv: src_path backend shared_dir node_dir kernels_csv.
# Prints one JSON object on the last stdout line.
_CLUSTER_NODE = r"""
import hashlib, json, sys, time
import numpy as np

sys.path.insert(0, sys.argv[1])
from repro.core import DiskStore, Engine, SharedStore, TranslationCache, \
    get_backend
from repro.core import kernels_suite as suite

backend, shared_dir, node_dir = sys.argv[2], sys.argv[3], sys.argv[4]
kernels = sys.argv[5].split(",")

cache = TranslationCache(store=DiskStore(node_dir),
                         shared=SharedStore(shared_dir))
be = get_backend(backend, cache=cache)
digests = {}
t0 = time.perf_counter()
for name in kernels:
    prog, _oracle, grid, block, args, outs = suite.example_launch(
        name, rng=np.random.default_rng(0))
    eng = Engine(prog, be, grid, block,
                 {k: np.array(v, copy=True) for k, v in args.items()})
    eng.run()
    h = hashlib.sha256()
    for o in outs:
        h.update(np.ascontiguousarray(np.asarray(eng.result(o))).tobytes())
    digests[name] = h.hexdigest()
wall_ms = (time.perf_counter() - t0) * 1e3
st = cache.stats()
print(json.dumps({
    "digests": digests, "wall_ms": wall_ms,
    "translated": st["translated"], "restored": st["restored"],
    "shared_fetches": st["shared_fetches"],
    "shared_publishes": st["shared_publishes"],
    "aot_restored": st["aot_restored"],
    "aot_fallbacks": st["aot_fallback_restores"],
    "translate_ms": st["translate_ms"], "restore_ms": st["restore_ms"],
    "trace_ms": st["trace_ms"], "compile_ms": st["compile_ms"],
    "restore_compile_ms": st["restore_compile_ms"],
}))
"""


def _oracle_digests(backend: str, kernels) -> tuple:
    """Cold single-process oracle: fresh memory-only cache.  Returns
    (digests, cache stats) — the bit-identity reference and the cold
    translation cost the fleet amortizes."""
    import hashlib as _hashlib
    cache = TranslationCache()
    be = get_backend(backend, cache=cache)
    digests = {}
    for name in kernels:
        prog, _oracle, grid, block, args, outs = suite.example_launch(
            name, rng=np.random.default_rng(0))
        eng = Engine(prog, be, grid, block,
                     {k: np.array(v, copy=True) for k, v in args.items()})
        eng.run()
        h = _hashlib.sha256()
        for o in outs:
            h.update(np.ascontiguousarray(
                np.asarray(eng.result(o))).tobytes())
        digests[name] = h.hexdigest()
    return digests, cache.stats()


def _spawn_nodes(n: int, backend: str, shared: Path, root: Path,
                 kernels, tag: str) -> list:
    """Launch ``n`` fresh cluster-node interpreters concurrently and
    return their parsed JSON reports (raises on any node failure)."""
    src = str(Path(suite.__file__).resolve().parents[2])
    script = root / "cluster_node.py"
    script.write_text(_CLUSTER_NODE)
    procs = []
    # the nodes stay on the CPU on purpose: this scenario counts
    # translations and AOT restores across processes (a chip belongs to
    # one process), so its timings are CPU timings, not chip numbers
    for i in range(n):
        node_dir = root / f"{tag}-node{i}"
        procs.append(subprocess.Popen(
            [sys.executable, str(script), src, backend, str(shared),
             str(node_dir), ",".join(kernels)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            env={**os.environ, "JAX_PLATFORMS": "cpu"}))
    reports = []
    for p in procs:
        out, err = p.communicate(timeout=600)
        if p.returncode != 0:
            raise RuntimeError(f"cluster node failed:\n{err.decode()}")
        reports.append(json.loads(out.decode().strip().splitlines()[-1]))
    return reports


def run_cluster(kernels=DEFAULT_CLUSTER_KERNELS, backends=("pallas",),
                nprocs: int = 4, shared_dir=None) -> list:
    """The fabric's headline claim, measured: ``nprocs`` fresh processes
    race up cold against one :class:`SharedStore` (fleet-wide
    single-flight dedupes every translation), then one more fresh
    late-joiner warm-starts purely from the fabric.

    Per backend the row reports: ``fleet_translated`` (must equal the
    cold oracle's ``expected_translations`` — exactly one translation per
    cache key fleet-wide), ``bit_identical`` (every node's outputs match
    the cold single-process oracle), and the late-joiner's split warm
    cost — ``warm_trace_ms`` / ``warm_compile_ms`` both ~0 (it fetched
    AOT executables) with ``speedup`` = oracle cold translation over its
    warm translation (restore) cost."""
    root = Path(shared_dir or tempfile.mkdtemp(prefix="hetgpu-cluster-"))
    rows = []
    total_cold = total_warm = 0.0
    try:
        for backend in backends:
            shared = root / f"fabric-{backend}"
            oracle, cst = _oracle_digests(backend, kernels)
            race = _spawn_nodes(nprocs, backend, shared, root, kernels,
                                tag=f"race-{backend}")
            warm = _spawn_nodes(1, backend, shared, root, kernels,
                                tag=f"warm-{backend}")[0]
            fleet_translated = sum(r["translated"] for r in race)
            bit_identical = all(r["digests"] == oracle
                                for r in race + [warm])
            cold_translation = cst["translate_ms"]
            warm_translation = warm["translate_ms"] + warm["restore_ms"]
            total_cold += cold_translation
            total_warm += warm_translation
            rows.append({
                "bench": "translation_cluster", "backend": backend,
                "kernels": len(kernels), "procs": nprocs + 1,
                "expected_translations": cst["translated"],
                "fleet_translated": fleet_translated,
                "race_warm_procs": sum(1 for r in race
                                       if r["translated"] == 0),
                "bit_identical": bit_identical,
                "cold_translation_ms": round(cold_translation, 1),
                "warm_translation_ms": round(warm_translation, 1),
                "warm_translated": warm["translated"],
                "warm_restored": warm["restored"],
                "warm_fetched": warm["shared_fetches"],
                "warm_aot_restored": warm["aot_restored"],
                "warm_trace_ms": round(warm["trace_ms"], 1),
                "warm_compile_ms": round(
                    warm["compile_ms"] + warm["restore_compile_ms"], 1),
                "speedup": round(
                    cold_translation / max(warm_translation, 1e-6), 1),
            })
        rows.append({
            "bench": "translation_cluster", "backend": "ALL",
            "kernels": len(kernels), "procs": nprocs + 1,
            "cold_translation_ms": round(total_cold, 1),
            "warm_translation_ms": round(total_warm, 1),
            "speedup": round(total_cold / max(total_warm, 1e-6), 1),
        })
    finally:
        if shared_dir is None:
            shutil.rmtree(root, ignore_errors=True)
    return rows
