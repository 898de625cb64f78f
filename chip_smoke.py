#!/usr/bin/env python3
"""Run the hetIR runtime's main path on the TPU, end to end.

    python3 chip_smoke.py            # one chip: the three phases below
    python3 chip_smoke.py --chips 4  # the fleet phase only, four chips

One chip:

1. **conformance** — the 17 suite kernels and the 4 zoo kernels at their
   example launches, through ``HetSession`` on the ``vectorized`` backend
   (XLA) and the compiled ``pallas`` backend (Mosaic), checked against the
   ``interp`` backend (the semantics oracle, on the host) bit for bit and
   against each kernel's NumPy oracle;
2. **serving at width** — a ``HetSession("pallas")`` behind
   ``ServingFrontEnd`` answers 8 ``attn_decode`` requests from 2 tenants
   at Llama-3.2-3B decode width (head dim 128, 24 query heads, 2,048
   keys in 16 tiles of 128), each checked against the oracle;
3. **live migration at width** — one launch paused mid-softmax (after
   half the tiles) on ``interp`` and one on ``vectorized``, each moved to
   the ``pallas`` session with ``migrate()`` and finished there, checked
   against the oracle and an unmigrated pallas run.

Four chips: a ``FleetCoordinator`` with four ``pallas`` worker processes,
one chip each, serves the width launches while one worker is drained
mid-kernel (cross-chip live migration) and one is killed mid-kernel by
the ``FaultInjector``; every result must equal the same launch run by a
one-worker fleet, bit for bit, with no launch lost.

The script exits non-zero without a result line when JAX finds no TPU or
any phase fails.  The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.  Timings on
earlier lines are from this one run, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# attn_decode at Llama-3.2-3B decode width (src/repro/configs/llama3_2_3b.py)
HEADS, HEAD_DIM, TILE, NTILES = 24, 128, 128, 16
N_REQUESTS = 8

# Ops the chip cannot make bit-exact, with the kernels they reach and the
# largest distance, in units in the last place, the smoke admits there.
ULP_BOUNDS: dict = {}


def log(*parts) -> None:
    print(*parts, flush=True)


class SmokeFailure(RuntimeError):
    pass


def ulp_distance(a, b) -> int:
    """Largest distance in float32 units in the last place (0 if equal
    bit for bit; NaNs count as equal only to NaNs)."""
    import numpy as np

    a = np.asarray(a, np.float32).reshape(-1)
    b = np.asarray(b, np.float32).reshape(-1)
    if np.array_equal(a.view(np.int32), b.view(np.int32)):
        return 0
    if not np.array_equal(np.isnan(a), np.isnan(b)):
        return 1 << 31

    def ordered(x):
        i = x.view(np.int32).astype(np.int64)
        return np.where(i < 0, -(i & 0x7FFFFFFF), i)

    keep = ~np.isnan(a)
    return int(np.max(np.abs(ordered(a[keep]) - ordered(b[keep])),
                      initial=0))


def check_equal(label: str, got, want, kernel: str, failures: list) -> int:
    """Bit-identity, or the stated ULP bound for ``kernel``."""
    import numpy as np

    got, want = np.asarray(got), np.asarray(want)
    if got.dtype.kind != "f":
        if not np.array_equal(got, want):
            failures.append(f"{label}: integer outputs differ")
        return 0
    d = ulp_distance(got, want)
    bound = ULP_BOUNDS.get(kernel, 0)
    if d > bound:
        failures.append(f"{label}: {d} ULP from the reference "
                        f"(bound {bound})")
    return d


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def run_session(sess, prog, grid, block, args, outs):
    import numpy as np

    fn = sess.load(prog).function()
    bound, bufs = {}, {}
    for k, v in args.items():
        if isinstance(v, np.ndarray):
            bufs[k] = sess.alloc(v.shape, v.dtype).copy_from_host(v)
            bound[k] = bufs[k]
        else:
            bound[k] = v
    fn.launch(grid, block, bound)
    return {o: bufs[o].copy_to_host() for o in outs}


def phase_conformance(seed: int, sessions, failures: list) -> None:
    import numpy as np
    from repro.core import kernels_suite as ks
    from repro.core.backends.pallas_mem import PallasRefusal
    import repro.zoo  # noqa: F401  (registers the zoo kernels)

    zoo = list(ks.registered_examples("zoo"))
    for name in list(ks.SUITE) + zoo:
        prog, oracle, grid, block, args, outs = ks.example_launch(
            name, np.random.default_rng(seed))
        ref = run_session(sessions["interp"], prog, grid, block, args, outs)
        expect = oracle({**args, "_num_blocks": grid, "_block_size": block})
        for be in ("vectorized", "pallas"):
            t0 = time.perf_counter()
            try:
                got = run_session(sessions[be], prog, grid, block, args,
                                  outs)
            except PallasRefusal as e:
                if be != "pallas" or name in zoo:
                    raise
                log(f"conformance {name:18s} {be:10s} REFUSED "
                    f"({e.reason})")
                continue
            dist = 0
            for o in outs:
                dist = max(dist, check_equal(f"{name}/{be}/{o} vs interp",
                                             got[o], ref[o], name,
                                             failures))
                if name in zoo:
                    check_equal(f"{name}/{be}/{o} vs oracle", got[o],
                                expect[o], name, failures)
                elif not np.allclose(got[o], expect[o], rtol=1e-5,
                                     atol=1e-5):
                    failures.append(f"{name}/{be}/{o}: off its oracle")
            log(f"conformance {name:18s} {be:10s} max_ulp_vs_interp={dist} "
                f"wall_s={time.perf_counter() - t0:.3f}")


def probe_division(seed: int) -> None:
    """How far this chip's own float32 division lands from IEEE: XLA's
    divide and a Mosaic kernel's, over 2**20 normal-range operands.  The
    runtime does not rely on it (``semantics._fdiv`` corrects the
    quotient); the numbers say what that correction is for."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl

    rng = np.random.default_rng(seed + 3)
    shape = (1024, 1024)
    x = (rng.standard_normal(shape) * np.exp(rng.uniform(-18, 18, shape))
         ).astype(np.float32)
    y = (rng.standard_normal(shape) * np.exp(rng.uniform(-18, 18, shape))
         ).astype(np.float32)
    want = x / y

    def kernel(x_ref, y_ref, o_ref):
        o_ref[...] = x_ref[...] / y_ref[...]

    mosaic = pl.pallas_call(
        kernel, grid=(8,),
        in_specs=[pl.BlockSpec((128, 1024), lambda i: (i, 0))] * 2,
        out_specs=pl.BlockSpec((128, 1024), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(shape, jnp.float32))
    for name, fn in (("xla", lambda a, b: a / b), ("mosaic", mosaic)):
        got = np.asarray(jax.jit(fn)(x, y))
        off = np.count_nonzero(got.view(np.int32) != want.view(np.int32))
        log(f"division on this chip ({name}): max_ulp_vs_ieee="
            f"{ulp_distance(got, want)} inexact={off}/{x.size}")


def width_args(rng):
    import numpy as np

    S = NTILES * TILE
    return {
        "Q": rng.standard_normal(HEADS * HEAD_DIM, dtype=np.float32),
        "K": rng.standard_normal(HEADS * S * HEAD_DIM, dtype=np.float32),
        "V": rng.standard_normal(HEADS * S * HEAD_DIM, dtype=np.float32),
        "O": np.zeros(HEADS * HEAD_DIM, np.float32),
        "ntiles": NTILES,
        "scale": np.float32(1.0 / np.sqrt(HEAD_DIM)),
    }


def phase_serving(seed: int, sess, prog, oracle, failures: list):
    import numpy as np
    from repro.core import ServingFrontEnd

    rng = np.random.default_rng(seed + 1)
    base = width_args(rng)
    fn = sess.load(prog).function()
    kb = sess.alloc(base["K"].shape).copy_from_host(base["K"])
    vb = sess.alloc(base["V"].shape).copy_from_host(base["V"])
    log(f"serving: attn_decode D={HEAD_DIM} T={TILE} grid={HEADS} "
        f"ntiles={NTILES} ({NTILES * TILE} keys); K/V f32 "
        f"{(base['K'].nbytes + base['V'].nbytes) / 1e6:.1f} MB; one K/V "
        f"row set per query head (the zoo kernel has no grouped KV heads: "
        f"Llama-3.2-3B's 8 KV heads are expanded to its 24 query heads)")
    front = ServingFrontEnd(sess)
    front.tenant("tenant-a", weight=1.0)
    front.tenant("tenant-b", weight=2.0)

    def request(tenant):
        q = rng.standard_normal(HEADS * HEAD_DIM, dtype=np.float32)
        qb = sess.alloc(q.shape).copy_from_host(q)
        ob = sess.alloc(q.shape)
        ticket = front.submit(tenant, fn, HEADS, TILE, {
            "Q": qb, "K": kb, "V": vb, "O": ob, "ntiles": NTILES,
            "scale": base["scale"]})
        return ticket, q, ob

    before = sess.cache_stats()
    t0 = time.perf_counter()
    warm = request("tenant-a")
    front.drain()
    warm[2].copy_to_host()
    setup_s = time.perf_counter() - t0
    after = sess.cache_stats()
    reqs = [request("tenant-a" if i % 2 == 0 else "tenant-b")
            for i in range(N_REQUESTS)]
    t0 = time.perf_counter()
    front.drain()
    outs = [ob.copy_to_host() for _, _, ob in reqs]
    steady_s = (time.perf_counter() - t0) / N_REQUESTS
    for i, ((ticket, q, _), out) in enumerate(zip(reqs, outs)):
        if not ticket.done():
            failures.append(f"serving request {i} did not complete")
        want = oracle({**base, "Q": q})["O"]
        d = check_equal(f"serving request {i}", out, want, "attn_decode",
                        failures)
        log(f"serving request {i} tenant={ticket.tenant} "
            f"ulp_vs_oracle={d}")
    segs = 2 + 2 * NTILES + 1
    log(f"serving set-up (first launch: translation + XLA/Mosaic compile "
        f"+ one run) s={setup_s:.3f} translate_ms="
        f"{after['translate_ms'] - before['translate_ms']:.1f} "
        f"compile_ms={after.get('compile_ms', 0.0) - before.get('compile_ms', 0.0):.1f}")
    log(f"serving steady wall per launch (outputs on the host) "
        f"s={steady_s:.4f} segments_per_launch={segs}")
    log(f"serving front end stats: {json.dumps(front.stats(), default=str)}")
    return base


def phase_migration(seed: int, sess, prog, oracle, failures: list) -> None:
    import numpy as np
    from repro.core import HetSession, migrate

    rng = np.random.default_rng(seed + 2)
    args = width_args(rng)
    want = oracle(args)["O"]
    unmigrated = run_session(sess, prog, HEADS, TILE, args, ("O",))["O"]
    check_equal("unmigrated pallas run", unmigrated, want, "attn_decode",
                failures)
    half = 2 + 2 * (NTILES // 2)     # q-staged, pre-loop, 8 tiles x 2
    for src_name in ("interp", "vectorized"):
        src = HetSession(src_name)
        fn = src.load(prog).function()
        bound = {k: (src.alloc(v.shape, v.dtype).copy_from_host(v)
                     if isinstance(v, np.ndarray) else v)
                 for k, v in args.items()}
        rec = fn.launch_async(HEADS, TILE, bound)
        t0 = time.perf_counter()
        if rec.advance(max_segments=half):
            failures.append(f"{src_name}: launch finished before migration")
            continue
        t1 = time.perf_counter()
        new = migrate(rec, src, sess, "attn_decode")
        new.wait()
        t2 = time.perf_counter()
        out = new.buffer("O").copy_to_host()
        d1 = check_equal(f"migrated {src_name}->pallas vs oracle", out,
                         want, "attn_decode", failures)
        if not np.array_equal(out.view(np.int32),
                              unmigrated.view(np.int32)):
            failures.append(f"migrated {src_name}->pallas differs from "
                            "the unmigrated pallas run")
        lm = sess.stats.get("last_migration", {})
        log(f"migration {src_name}->pallas after {half} of "
            f"{2 + 2 * NTILES + 1} segments: src_s={t1 - t0:.3f} "
            f"migrate+finish_s={t2 - t1:.3f} payload_bytes="
            f"{lm.get('payload_bytes')} ulp_vs_oracle={d1} "
            f"bit_identical_to_unmigrated="
            f"{np.array_equal(out.view(np.int32), unmigrated.view(np.int32))}")


def one_chip(seed: int) -> dict:
    import jax
    from repro.device import configure_compile_cache, device_info

    cache_dir = configure_compile_cache()
    info = device_info()
    if info["platform"] != "tpu":
        raise SmokeFailure(f"JAX finds no TPU (platform "
                           f"{info['platform']!r})")
    from repro.core import HetSession
    from repro.zoo.kernels import attn_decode

    log(f"device: {info} (one chip run of chip_smoke.py, not a benchmark)")
    log(f"jax {jax.__version__}; compile cache: {cache_dir}")
    failures: list = []
    probe_division(seed)
    sessions = {b: HetSession(b) for b in ("interp", "vectorized",
                                             "pallas")}
    t0 = time.perf_counter()
    phase_conformance(seed, sessions, failures)
    log(f"phase conformance s={time.perf_counter() - t0:.1f}")

    prog, oracle = attn_decode(D=HEAD_DIM, T=TILE)
    sess = HetSession("pallas")
    t0 = time.perf_counter()
    phase_serving(seed, sess, prog, oracle, failures)
    log(f"phase serving s={time.perf_counter() - t0:.1f}")
    t0 = time.perf_counter()
    phase_migration(seed, sess, prog, oracle, failures)
    log(f"phase migration s={time.perf_counter() - t0:.1f}")

    # every backend shares the process-wide translation cache
    st = sess.cache_stats()
    log(f"block_stats conformance={json.dumps(sessions['pallas'].block_stats())} "
        f"width={json.dumps(sess.block_stats())}")
    log(f"translation cache: translated={st['translated']} "
        f"trace_ms={st['trace_ms']:.1f} compile_ms={st['compile_ms']:.1f} "
        f"export_fallbacks={st['export_fallbacks']} "
        f"aot_export_fallbacks={st['aot_export_fallbacks']}")
    if st["export_fallbacks"]:
        failures.append(f"export_fallbacks={st['export_fallbacks']} "
                        f"({st['last_export_error']})")
    if failures:
        raise SmokeFailure("; ".join(failures))
    return info


# ---------------------------------------------------------------------------
# four chips: the fleet
# ---------------------------------------------------------------------------

def fleet_run(backends, launches, prog, fabric, fault_plan,
              drain_worker=None):
    """Serve ``launches`` on a fleet whose workers share the translation
    fabric ``fabric``; returns (results, fleet counters, per-worker
    devices)."""
    from repro.core.fleet import FleetCoordinator

    with FleetCoordinator(backends=backends, fault_plan=fault_plan,
                          shared_dir=fabric, slice_segments=4,
                          rpc_timeout=900.0) as fleet:
        devices = {wid: fleet._rpc(w, "ping", {"device": True})[1]["device"]
                   for wid, w in fleet.workers.items()}
        fleet.register(prog)
        tickets = [fleet.submit("attn_decode", HEADS, TILE, a,
                                outputs=("O",)) for a in launches]
        if drain_worker is not None:
            # two pump rounds put every worker mid-kernel, then the
            # drained worker's launches move to the others live
            fleet.pump()
            fleet.pump()
            moved = fleet.drain(drain_worker)
            log(f"fleet: drained worker {drain_worker} mid-kernel, "
                f"{moved} launch(es) migrated live")
        fleet.wait_all(timeout=1800.0)
        results = [t.result("O") if t.finished else None for t in tickets]
        return results, dict(fleet.counters), devices


def four_chips(seed: int) -> dict:
    """The fleet phase: the coordinator (this process) never touches a
    chip; each worker process holds its own."""
    import numpy as np
    from repro.core.fleet import MID_KERNEL, tpu_chip_count
    from repro.device import configure_compile_cache
    from repro.zoo.kernels import attn_decode

    # exported to the workers, which share what the first one compiles
    log(f"compile cache: {configure_compile_cache()}")
    chips = tpu_chip_count()
    if chips < 4:
        raise SmokeFailure(f"--chips 4 needs four TPU chips, found {chips}")
    prog, oracle = attn_decode(D=HEAD_DIM, T=TILE)
    rng = np.random.default_rng(seed + 1)
    base = width_args(rng)
    launches = [{**base, "Q": rng.standard_normal(HEADS * HEAD_DIM,
                                                  dtype=np.float32)}
                for _ in range(N_REQUESTS)]
    failures: list = []

    kill = [{"point": MID_KERNEL, "worker": 3, "kernel": "attn_decode",
             "nth": 1, "after_segments": 6}]
    # one translation fabric: each kernel is translated once, fleet-wide
    fabric = tempfile.mkdtemp(prefix="hetgpu-fabric-")
    t0 = time.perf_counter()
    try:
        got, counters, devs = fleet_run(("pallas",) * 4, launches, prog,
                                        fabric, fault_plan=kill,
                                        drain_worker=1)
        log(f"fleet: four pallas workers on {json.dumps(devs)}, "
            f"s={time.perf_counter() - t0:.1f}")
        log(f"fleet counters: {json.dumps(counters)}")
        # the reference runs after the fleet has let go of every chip
        t0 = time.perf_counter()
        ref, _, dev1 = fleet_run(("pallas",), launches, prog, fabric,
                                 fault_plan=[])
    finally:
        shutil.rmtree(fabric, ignore_errors=True)
    log(f"fleet reference: one pallas worker on {dev1}, "
        f"s={time.perf_counter() - t0:.1f}")
    for i, (a, r) in enumerate(zip(launches, ref)):
        check_equal(f"one-worker launch {i} vs oracle", r,
                    oracle(a)["O"], "attn_decode", failures)
    lost = sum(r is None for r in got)
    same = sum(r is not None and np.array_equal(r.view(np.int32),
                                                e.view(np.int32))
               for r, e in zip(got, ref))
    log(f"fleet: launches={len(got)} lost={lost} "
        f"bit_identical_to_one_worker={same}/{len(got)} "
        f"workers_lost={counters['workers_lost']} "
        f"migrated={counters['migrated']} retried={counters['retried']}")
    if lost or same != len(got):
        failures.append(f"fleet: {lost} lost, {len(got) - same} differ "
                        "from the one-worker run")
    if counters["workers_lost"] < 1 or counters["migrated"] < 1:
        failures.append("fleet: the kill or the drain did not happen")
    kinds = {(d["platform"], d["kind"]) for d in devs.values()}
    if len(devs) != 4 or len(kinds) != 1 or \
            any(d["platform"] != "tpu" or d["count"] != 1
                for d in devs.values()):
        failures.append(f"fleet: workers are not one TPU chip each: {devs}")
    if failures:
        raise SmokeFailure("; ".join(failures))
    d = next(iter(devs.values()))
    return {"platform": d["platform"], "kind": d["kind"],
            "count": sum(x["count"] for x in devs.values())}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the fleet phase, one chip per worker")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every input (default 0)")
    opts = ap.parse_args()
    src = ROOT / "src"
    if not (src / "repro" / "core").is_dir():
        print(f"chip_smoke: no repro sources under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    try:
        info = four_chips(opts.seed) if opts.chips == 4 \
            else one_chip(opts.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
