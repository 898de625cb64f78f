"""Driver-style API demo — the CI api-surface smoke.

Exercises the whole object model end to end: Module/Function loading with
typed parameter metadata, DeviceBuffer allocation + explicit transfers,
two genuinely asynchronous Streams whose segments interleave, Event-based
cross-stream ordering, and one live migration of an in-flight async
launch to another backend.  Exits non-zero on any mismatch.

    PYTHONPATH=src python examples/driver_api_demo.py
"""
import sys

import numpy as np

from repro.core import HetSession, migrate
from repro.core import kernels_suite as suite
from repro.device import configure_compile_cache


def main() -> int:
    failures = []

    def check(label, ok):
        print(f"  {'ok ' if ok else 'FAIL'} {label}")
        if not ok:
            failures.append(label)

    session = HetSession("vectorized")
    rng = np.random.default_rng(0)

    # --- modules and typed functions -----------------------------------
    print("module loading + typed metadata:")
    counter_prog, counter_oracle = suite.persistent_counter()
    mod = session.load([suite.vadd()[0], counter_prog])
    vadd = mod.function("vadd")
    counter = mod.function("persistent_counter")
    print(f"  {vadd}")
    check("param metadata", vadd.param("A").kind == "buffer"
          and vadd.param("n").dtype == "i32")

    # --- two streams + segment interleaving ----------------------------
    print("two async streams (segment-granularity interleaving):")
    st1, st2 = session.stream(), session.stream()
    init1 = rng.normal(size=64).astype(np.float32)
    init2 = rng.normal(size=64).astype(np.float32)
    s1 = session.alloc(64).copy_from_host(init1)
    s2 = session.alloc(64).copy_from_host(init2)
    session.sched_trace.clear()
    counter.launch_async(2, 32, {"State": s1, "iters": 6}, stream=st1)
    counter.launch_async(2, 32, {"State": s2, "iters": 6}, stream=st2)
    session.synchronize()
    ids = [t["stream"] for t in session.sched_trace]
    n_overlap = 2 * min(ids.count(st1.sid), ids.count(st2.sid))
    alternated = n_overlap >= 8 and all(
        a != b for a, b in zip(ids[:n_overlap], ids[:n_overlap][1:]))
    print(f"  trace (stream ids): {ids}")
    check("streams alternate per segment", alternated)
    check("stream-1 result", np.allclose(
        s1.copy_to_host(),
        counter_oracle({"State": init1.copy(), "iters": 6})["State"],
        atol=1e-4))

    # --- events: cross-stream ordering ----------------------------------
    print("event-ordered cross-stream dependency:")
    c = session.alloc(64)
    r1 = counter.launch_async(2, 32, {"State": s1, "iters": 4},
                              stream=st1)
    ev = st1.record_event()
    st2.wait_event(ev)                     # st2 waits for st1's counter
    vadd.launch_async(2, 32, {"A": s1, "B": s1, "C": c, "n": 64},
                      stream=st2)
    session.synchronize()
    check("event wait ordered the read", np.allclose(
        c.copy_to_host(), 2 * s1.copy_to_host(), atol=1e-5))
    check("event completed", ev.query() and r1.done())

    # --- live migration of an in-flight async launch --------------------
    print("async launch migrated mid-kernel (vectorized -> pallas):")
    dst = HetSession("pallas")
    dst.load(counter_prog)
    init3 = rng.normal(size=64).astype(np.float32)
    s3 = session.alloc(64).copy_from_host(init3)
    rec = counter.launch_async(2, 32, {"State": s3, "iters": 6},
                               stream=st1)
    session.step(3)                        # pause point: mid-kernel
    check("launch is in flight",
          rec.started and not rec.finished)
    new = migrate(rec, session, dst, "persistent_counter")
    dst.synchronize()
    expect = counter_oracle({"State": init3.copy(), "iters": 6})["State"]
    check("migrated result", np.allclose(
        new.buffer("State").copy_to_host(), expect, atol=1e-4))
    check("buffer identity stable across the hop",
          new.buffer("State").uid == s3.uid)
    print(f"  migration stats: {dst.stats['last_migration']}")

    print(f"\n{'ALL OK' if not failures else 'FAILED: ' + str(failures)}")
    return 1 if failures else 0


if __name__ == "__main__":
    configure_compile_cache()
    sys.exit(main())
