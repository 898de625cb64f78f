"""Process-safety tests for the persistent cache tier.

``test_persistent_cache.py`` covers thread-safe writers inside one
process; a fleet (ISSUE 7) makes N *processes* share one
:class:`~repro.core.cache.DiskStore`, which is a different contract:

* atomic publishes must never yield torn/corrupt reads under concurrent
  re-publication of the same key;
* the corrupt-entry quarantine must never unlink a healthy entry that
  another process republished between the failed read and the unlink
  (the stat-guard in ``_read_envelope``);
* ``get_or_translate`` must be cross-process *single-flight*: N
  processes missing on the same key produce exactly one translation
  (the per-key ``flock`` in :meth:`DiskStore.lock`), everyone else
  restores the published entry;
* with a :class:`~repro.core.cache.SharedStore` fabric attached the
  same bar goes *fleet-wide*: one translation per key across fresh
  processes that share nothing but the fabric directory, survivors of
  a SIGKILL mid-publish see a clean miss (never corruption, never a
  wedged lock), and ``gc()`` sweeps the orphaned lock sidecars the
  protocol deliberately never unlinks on release.

The directed tests below run in tier-1; the N-process stress tests are
marked ``slow`` and run in CI's chaos job.  Subprocess workers are
spawned from a script written to ``tmp_path`` (spawn cannot import
pytest test modules).
"""
import json
import os
import pickle
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

from repro.core.cache import (DiskStore, SharedStore, TranslationCache,
                              register_reviver)

SRC = str(Path(__file__).resolve().parents[1] / "src")


# ---------------------------------------------------------------------------
# directed (tier-1) coverage of the new locking surface
# ---------------------------------------------------------------------------

def test_lock_is_exclusive_and_reentrant_across_keys(tmp_path):
    store = DiskStore(tmp_path, tag="t")
    order = []

    def hold(key, label, dwell):
        with store.lock(key) as locked:
            assert locked
            order.append(("enter", label))
            time.sleep(dwell)
            order.append(("exit", label))

    t1 = threading.Thread(target=hold, args=("k", "a", 0.15))
    t1.start()
    time.sleep(0.05)
    # a different key does not contend
    hold("other", "other", 0.0)
    # the same key must wait for the holder
    hold("k", "b", 0.0)
    t1.join(timeout=60)
    assert not t1.is_alive()
    assert order.index(("exit", "a")) < order.index(("enter", "b"))
    # lock files persist (never unlinked — see DiskStore.lock docstring)
    assert list(store.dir.glob("*.lock"))


def test_single_flight_translation_threads(tmp_path):
    """Two threads missing on one key: one translation, one restore."""
    register_reviver("mpstress", lambda p: p)
    store = DiskStore(tmp_path, tag="t")
    caches = [TranslationCache(store=store) for _ in range(2)]
    key = ("mpstress", "shared-key")
    started = threading.Barrier(2, timeout=60)
    calls = []

    def translate():
        calls.append(1)
        time.sleep(0.1)     # widen the race window
        return {"v": 42}, ("mpstress", {"v": 42})

    def run(cache):
        started.wait()
        assert cache.get_or_translate(key, translate) == {"v": 42}

    ts = [threading.Thread(target=run, args=(c,)) for c in caches]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=60)
        assert not t.is_alive()
    assert len(calls) == 1
    assert sum(c.translated for c in caches) == 1
    assert sum(c.restored for c in caches) == 1


def test_quarantine_spares_republished_entry(tmp_path):
    """A corrupt read must not unlink the path if a healthy entry was
    atomically republished there in the meantime."""
    store = DiskStore(tmp_path, tag="t")
    key = ("k",)
    path = store._path(key)
    path.write_bytes(b"garbage \x00 bytes")

    real_read = Path.read_bytes
    healthy = {"done": False}

    def read_then_republish(self):
        blob = real_read(self)
        if self == path and not healthy["done"]:
            healthy["done"] = True
            # another process wins the race: republish a good entry
            # after our read, before our quarantine unlink
            store.save(key, "kind", {"ok": True})
        return blob

    try:
        Path.read_bytes = read_then_republish
        assert store.load(key) is None      # the garbled read: a miss
    finally:
        Path.read_bytes = real_read
    assert store.corrupt == 1
    # the republished healthy entry survived the quarantine
    env = store.load(key)
    assert env is not None and env["payload"] == {"ok": True}


def test_quarantine_still_removes_stable_corruption(tmp_path):
    store = DiskStore(tmp_path, tag="t")
    key = ("k",)
    path = store._path(key)
    path.write_bytes(b"garbage")
    assert store.load(key) is None
    assert not path.exists()                # stable corruption: unlinked
    assert store.corrupt == 1


def test_single_flight_opt_out(tmp_path, monkeypatch):
    monkeypatch.setenv("HETGPU_CACHE_SINGLE_FLIGHT", "0")
    register_reviver("mpstress", lambda p: p)
    store = DiskStore(tmp_path, tag="t")
    cache = TranslationCache(store=store)
    v = cache.get_or_translate(("mpstress", "x"),
                               lambda: (1, ("mpstress", 1)))
    assert v == 1 and cache.translated == 1
    assert not list(store.dir.glob("*.lock"))   # lock never taken


def test_gc_sweeps_orphan_lock_sidecars(tmp_path):
    """Lock sidecars are never unlinked on release (that would split the
    lock), so they accumulate; ``gc()`` sweeps the ones whose entry is
    gone — under a non-blocking flock, so a sidecar someone holds *right
    now* is never touched."""
    store = DiskStore(tmp_path, tag="t")
    store.save(("live",), "kind", {"v": 1})
    with store.lock(("live",)):
        pass                            # sidecar with a matching .tce
    with store.lock(("orphan",)):
        pass                            # sidecar whose entry never landed
    assert len(list(store.dir.glob("*.lock"))) == 2
    store.gc()
    assert store.lock_sweeps == 1
    remaining = list(store.dir.glob("*.lock"))
    assert len(remaining) == 1
    assert remaining[0].with_suffix(".tce").exists()


def test_gc_never_sweeps_a_held_lock(tmp_path):
    store = DiskStore(tmp_path, tag="t")
    entered, release = threading.Event(), threading.Event()

    def hold():
        with store.lock(("held",)):
            entered.set()
            release.wait(5)

    t = threading.Thread(target=hold)
    t.start()
    assert entered.wait(5)
    store.gc()                          # sidecar is held: must survive
    assert store.lock_sweeps == 0
    assert list(store.dir.glob("*.lock"))
    release.set()
    t.join(timeout=60)
    assert not t.is_alive()
    store.gc()                          # now orphaned and free: swept
    assert store.lock_sweeps == 1
    assert not list(store.dir.glob("*.lock"))


def test_shared_tier_fetch_on_miss_and_replicate(tmp_path):
    """The fabric contract end-to-end in one process: node 1 translates
    and publishes; node 2 (fresh local store, same fabric) fetches on
    miss and replicates locally; node 3 then warm-starts from node 2's
    local store alone — the fabric is a fill path, not a dependency."""
    register_reviver("mpstress", lambda p: p)
    shared = SharedStore(tmp_path / "fabric", tag="t")
    key = ("mpstress", "k")

    def boom():
        raise AssertionError("fleet already translated this key")

    c1 = TranslationCache(store=DiskStore(tmp_path / "n1", tag="t"),
                          shared=shared)
    v = c1.get_or_translate(key, lambda: ({"v": 7}, ("mpstress", {"v": 7})))
    assert v == {"v": 7} and c1.translated == 1
    assert shared.publishes == 1 and c1.shared_publishes == 1

    store2 = DiskStore(tmp_path / "n2", tag="t")
    c2 = TranslationCache(store=store2, shared=shared)
    assert c2.get_or_translate(key, boom) == {"v": 7}
    assert c2.translated == 0 and c2.restored == 1
    assert c2.shared_fetches == 1 and c2.replicated == 1

    c3 = TranslationCache(store=store2)
    assert c3.get_or_translate(key, boom) == {"v": 7}
    assert c3.translated == 0 and c3.restored == 1


# ---------------------------------------------------------------------------
# N-process stress (slow; CI chaos job)
# ---------------------------------------------------------------------------

_WORKER = r"""
import json, os, sys, time
sys.path.insert(0, {src!r})
import numpy as np
from repro.core.cache import DiskStore, TranslationCache, register_reviver

root, out_path, nkeys, seed = sys.argv[1:5]
nkeys, seed = int(nkeys), int(seed)
register_reviver("mpstress", lambda p: p)
store = DiskStore(root, tag="stress")
cache = TranslationCache(store=store)

def make_translate(i):
    def translate():
        time.sleep(0.02)    # widen the cross-process race window
        payload = {{"key": i, "data": [i * 3, i * 3 + 1]}}
        return payload, ("mpstress", payload)
    return translate

rng = np.random.default_rng(seed)
order = rng.permutation(nkeys)
values = {{}}
for i in order:
    i = int(i)
    v = cache.get_or_translate(("mpstress", i), make_translate(i))
    values[i] = v
ok = all(values[i] == {{"key": i, "data": [i * 3, i * 3 + 1]}}
         for i in range(nkeys))
json.dump({{"pid": os.getpid(), "ok": ok,
           "translated": cache.translated, "restored": cache.restored,
           "hits": cache.hits, "corrupt": store.corrupt,
           "load_misses": store.load_misses}}, open(out_path, "w"))
"""


@pytest.mark.slow
def test_nproc_get_or_translate_single_flight(tmp_path):
    """6 processes x 8 keys against one store: every process sees every
    value intact, zero corrupt reads, and the fleet translates each key
    exactly once (single-flight) — the rest restore from disk."""
    nproc, nkeys = 6, 8
    script = tmp_path / "worker.py"
    script.write_text(_WORKER.format(src=SRC))
    store_dir = tmp_path / "store"
    procs, outs = [], []
    for i in range(nproc):
        out = tmp_path / f"out{i}.json"
        outs.append(out)
        procs.append(subprocess.Popen(
            [sys.executable, str(script), str(store_dir), str(out),
             str(nkeys), str(100 + i)],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE))
    for p in procs:
        _, err = p.communicate(timeout=120)
        assert p.returncode == 0, err.decode()
    results = [json.loads(o.read_text()) for o in outs]
    assert all(r["ok"] for r in results)
    assert all(r["corrupt"] == 0 for r in results)
    # the single-flight bar: one translation per key across the fleet
    assert sum(r["translated"] for r in results) == nkeys
    # everyone served every key: translated locally or restored from disk
    for r in results:
        assert r["translated"] + r["restored"] == nkeys
    # and the store holds exactly the distinct keys
    store = DiskStore(store_dir, tag="stress")
    assert store.entry_count() == nkeys


_REPUBLISHER = r"""
import sys, time
sys.path.insert(0, {src!r})
from repro.core.cache import DiskStore

root, rounds = sys.argv[1], int(sys.argv[2])
store = DiskStore(root, tag="stress")
for n in range(rounds):
    store.save(("hot",), "kind", {{"round": n, "blob": "x" * (n % 7) * 512}})
"""


@pytest.mark.slow
def test_nproc_republish_never_tears(tmp_path):
    """Writers hammer one key with differently-sized payloads while
    readers poll it: every read is either a miss (impossible here after
    the first publish) or a *complete* envelope — atomic publishes never
    yield torn bytes, and nothing healthy gets quarantined."""
    script = tmp_path / "writer.py"
    script.write_text(_REPUBLISHER.format(src=SRC))
    store_dir = tmp_path / "store"
    store = DiskStore(store_dir, tag="stress")
    store.save(("hot",), "kind", {"round": -1, "blob": ""})
    writers = [subprocess.Popen(
        [sys.executable, str(script), str(store_dir), "200"],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE)
        for _ in range(3)]
    reads = 0
    try:
        while any(p.poll() is None for p in writers):
            env = store.load(("hot",))
            assert env is not None, "torn or quarantined read"
            assert set(env["payload"]) == {"round", "blob"}
            reads += 1
    finally:
        for p in writers:
            _, err = p.communicate(timeout=60)
            assert p.returncode == 0, err.decode()
    assert store.corrupt == 0
    assert reads > 10   # the loop really overlapped the writers


_TORN_PUBLISHER = r"""
import os, sys, time
from pathlib import Path
sys.path.insert(0, {src!r})
from repro.core.cache import DiskStore, SharedStore, TranslationCache, \
    register_reviver

shared_dir, node_dir, marker = sys.argv[1:4]
register_reviver("mpstress", lambda p: p)
cache = TranslationCache(store=DiskStore(node_dir, tag="stress"),
                         shared=SharedStore(shared_dir, tag="stress"))

real_replace = os.replace
def torn_replace(srcp, dstp):
    # Freeze only the *shared-tier* publish, after the temp file is fully
    # written but before the atomic rename — the parent SIGKILLs us here,
    # while we also still hold the fleet-wide translation flock.
    if str(dstp).startswith(shared_dir):
        Path(marker).write_text("mid-publish")
        time.sleep(120)
    real_replace(srcp, dstp)
os.replace = torn_replace

cache.get_or_translate(("mpstress", "torn"),
                       lambda: ({{"v": 1}}, ("mpstress", {{"v": 1}})))
"""


@pytest.mark.slow
def test_sigkill_mid_publish_is_a_clean_miss(tmp_path):
    """SIGKILL a process between writing the shared-tier temp file and the
    atomic rename (while it holds the fleet-wide translation lock):
    readers must see a clean miss — never a torn envelope, never
    quarantine churn — the orphaned temp file is swept on the next store
    startup, and the flock dies with the process so a fresh node can
    immediately translate and publish the same key."""
    script = tmp_path / "torn.py"
    script.write_text(_TORN_PUBLISHER.format(src=SRC))
    shared_dir = tmp_path / "fabric"
    marker = tmp_path / "marker"
    proc = subprocess.Popen(
        [sys.executable, str(script), str(shared_dir),
         str(tmp_path / "n1"), str(marker)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE)
    deadline = time.monotonic() + 60
    while not marker.exists():
        assert proc.poll() is None, proc.communicate()[1].decode()
        assert time.monotonic() < deadline, "publisher never reached rename"
        time.sleep(0.01)
    proc.kill()                                     # SIGKILL, mid-publish
    proc.wait(timeout=30)

    fab = next(p for p in shared_dir.iterdir() if p.is_dir())
    assert not list(fab.glob("*.tce"))              # rename never happened
    torn = list(fab.glob("*.tmp"))
    assert torn                                     # the torn temp remains

    shared = SharedStore(shared_dir, tag="stress")
    # startup's temp sweep is age-gated (it must never race a *live*
    # writer), so the fresh orphan survives...
    assert list(shared.dir.glob("*.tmp"))
    assert shared.fetch(("mpstress", "torn")) is None   # clean miss
    assert shared.corrupt == 0
    # ...and once it is stale, the next startup sweeps it
    old = time.time() - 7200
    os.utime(torn[0], (old, old))
    SharedStore(shared_dir, tag="stress")
    assert not list(shared.dir.glob("*.tmp"))

    # the flock died with the process: a fresh node translates right away
    register_reviver("mpstress", lambda p: p)
    c2 = TranslationCache(store=DiskStore(tmp_path / "n2", tag="stress"),
                          shared=shared)
    v = c2.get_or_translate(("mpstress", "torn"),
                            lambda: ({"v": 2}, ("mpstress", {"v": 2})))
    assert v == {"v": 2} and c2.translated == 1
    assert shared.publishes == 1
    assert shared.fetch(("mpstress", "torn"))["payload"] == {"v": 2}


@pytest.mark.fleet
def test_fleet_prewarm_publishes_to_fabric(tmp_path):
    """`FleetCoordinator.prewarm()` translates every registered program
    once and publishes to the fabric; a fresh node sharing nothing but
    the fabric directory then warm-starts without translating."""
    from repro.core import FleetCoordinator, HetSession
    from repro.core import kernels_suite as suite

    prog = suite.vadd()[0]
    shared = tmp_path / "fabric"
    with FleetCoordinator(backends=("interp",), queue_dir=tmp_path / "q",
                          shared_dir=shared, fault_plan=[]) as fleet:
        fleet.register(prog)
        report = fleet.prewarm()
        assert report["interp"]["translated"] > 0

    node = HetSession("interp", shared=str(shared))
    rep = node.warmup([prog], grids=((2, 32),))
    assert rep["translated"] == 0
    assert rep["restored"] > 0
    assert rep["fetched"] == rep["restored"]


@pytest.mark.slow
def test_cluster_fabric_translate_once_fleet_wide():
    """ISSUE 9 acceptance: >=4 fresh processes over one shared fabric,
    exactly one translation per cache key fleet-wide, a late-joining
    fifth process warm-starts with ~0 trace *and* ~0 XLA compile (AOT
    executables came over the fabric), bit-identical to a cold
    single-process oracle, >=5x cheaper than translating."""
    from benchmarks.bench_translation import run_cluster
    row = run_cluster(nprocs=4)[0]
    assert row["fleet_translated"] == row["expected_translations"]
    assert row["bit_identical"]
    assert row["warm_translated"] == 0
    assert row["warm_fetched"] == row["expected_translations"]
    assert row["warm_aot_restored"] == row["expected_translations"]
    assert row["warm_trace_ms"] <= 5.0
    assert row["warm_compile_ms"] <= 5.0
    assert row["speedup"] >= 5.0
