"""Persistent translation-cache tests (paper §4.2, cluster-lifetime JIT).

The on-disk tier must make translations survive the in-memory cache:
rebuilding a session against the same store turns every relaunch into a
disk restore (never a re-translation) with bit-identical results; corrupt
or version-skewed entry files degrade to misses, never exceptions;
concurrent writers are safe; eviction is cost-aware (GDSF), so expensive
translations outlive cheap ones; `HetSession.warmup` ahead-of-time
translates a kernel set; and `migrate` preloads the destination's cache.
"""
import pickle
import threading

import numpy as np
import pytest

from repro.core import (DiskStore, Engine, HetSession, OPT_MAX,
                        TranslationCache, get_backend, migrate)
from repro.core import kernels_suite as suite
from repro.core import passes

RNG = np.random.default_rng(7)


def _vadd_session(backend, store):
    s = HetSession(backend, cache=TranslationCache(store=store))
    prog, _ = suite.vadd()
    s.load_kernel(prog)
    return s


def _vadd_args(n=128):
    return {"A": RNG.normal(size=n).astype(np.float32),
            "B": RNG.normal(size=n).astype(np.float32),
            "C": np.zeros(n, np.float32), "n": n}


# ---------------------------------------------------------------------------
# cross-instance reuse (the acceptance scenario)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["interp", "vectorized"])
def test_cross_instance_restore_is_not_a_retranslation(backend, tmp_path):
    """Translate a suite kernel, drop the in-memory cache entirely (new
    TranslationCache instance), rebuild the session against the same
    on-disk store: the relaunch must be served by disk restores — zero
    fresh translations — and produce bit-identical output."""
    args = _vadd_args()
    s1 = _vadd_session(backend, DiskStore(tmp_path))
    s1.launch("vadd", grid=4, block=32, args=dict(args))
    out1 = s1._streams[0][-1].engine.result("C")
    st1 = s1.cache_stats()
    assert st1["translated"] >= 1 and st1["restored"] == 0

    # fresh memory tier, same persistent store — a "process restart"
    s2 = _vadd_session(backend, DiskStore(tmp_path))
    s2.launch("vadd", grid=4, block=32, args=dict(args))
    out2 = s2._streams[0][-1].engine.result("C")
    st2 = s2.cache_stats()
    assert st2["translated"] == 0, "relaunch must not re-translate"
    assert st2["restored"] == st1["translated"]
    assert np.array_equal(np.asarray(out1), np.asarray(out2)), \
        "disk-restored translation changed semantics"


def test_restore_across_cache_instances_direct(tmp_path):
    """DiskStore round-trip at the TranslationCache level."""
    store = DiskStore(tmp_path)
    c1 = TranslationCache(store=store)
    c1.get_or_translate(("interp", "fp", 0, 0),
                        lambda: ([1, 2, 3], ("interp-plan", [1, 2, 3])))
    c2 = TranslationCache(store=DiskStore(tmp_path))
    calls = []
    val = c2.get_or_translate(("interp", "fp", 0, 0),
                              lambda: (calls.append(1) or [], None))
    assert val == [1, 2, 3] and not calls
    assert c2.stats()["restored"] == 1 and c2.stats()["translated"] == 0


# ---------------------------------------------------------------------------
# corruption tolerance / invalidation
# ---------------------------------------------------------------------------


def test_truncated_and_corrupt_entries_load_as_misses(tmp_path):
    store = DiskStore(tmp_path)
    s1 = _vadd_session("interp", store)
    s1.launch("vadd", grid=4, block=32, args=_vadd_args())
    files = list(store.dir.glob("*.tce"))
    assert files
    files[0].write_bytes(files[0].read_bytes()[: len(files[0]
                                                    .read_bytes()) // 2])
    for f in files[1:]:
        f.write_bytes(b"\x00garbage\xff")

    s2 = _vadd_session("interp", DiskStore(tmp_path))
    s2.launch("vadd", grid=4, block=32, args=_vadd_args())  # must not raise
    st = s2.cache_stats()
    assert st["translated"] >= 1  # re-translated past the bad entries
    assert st["store"]["corrupt"] >= 1
    # corrupt files were quarantined and fresh entries re-persisted
    s3 = _vadd_session("interp", DiskStore(tmp_path))
    s3.launch("vadd", grid=4, block=32, args=_vadd_args())
    assert s3.cache_stats()["translated"] == 0


def test_version_mismatch_invalidates_entry(tmp_path):
    store = DiskStore(tmp_path)
    key = ("interp", "fp", 0, 0)
    store.save(key, "interp-plan", [1, 2, 3])
    path = store._path(key)
    env = pickle.loads(path.read_bytes())
    env["version"] = 999  # a future format
    path.write_bytes(pickle.dumps(env))
    assert store.load(key) is None
    assert store.stats()["corrupt"] >= 1


def test_runtime_tag_isolates_stores(tmp_path):
    """Entries written under one runtime tag are invisible to another
    (jax upgrade / platform change invalidation)."""
    old = DiskStore(tmp_path, tag="v0-jax0.0.0-cpu")
    old.save(("interp", "fp", 0, 0), "interp-plan", [1])
    new = DiskStore(tmp_path)  # current runtime tag
    assert new.load(("interp", "fp", 0, 0)) is None
    assert new.entry_count() == 0 and old.entry_count() == 1


def test_key_collision_guard(tmp_path):
    """An envelope whose stored key differs from the requested key (hash
    collision, or a tampered file) is a miss."""
    store = DiskStore(tmp_path)
    key_a, key_b = ("interp", "a", 0, 0), ("interp", "b", 0, 0)
    store.save(key_a, "interp-plan", [1])
    # graft A's envelope onto B's path
    store._path(key_b).write_bytes(store._path(key_a).read_bytes())
    assert store.load(key_b) is None


@pytest.mark.fast
def test_session_accepts_same_path_store(tmp_path):
    """cache already bound to a store + store= at the same path is fine;
    a genuinely different path is refused loudly."""
    cache = TranslationCache(store=DiskStore(tmp_path / "a"))
    HetSession("interp", cache=cache, store=DiskStore(tmp_path / "a"))
    with pytest.raises(ValueError):
        HetSession("interp", cache=cache, store=DiskStore(tmp_path / "b"))


@pytest.mark.fast
def test_failed_store_write_degrades_to_memory_only(tmp_path, monkeypatch):
    """A full/read-only disk must not fail the launch: the translation
    stays usable in memory and persist_errors counts the loss."""
    store = DiskStore(tmp_path)

    def boom(*a, **k):
        raise OSError(28, "No space left on device")

    monkeypatch.setattr(store, "save", boom)
    cache = TranslationCache(store=store)
    val = cache.get_or_translate(
        ("interp", "fp", 0, 0), lambda: ("LIVE", ("interp-plan", "LIVE")))
    assert val == "LIVE"
    assert cache.get(("interp", "fp", 0, 0)) == "LIVE"
    assert cache.stats()["persist_errors"] == 1


# ---------------------------------------------------------------------------
# pass-pipeline fingerprint invalidation
# ---------------------------------------------------------------------------


def test_store_tag_carries_pipeline_fingerprint(tmp_path):
    store = DiskStore(tmp_path)
    assert f"-p{passes.pipeline_fingerprint()}-" in store.tag


def test_pass_set_change_invalidates_persisted_entries(tmp_path,
                                                       monkeypatch):
    """A store populated under one pass pipeline must be invisible to a
    runtime with a different pass set — otherwise a stale artifact,
    optimized by passes that no longer exist (or have been fixed), would
    be restored silently."""
    old_fp = passes.pipeline_fingerprint()
    s1 = _vadd_session("interp", DiskStore(tmp_path))
    s1.launch("vadd", grid=4, block=32, args=_vadd_args())
    assert DiskStore(tmp_path).entry_count() >= 1

    # simulate a pass-semantics change (any pipeline edit has this effect)
    monkeypatch.setattr(passes, "_PASS_SCHEMA_VERSION", 10 ** 6)
    assert passes.pipeline_fingerprint() != old_fp
    fresh = DiskStore(tmp_path)
    assert fresh.tag != s1.cache.store.tag
    assert fresh.entry_count() == 0, \
        "entries persisted by the old pipeline leaked into the new tag"
    s2 = _vadd_session("interp", fresh)
    s2.launch("vadd", grid=4, block=32, args=_vadd_args())
    st = s2.cache_stats()
    assert st["translated"] >= 1 and st["restored"] == 0, \
        "stale optimized artifact restored across a pass-set change"


# ---------------------------------------------------------------------------
# cooperative checkpoint + migrate() on OPT_MAX-unrolled programs
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("src,dst", [("vectorized", "interp"),
                                     ("interp", "vectorized")])
def test_migrate_unrolled_omax_bit_identical(src, dst, tmp_path):
    """Mid-kernel checkpoint of an OPT_MAX program whose inner tile loop
    was *unrolled*: pause at a barrier inside the k-loop, migrate to the
    other backend (store-warmed destination), and finish **bit-identical**
    to an uninterrupted run on the destination backend.  This is the
    paper's migration story composed with the phase-2 optimizer: node
    indices are positions in the *optimized* segmented program, so the
    snapshot only restores correctly if the destination re-derives the
    exact same unrolled body."""
    M, K, N, TK = 4, 32, 16, 8
    args = {"A": RNG.normal(size=M * K).astype(np.float32),
            "B": RNG.normal(size=K * N).astype(np.float32),
            "C": np.zeros(M * N, np.float32),
            "K": K, "N": N, "ktiles": K // TK}
    prog, _ = suite.matmul_tiled(TK)

    ref = Engine(prog, get_backend(dst, cache=TranslationCache()),
                 M, N, dict(args), opt_level=OPT_MAX)
    assert ref.run()

    s_src = HetSession(src, opt_level=OPT_MAX,
                       cache=TranslationCache(store=DiskStore(tmp_path)))
    s_dst = HetSession(dst, opt_level=OPT_MAX,
                       cache=TranslationCache(store=DiskStore(tmp_path)))
    s_src.load_kernel(prog)
    s_dst.load_kernel(prog)
    rec = s_src.launch("matmul_tiled", grid=M, block=N, args=dict(args),
                       blocking=False)
    # the inner loop really did unroll, and we really do pause mid-kernel
    assert rec.engine.opt_stats.per_pass.get("unroll_loops", 0) >= 1
    assert not rec.engine.run(max_segments=5)
    new = migrate(rec, s_src, s_dst, "matmul_tiled")
    s_dst.run_to_completion(new)
    assert new.finished
    np.testing.assert_array_equal(
        np.asarray(new.engine.result("C")), np.asarray(ref.result("C")),
        err_msg=f"{src}->{dst} migrated OPT_MAX run diverged")


# ---------------------------------------------------------------------------
# concurrency
# ---------------------------------------------------------------------------


def test_concurrent_writers_threads(tmp_path):
    """Many threads translating into one store: atomic temp-file+rename
    writes mean no torn entries and no exceptions; a fresh cache can
    restore everything afterwards."""
    store = DiskStore(tmp_path)
    cache = TranslationCache(store=store)
    errors = []

    def worker(i):
        try:
            be = get_backend("interp", cache=cache)
            prog, _ = suite.vadd()
            eng = Engine(prog, be, 2 + (i % 3), 32, _vadd_args(
                (2 + (i % 3)) * 32))
            eng.run()
        except Exception as exc:  # pragma: no cover - the assertion target
            errors.append(exc)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive()
    assert not errors
    assert not list(store.dir.glob("*.tmp")), "leaked temp files"
    fresh = TranslationCache(store=DiskStore(tmp_path))
    assert fresh.preload(backend="interp") >= 1


# ---------------------------------------------------------------------------
# cost-aware (GDSF) eviction
# ---------------------------------------------------------------------------


@pytest.mark.fast
def test_eviction_is_cost_aware_not_lru():
    """A capacity-2 cache holding one expensive and one cheap entry must
    evict the *cheap* one when a third arrives — plain LRU would have
    evicted the oldest (expensive) entry."""
    c = TranslationCache(capacity=2)
    c.put("expensive", "E", cost_ms=500.0, size_bytes=10)
    c.put("cheap-1", "c1", cost_ms=0.01, size_bytes=10)
    c.put("cheap-2", "c2", cost_ms=0.01, size_bytes=10)
    assert c.stats()["evictions"] == 1
    assert c.get("expensive") == "E"
    assert c.get("cheap-1") is None


@pytest.mark.fast
def test_eviction_clock_ages_out_stale_entries():
    """The GDSF clock advances on eviction, so an expensive-but-idle entry
    is eventually displaced by repeatedly-touched cheap ones."""
    c = TranslationCache(capacity=2)
    c.put("old", "O", cost_ms=1.0, size_bytes=1000)  # score ~ 0.001
    for i in range(50):
        c.put(f"k{i}", i, cost_ms=50.0, size_bytes=10)  # scores >= 5
    assert c.get("old") is None


@pytest.mark.fast
def test_ties_fall_back_to_lru():
    c = TranslationCache(capacity=2)
    c.put("a", 1)
    c.put("b", 2)
    assert c.get("a") == 1  # refresh recency of a
    c.put("c", 3)  # equal scores: evict least-recently-used => b
    assert c.get("b") is None and c.get("a") == 1 and c.get("c") == 3


# ---------------------------------------------------------------------------
# warm-up API
# ---------------------------------------------------------------------------


def test_warmup_translates_then_restores(tmp_path):
    progs = [suite.vadd()[0], (suite.saxpy()[0],
                               {"X": np.ones(64, np.float32),
                                "Y": np.ones(64, np.float32),
                                "n": 64, "a": 2.0})]
    s1 = HetSession("interp", cache=TranslationCache(store=DiskStore(
        tmp_path)))
    rep1 = s1.warmup(progs, grids=((2, 32),))
    assert rep1["errors"] == 0
    assert rep1["translated"] >= 2 and rep1["restored"] == 0

    # a warm node: same store, cold memory — everything restores from disk
    s2 = HetSession("interp", cache=TranslationCache(store=DiskStore(
        tmp_path)))
    rep2 = s2.warmup(progs, grids=((2, 32),))
    assert rep2["errors"] == 0
    assert rep2["translated"] == 0
    assert rep2["restored"] == rep1["translated"]
    # and a post-warmup launch is all memory hits
    prog, _ = suite.vadd()
    s2.load_kernel(prog)
    s2.launch("vadd", grid=2, block=32, args=_vadd_args(64))
    st = s2.cache_stats()
    assert st["translated"] == 0 and st["restored"] == rep1["translated"]


def test_warmup_reports_unlaunchable_kernels(tmp_path):
    """Synthesized args cannot drive every kernel — warm-up reports the
    failure instead of raising."""
    s = HetSession("interp", cache=TranslationCache(store=DiskStore(
        tmp_path)))
    rep = s.warmup([suite.matmul_tiled()[0]], grids=((2, 8),))
    assert len(rep["kernels"]) == 1
    assert rep["errors"] in (0, 1)  # best-effort either way
    statuses = {e["status"].split(":")[0] for e in rep["kernels"]}
    assert statuses <= {"ok", "error"}


# ---------------------------------------------------------------------------
# migration preloads the destination cache
# ---------------------------------------------------------------------------


def test_migrate_warms_destination_from_store(tmp_path):
    """A destination node whose runtime has previously translated this
    program (cluster lifetime) pays zero translation on migration."""
    args = _vadd_args()
    # cluster history: some interp session once ran vadd against the store
    hist = _vadd_session("interp", DiskStore(tmp_path))
    hist.launch("vadd", grid=4, block=32, args=dict(args))

    src = _vadd_session("vectorized", None)
    dst = _vadd_session("interp", DiskStore(tmp_path))
    rec = src.launch("vadd", grid=4, block=32, args=dict(args),
                     blocking=False)
    new = migrate(rec, src, dst, "vadd")
    assert dst.stats["last_migration"]["cache_restored"] >= 1
    dst.run_to_completion(new)
    assert dst.cache_stats()["translated"] == 0, \
        "migration destination re-translated despite a warm store"
    ref = _vadd_session("interp", None)
    ref.launch("vadd", grid=4, block=32, args=dict(args))
    np.testing.assert_array_equal(
        np.asarray(new.engine.result("C")),
        np.asarray(ref._streams[0][-1].engine.result("C")))


# ---------------------------------------------------------------------------
# on-disk size bound + garbage collection (GDSF score)
# ---------------------------------------------------------------------------


def _blob(i, n=1024):
    return bytes([i % 256]) * n


@pytest.mark.fast
def test_diskstore_gc_bounds_the_store(tmp_path):
    """With a byte bound, the store must stop growing: after every save
    the on-disk total stays within max_bytes."""
    store = DiskStore(tmp_path, max_bytes=16_000)
    for i in range(40):
        store.save(("interp", f"fp{i}", 0, 0), "interp-plan", _blob(i),
                   cost_ms=float(i))
        assert store.total_bytes() <= 16_000, f"store grew past bound at {i}"
    assert store.entry_count() < 40
    st = store.stats()
    assert st["gc_evictions"] > 0 and st["gc_runs"] > 0
    assert st["max_bytes"] == 16_000


@pytest.mark.fast
def test_diskstore_gc_evicts_by_gdsf_score(tmp_path):
    """The cheap-to-rebuild entries go first: an expensive translation
    survives a GC that evicts many cheap ones."""
    store = DiskStore(tmp_path, max_bytes=8_000)
    exp_key = ("pallas", "exp", 0, 0)
    store.save(exp_key, "interp-plan", _blob(0), cost_ms=5000.0)
    for i in range(30):
        store.save(("interp", f"cheap{i}", 0, 0), "interp-plan",
                   _blob(i), cost_ms=0.01)
    assert store.load(exp_key) is not None, \
        "GC evicted the expensive entry while cheap ones existed"
    assert store.total_bytes() <= 8_000


@pytest.mark.fast
def test_diskstore_bound_from_env(tmp_path, monkeypatch):
    monkeypatch.setenv("HETGPU_CACHE_MAX_BYTES", "12345")
    assert DiskStore(tmp_path).max_bytes == 12345
    monkeypatch.delenv("HETGPU_CACHE_MAX_BYTES")
    assert DiskStore(tmp_path).max_bytes == 0  # unbounded default


@pytest.mark.fast
def test_diskstore_gc_explicit_and_unbounded_default(tmp_path):
    store = DiskStore(tmp_path)  # unbounded: saves never trigger gc
    for i in range(10):
        store.save(("interp", f"fp{i}", 0, 0), "interp-plan", _blob(i))
    assert store.entry_count() == 10
    assert store.gc() == 0  # no bound, explicit gc is a no-op
    assert store.gc(limit=4_000) > 0  # explicit limit evicts
    assert store.total_bytes() <= 4_000


def test_bounded_store_still_serves_the_working_set(tmp_path):
    """A launch against a tightly bounded store stays correct (worst case
    it re-translates what GC evicted), and the store honours the bound."""
    args = _vadd_args()
    store = DiskStore(tmp_path, max_bytes=4096)
    s = _vadd_session("interp", store)
    s.launch("vadd", grid=4, block=32, args=dict(args))
    assert store.total_bytes() <= 4096
    out = s._streams[0][-1].engine.result("C")
    ref = _vadd_session("interp", None)
    ref.launch("vadd", grid=4, block=32, args=dict(args))
    np.testing.assert_array_equal(
        np.asarray(out),
        np.asarray(ref._streams[0][-1].engine.result("C")))


# ---------------------------------------------------------------------------
# acceptance: cold vs warm benchmark ratio
# ---------------------------------------------------------------------------


def test_bench_cold_warm_speedup_at_least_5x(tmp_path):
    from benchmarks.bench_translation import run_cold_warm

    rows = run_cold_warm(store_dir=str(tmp_path))
    per_backend = [r for r in rows if r["backend"] != "ALL"]
    assert all(r["warm_translated"] == 0 for r in per_backend), \
        "warm start re-translated instead of restoring"
    assert all(r["warm_restored"] == r["cold_translated"]
               for r in per_backend)
    agg = next(r for r in rows if r["backend"] == "ALL")
    assert agg["speedup"] >= 5.0, rows
