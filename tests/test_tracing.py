"""The runtime's host spans (``repro.core.tracing``) as a profiler trace
reads them back: names and counters, one span per executed segment,
spans that nest on the calling thread, migration timings that are the
spans' own durations, and a runtime that schedules and counts the same
with no profiler running.

One profiler session records three tiny runs, each inside a span of the
test's own: one ``attn_decode`` launch on the pallas backend
(interpreted), a two-tenant ``ServingFrontEnd`` pumped until its
requests are done, and a launch started on the vectorized backend and
migrated to pallas.
"""
import numpy as np
import pytest

from repro.core import (HetSession, ServingFrontEnd, TranslationCache,
                        migrate)
from repro.zoo import kernels as zoo

H, D, T, NTILES = 2, 16, 32, 2
SCENARIOS = ("launch", "serve", "migrate")

#: every span of the runtime, and the counters it must carry: the
#: launch's ``seq`` wherever one exists, and the bind's queue wait and
#: bytes copied
SPANS = {
    "hetgpu.launch.bind": {"seq", "queued_ms", "copied_bytes"},
    "hetgpu.launch.writeback": {"seq"},
    "hetgpu.segment": {"seq"},
    "hetgpu.segment.lookup": {"seq"},
    "hetgpu.segment.upload": {"seq"},
    "hetgpu.cache.translate": set(),
    "hetgpu.cache.revive": set(),
    "hetgpu.sched.step": {"seq"},
    "hetgpu.serve.pump": set(),
    "hetgpu.serve.submit": set(),
    "hetgpu.checkpoint": {"seq"},
    "hetgpu.checkpoint.snapshot": {"seq"},
    "hetgpu.checkpoint.encode": {"seq"},
    "hetgpu.migrate.preload": {"seq"},
    "hetgpu.restore": {"seq"},
    "hetgpu.restore.decode": set(),
    "hetgpu.restore.resume": set(),
    "hetgpu.restore.rebind": {"seq"},
}


def _buffers(sess, rng):
    n_kv = H * NTILES * T * D
    return {"Q": sess.alloc(H * D).copy_from_host(
                rng.standard_normal(H * D).astype(np.float32)),
            "K": sess.alloc(n_kv).copy_from_host(
                rng.standard_normal(n_kv).astype(np.float32)),
            "V": sess.alloc(n_kv).copy_from_host(
                rng.standard_normal(n_kv).astype(np.float32)),
            "O": sess.alloc(H * D),
            "ntiles": NTILES, "scale": np.float32(1 / np.sqrt(D))}


def _launch(store, prog):
    sess = HetSession("pallas", cache=TranslationCache(store=store))
    fn = sess.load(prog).function()
    fn.launch(H, T, _buffers(sess, np.random.default_rng(1)))
    return [sess]


def _serve(store, prog):
    sess = HetSession("pallas", cache=TranslationCache(store=store))
    fn = sess.load(prog).function()
    front = ServingFrontEnd(sess)
    front.tenant("a", weight=1.0)
    front.tenant("b", weight=2.0)
    rng = np.random.default_rng(2)
    tickets = [front.submit(name, fn, H, T, _buffers(sess, rng))
               for name in ("a", "b", "a", "b")]
    while not all(t.done() for t in tickets):
        front.pump(1)
    return [sess]


def _migrate(store, prog):
    src = HetSession("vectorized", cache=TranslationCache())
    dst = HetSession("pallas", cache=TranslationCache(store=store))
    fn_src = src.load(prog).function()
    fn_dst = dst.load(prog).function()
    rec = fn_src.launch_async(H, T, _buffers(src, np.random.default_rng(3)))
    assert not rec.advance(max_segments=4)
    new = migrate(rec, src, dst, fn_dst)
    assert new.wait()
    return [src, dst]


RUNS = {"launch": _launch, "serve": _serve, "migrate": _migrate}


def _events(path):
    """Every ``hetgpu.*`` and ``test.*`` host span of the trace under
    ``path``: ``(name, start_ns, end_ns, stats)``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(next(path.rglob("*.xplane.pb"))))
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(("hetgpu.", "test.")):
                    out.append((ev.name, int(ev.start_ns),
                                int(ev.start_ns + ev.duration_ns),
                                dict(ev.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _counters(sessions):
    """What the runtime scheduled and counted, without the times: the
    scheduler's trace and every stat that is not a duration."""
    out = []
    for s in sessions:
        stats = {k: v for k, v in s.stats.items() if not k.endswith("_ms")}
        if "last_migration" in stats:
            stats["last_migration"] = {
                k: v for k, v in stats["last_migration"].items()
                if not k.endswith("_ms")}
        out.append((list(s.sched_trace), stats))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    import jax

    store = tmp_path_factory.mktemp("store")
    prog, _ = zoo.attn_decode(D=D, T=T)
    # the store holds the pallas translations every traced session
    # revives; the migration's source translates afresh on vectorized
    _launch(store, prog)
    trace_dir = tmp_path_factory.mktemp("trace")
    sessions = {}
    jax.profiler.start_trace(str(trace_dir))
    try:
        for name in SCENARIOS:
            with jax.profiler.TraceAnnotation("test." + name):
                sessions[name] = RUNS[name](store, prog)
    finally:
        jax.profiler.stop_trace()
    events = _events(trace_dir)
    windows = {e[0][len("test."):]: (e[1], e[2]) for e in events
               if e[0].startswith("test.")}
    spans = {name: [e for e in events if e[0].startswith("hetgpu.")
                    and lo <= e[1] and e[2] <= hi]
             for name, (lo, hi) in windows.items()}
    return {"store": store, "prog": prog, "sessions": sessions,
            "spans": spans}


@pytest.mark.parametrize("name", sorted(SPANS))
def test_every_span_appears_with_its_counters(traced, name):
    found = [e for evs in traced["spans"].values() for e in evs
             if e[0] == name]
    assert found, f"no {name} span in the trace"
    for _, _, _, stats in found:
        assert SPANS[name] <= set(stats), (name, stats)


@pytest.mark.parametrize("scenario", SCENARIOS)
def test_one_segment_span_per_executed_segment(traced, scenario):
    """The segment spans, in time order, are the scheduler's trace: the
    same count as ``segments_executed``, each carrying the ``seq`` of the
    launch whose segment the trace logs at that place."""
    segs = [e[3] for e in traced["spans"][scenario]
            if e[0] == "hetgpu.segment"]
    sessions = traced["sessions"][scenario]
    assert len(segs) == sum(s.stats["segments_executed"] for s in sessions)
    assert [st["seq"] for st in segs] == [
        t["seq"] for s in sessions for t in s.sched_trace]


@pytest.mark.parametrize("scenario", SCENARIOS)
def test_bind_precedes_the_launch_first_segment(traced, scenario):
    spans = traced["spans"][scenario]
    binds = [e for e in spans if e[0] == "hetgpu.launch.bind"]
    assert binds
    for _, _, bind_end, st in binds:
        first = min(e[1] for e in spans if e[0] == "hetgpu.segment"
                    and e[3]["seq"] == st["seq"])
        assert bind_end <= first
        assert st["queued_ms"] >= 0


@pytest.mark.parametrize("scenario", SCENARIOS)
def test_bind_copies_only_the_written_buffer(traced, scenario):
    """``attn_decode`` writes O alone: the bind copies its H*D floats, and
    binds the read-only Q, K and V by reference."""
    binds = [e[3] for e in traced["spans"][scenario]
             if e[0] == "hetgpu.launch.bind"]
    assert binds
    assert all(st["copied_bytes"] == H * D * 4 for st in binds)


@pytest.mark.parametrize("key,span", [
    ("checkpoint_ms", "hetgpu.checkpoint"),
    ("warmup_ms", "hetgpu.migrate.preload"),
    ("restore_ms", "hetgpu.restore")])
def test_last_migration_is_the_spans_duration(traced, key, span):
    _, dst = traced["sessions"]["migrate"]
    (found,) = [e for e in traced["spans"]["migrate"] if e[0] == span]
    assert dst.stats["last_migration"][key] == pytest.approx(
        (found[2] - found[1]) * 1e-6, abs=0.1)


@pytest.mark.parametrize("scenario", SCENARIOS)
def test_no_profiler_schedules_and_counts_the_same(traced, scenario):
    again = RUNS[scenario](traced["store"], traced["prog"])
    assert _counters(again) == _counters(traced["sessions"][scenario])
