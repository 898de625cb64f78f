"""The reduction of the program's ``hetgpu.*`` spans and the per-layer
metrics that read it: self time, idle time by innermost span across the
benchmark's and the program's spans, each reader on a hand-computed
window, and a recorded chip trace of one decode launch."""
import gzip
import shutil
from pathlib import Path
from types import SimpleNamespace

import pytest

import harness
import program_spans as ps

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000          # the synthetic window's times are in ms

#: one pump that binds a launch and runs one segment, a copy to the
#: host, and one checkpoint and restore, in a window of 1,000 ms; the
#: write-back's first half waits on a kernel still running
HOST = [
    ("bench.pump", 0, 500, {}),
    ("hetgpu.serve.pump", 5, 495, {}),
    ("hetgpu.sched.step", 8, 490, {"seq": 0}),
    ("hetgpu.launch.bind", 10, 100, {"seq": 0, "queued_ms": 40.0}),
    ("hetgpu.segment", 120, 400, {"seq": 0}),
    ("hetgpu.segment.lookup", 120, 130, {"seq": 0}),
    ("hetgpu.segment.upload", 130, 200, {"seq": 0}),
    ("hetgpu.launch.writeback", 400, 420, {"seq": 0}),
    ("bench.copy_to_host", 600, 700, {}),
    ("hetgpu.checkpoint", 700, 800, {"seq": 1}),
    ("hetgpu.checkpoint.encode", 720, 760, {"seq": 1}),
    ("hetgpu.restore", 800, 900, {"seq": 0}),
    ("hetgpu.restore.decode", 800, 830, {}),
]
OPS = [(200, 380), (390, 410), (450, 550)]


def _reduced():
    return ps.reduce_events(
        (0, 1000 * MS), [(n, s * MS, e * MS, st) for n, s, e, st in HOST],
        [[(s * MS, e * MS) for s, e in OPS]])


def test_self_time_is_the_span_less_its_children():
    by = _reduced()["by_name"]
    self_ms = {n: round(d["self_s"] * 1e3, 6) for n, d in by.items()}
    assert self_ms == {
        "hetgpu.serve.pump": 8, "hetgpu.sched.step": 92,
        "hetgpu.launch.bind": 90, "hetgpu.segment": 200,
        "hetgpu.segment.lookup": 10, "hetgpu.segment.upload": 70,
        "hetgpu.launch.writeback": 20, "hetgpu.checkpoint": 60,
        "hetgpu.checkpoint.encode": 40, "hetgpu.restore": 70,
        "hetgpu.restore.decode": 30}
    assert by["hetgpu.segment"]["total_s"] == pytest.approx(0.280)
    assert all(d["count"] == 1 for d in by.values())


def test_idle_goes_to_the_innermost_span_of_either_kind():
    idle = {n: round(s * 1e3, 6)
            for n, s in _reduced()["idle_by_span"].items()}
    assert idle == {
        "bench.pump": 5, "hetgpu.serve.pump": 3, "hetgpu.sched.step": 52,
        "hetgpu.launch.bind": 90, "hetgpu.segment.lookup": 10,
        "hetgpu.segment.upload": 70, "hetgpu.segment": 10,
        "hetgpu.launch.writeback": 10, "bench.copy_to_host": 100,
        "hetgpu.checkpoint": 60, "hetgpu.checkpoint.encode": 40,
        "hetgpu.restore.decode": 30, "hetgpu.restore": 70,
        "host:other": 150}
    assert sum(idle.values()) == 1000 - 300


def _read(name, reduced, monkeypatch):
    monkeypatch.setattr(ps, "read", lambda ctx: reduced)
    mod = harness.load_module(harness.metric_reader(name),
                              "metric_" + name.replace(".", "_"))
    return mod.read(SimpleNamespace(trace={}))


@pytest.mark.parametrize("name,want", [
    ("host_copy_ms.decode", 90 + 70 + 10),
    ("segment_host_ms.decode", 280 - 70),
    ("segment_host_ms.serve", 280 - 70),
    ("segment_lookup_ms.serve", 10),
    ("queue_wait_p90_ms.serve", 40),
    ("sched_self_ms.serve", 490 - 280),
    ("program_idle_share.decode", 44.5),
    ("serialize_ms.migrate", 40 + 30)])
def test_each_reader_on_the_window(name, want, monkeypatch):
    assert _read(name, _reduced(), monkeypatch) == pytest.approx(want)


PROGRAM_SPAN_METRICS = [
    "host_copy_ms.decode", "segment_host_ms.decode",
    "segment_host_ms.serve", "segment_lookup_ms.serve",
    "queue_wait_p90_ms.serve", "sched_self_ms.serve",
    "program_idle_share.decode", "program_idle_share.serve",
    "program_idle_share.migrate", "serialize_ms.migrate"]


def test_the_metrics_are_the_program_span_entries(bench_spec):
    names = [m["name"] for m in bench_spec["per_layer"]
             if m["name"] in PROGRAM_SPAN_METRICS]
    assert sorted(names) == sorted(PROGRAM_SPAN_METRICS)


@pytest.mark.parametrize("name", PROGRAM_SPAN_METRICS)
def test_no_trace_or_no_program_span_reads_nothing(name, monkeypatch):
    mod = harness.load_module(harness.metric_reader(name), "m_none")
    assert mod.read(SimpleNamespace(trace=None)) is None
    # a tree whose program emits no span: the window holds bench.* only
    no_spans = ps.reduce_events((0, 10), [("bench.launch", 0, 5, {})],
                                [[(1, 2)]])
    monkeypatch.setattr(ps, "_reduced", lambda path: no_spans)
    monkeypatch.setattr(ps.tr, "latest_xplane", lambda d: d)
    assert mod.read(SimpleNamespace(trace={})) is None


def test_recorded_trace_of_one_decode_launch(tmp_path, monkeypatch):
    """A trace recorded on a TPU v5 lite: one launch of
    ``glm4-9b.decode-8k`` (131 segments) inside ``bench.window``, with
    the program's spans."""
    src = DATA / "glm4-9b-decode-one-launch-spans.xplane.pb.gz"
    dst = tmp_path / "t.xplane.pb"
    with gzip.open(src) as f, open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    r = ps.reduce_spans(dst)
    assert r["window_s"] == pytest.approx(1.1235, abs=1e-4)
    assert len(ps.named(r, "segment")) == 131
    (bind,) = ps.named(r, "launch.bind")
    assert {ev[3]["seq"] for ev in ps.named(r, "segment")} == \
        {bind[3]["seq"]}
    assert len(ps.named(r, "segment.upload")) == 4    # Q, K, V, O
    # the device idle under bind 279.9 ms, upload 1.4 ms, writeback 3.3
    # ms; the writeback's other 174.3 ms wait on the launch's kernels
    assert _read("host_copy_ms.decode", r, monkeypatch) == \
        pytest.approx(284.5747, abs=1e-3)
    assert _read("program_idle_share.decode", r, monkeypatch) == \
        pytest.approx(32.925, abs=1e-3)
    # the device sat idle through the bind: its copies hold the chip
    assert r["idle_by_span"]["hetgpu.launch.bind"] == \
        pytest.approx(ps.ms(bind) * 1e-3, rel=1e-9)
