"""``bind_copy_mb.decode``: the median ``copied_bytes`` of the window's
``hetgpu.launch.bind`` spans, in MB, on hand-made windows and on a chip
trace recorded before the bind carried the counter."""
import gzip
import shutil
from pathlib import Path
from types import SimpleNamespace

import pytest

import harness
import program_spans as ps

DATA = Path(__file__).resolve().parent / "data"
NAME = "bind_copy_mb.decode"


def _window(binds):
    """A 100-ns window with one bind span per counter set in ``binds``."""
    host = [("hetgpu.launch.bind", 10 * i, 10 * i + 5,
             dict(seq=i, queued_ms=0.0, **b)) for i, b in enumerate(binds)]
    return ps.reduce_events((0, 100), host, [[(0, 1)]])


def _read(reduced, monkeypatch):
    monkeypatch.setattr(ps, "read", lambda ctx: reduced)
    mod = harness.load_module(harness.metric_reader(NAME), "metric_bind")
    return mod.read(SimpleNamespace(trace={}))


@pytest.mark.parametrize("copied,want", [
    ([16_384], 0.016384),
    ([16_384, 268_451_840, 16_384], 0.016384),
    ([268_435_456, 268_451_840], 268.443648)])
def test_median_of_the_binds_copied_bytes(copied, want, monkeypatch):
    r = _window([{"copied_bytes": c} for c in copied])
    assert _read(r, monkeypatch) == pytest.approx(want)


def test_a_bind_without_the_counter_reads_nothing(monkeypatch):
    assert _read(_window([{}, {}]), monkeypatch) is None
    mod = harness.load_module(harness.metric_reader(NAME), "metric_bind")
    assert mod.read(SimpleNamespace(trace=None)) is None


def test_recorded_trace_before_the_counter(tmp_path, monkeypatch):
    src = DATA / "glm4-9b-decode-one-launch-spans.xplane.pb.gz"
    dst = tmp_path / "t.xplane.pb"
    with gzip.open(src) as f, open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    r = ps.reduce_spans(dst)
    assert len(ps.named(r, "launch.bind")) == 1
    assert _read(r, monkeypatch) is None


def test_the_entry_reads_the_decode_cell(bench_spec):
    (m,) = [m for m in bench_spec["per_layer"] if m["name"] == NAME]
    assert (m["source"], m["moves"], m["workloads"]) == \
        ("program_span", "decode_ms", ["glm4-9b.decode-8k"])
