"""The reduction from a profiler trace to busy time, idle share, kernel
time and idle gaps by host span."""
from pathlib import Path

import pytest

import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"


def test_union_gaps_and_clip():
    busy = tr.union(tr.clip([(0, 10), (5, 20), (30, 40), (95, 120)], 0, 100))
    assert busy == [(0, 20), (30, 40), (95, 100)]
    assert tr.gaps(busy, 0, 100) == [(20, 30), (40, 95)]


def test_idle_goes_to_the_innermost_span():
    spans = [(0, 100, "bench.launch"), (10, 20, "bench.pump"),
             (30, 60, "bench.migrate"), (40, 50, "bench.first_segment"),
             (200, 300, "bench.copy_to_host")]
    idle = tr.attribute([(5, 45), (90, 250)], spans)
    ns = {k: round(v * 1e9) for k, v in idle.items()}
    assert ns == {"bench.launch": 25, "bench.pump": 10, "bench.migrate": 10,
                  "bench.first_segment": 5, "host:other": 100,
                  "bench.copy_to_host": 50}


def test_recorded_trace_of_one_decode_launch(tmp_path):
    """A trace recorded on a TPU v5 lite: one launch of
    ``glm4-9b.decode-8k`` (131 segment kernels) inside ``bench.window``."""
    import gzip
    import shutil

    src = DATA / "glm4-9b-decode-one-launch.xplane.pb.gz"
    dst = tmp_path / "t.xplane.pb"
    with gzip.open(src) as f, open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    r = tr.reduce_trace(dst)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(1.07685, abs=1e-4)
    assert r["busy_s"] == pytest.approx(0.741478, abs=1e-5)
    assert r["idle_share"] == pytest.approx(1 - 0.741478 / 1.07685, abs=1e-4)
    # the Mosaic kernels are nearly all of the busy time
    assert 0.99 * r["busy_s"] < r["kernel_s"] <= r["busy_s"]
    kernels = [(n, s) for n, s in r["top_ops"] if n.endswith(":custom-call")]
    assert len(kernels) == 6          # the six segment kernels of a launch
    assert sum(s for _, s in kernels) == pytest.approx(r["kernel_s"],
                                                       abs=1e-5)
    idle = dict(r["idle_by_span"])
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"],
                                               rel=1e-6)
    assert max(idle, key=idle.get) == "bench.launch"
    assert tr.op_kind("%fn.1 = (f32[2]{0}, s32[2]{0}) custom-call(f32[2] "
                      "%a), custom_call_target=\"tpu_custom_call\"") == \
        ("fn", "custom-call")
