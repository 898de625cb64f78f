"""Put the benchmark's modules and the program's sources on the path, and
give the tests a tiny configuration that a CPU run can hold."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

#: attn_decode at a size the CPU interprets in seconds: 2 heads, head dim
#: 16 on 32-key tiles (the clamped-lane path), 2 tiles
TINY = {"name": "tiny-attn", "kernel": "attn_decode",
        "attn": {"heads": 2, "head_dim": 16, "tile": 32, "keys": 64,
                 "scale": 0.25, "dtype": "float32"},
        "resident_sequences": 3}


@pytest.fixture
def bench_spec():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "chip_compile: compiles the cells' segment kernels for "
        "a described TPU v5e (slow)")


#: the published peaks of the chip the cells run on, for CPU runs of the
#: harness (which has no device kind of its own in the table)
V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def run_tiny(monkeypatch, spec, workload, seed=5_000_000_017, seconds=1.5,
             traffic=None):
    """One run of ``workload`` on the CPU at the tiny size, with the
    cell's own traffic (overridden by ``traffic``) and its own limits:
    everything of a chip run but the look for the chip."""
    import harness
    import run as runmod
    import work

    real = harness.load_json

    def load_json(kind, name):
        d = real(kind, name)
        if kind == "configs":
            return dict(TINY, limits=d["limits"])
        if kind == "traffic":
            d = {**d, **(traffic or {})}
            if d["path"] == "serve":
                d["sequences_per_tenant"] = 1
        return d

    monkeypatch.setattr(harness, "load_json", load_json)
    monkeypatch.setattr(work, "peaks", lambda kind: V5E)
    wl, cell = runmod.cell_spec(spec, workload)
    return harness.run_cell(
        cell, wl, seed, seconds, False, 0.0,
        require=lambda chips: {"platform": "cpu", "kind": "cpu",
                               "count": 1})
