"""BENCHMARK.json against the benchmark's files: every name resolves to a
file found by name, and every cell reports what it must."""
import json
import re

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_every_name_resolves_to_its_file(bench_spec):
    for c in bench_spec["configs"]:
        assert NAME.match(c["name"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert (BENCH / "kernels" / f"{cfg['kernel']}.py").is_file()
        assert (BENCH / "reference" / f"{cfg['kernel']}.py").is_file()
        for k in c["reduced"]:
            assert cfg["published"][k] != cfg[k]
    for w in bench_spec["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
        assert w["config"] in {c["name"] for c in bench_spec["configs"]}
    import harness

    for m in bench_spec["per_layer"]:
        assert harness.metric_reader(m["name"]).is_file()


def test_every_traffic_path_resolves(bench_spec):
    """A mix's path is one of ``loadgen.PATHS`` or a file
    ``paths/<path>.py``."""
    import loadgen

    for w in bench_spec["workloads"]:
        path = json.loads((BENCH / "traffic" / f"{w['traffic']}.json")
                          .read_text())["path"]
        assert path in loadgen.PATHS or \
            (BENCH / "paths" / f"{path}.py").is_file()


def test_every_cell_reports_what_it_must(bench_spec):
    import end_to_end
    import run

    for w in bench_spec["workloads"]:
        _, cell = run.cell_spec(bench_spec, w["name"])
        assert "setup_s" in cell["cell_end_to_end"]
        assert len(cell["cell_end_to_end"]) >= 2
        assert cell["cell_per_layer"]
        for name in cell["cell_end_to_end"]:
            assert name in end_to_end.METRICS
        for name in cell["cell_per_layer"]:
            assert cell["per_layer"][name]["moves"] in \
                cell["cell_end_to_end"]
