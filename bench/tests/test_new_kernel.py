"""A kernel and a traffic path enter the benchmark as new files only.

Each test copies ``bench/`` to a temporary directory and adds the files
of ``tests/data/added/`` there: a configuration of the zoo's
``moe_route_gmm`` at a toy size with its kernel and reference modules
(shared resident weights, two compared outputs, one of them int32, no
K/V), and a traffic path given only as ``paths/twice.py``.  The copy's
own harness runs them on the CPU, with everything of a chip run but the
look for the chip, and no file the copy already had is changed."""
import copy
import filecmp
import json
import shutil
import sys

import pytest

from conftest import BENCH, TINY, V5E

ADDED = BENCH / "tests" / "data" / "added"
#: the benchmark's modules that import one another by name
MODULES = ("harness", "loadgen", "end_to_end", "trace_reduce", "work",
           "run", "program_spans")
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


@pytest.fixture
def bench_copy(tmp_path, monkeypatch):
    """``bench/`` copied, the added files put beside its own, and its
    modules imported in place of the tree's for the test's length."""
    dst = tmp_path / "bench"
    shutil.copytree(BENCH, dst, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    had = sorted(p.relative_to(dst) for p in dst.rglob("*") if p.is_file())
    for src in ADDED.rglob("*"):
        if src.is_file() and "__pycache__" not in src.parts:
            target = dst / src.relative_to(ADDED)
            assert not target.exists(), f"{target} would be edited"
            target.parent.mkdir(exist_ok=True)
            shutil.copyfile(src, target)
    (dst / "configs" / "tiny-attn.json").write_text(json.dumps(dict(
        TINY, limits=json.loads((BENCH / "configs" / "glm4-9b-attn.json")
                                .read_text())["limits"])))
    saved = {n: sys.modules.pop(n) for n in MODULES if n in sys.modules}
    monkeypatch.syspath_prepend(str(dst))
    import work

    monkeypatch.setattr(work, "peaks", lambda kind: V5E)
    try:
        yield dst
    finally:
        for n in MODULES:
            sys.modules.pop(n, None)
        sys.modules.update(saved)
    for rel in had:
        assert filecmp.cmp(BENCH / rel, dst / rel, shallow=False), rel


def _run(bench_spec, config, traffic, metric, seed=3_000_000_019,
         seconds=1.5):
    """One run of a cell made of ``config`` and ``traffic``, reporting
    ``metric`` and ``setup_s``, through the copy's harness."""
    import harness
    import run as runmod

    name = f"{config}.{traffic}"
    spec = copy.deepcopy(bench_spec)
    spec["workloads"].append({"name": name, "config": config,
                              "traffic": traffic, "chips": 1})
    for m in spec["end_to_end"]:
        if m["name"] == metric:
            m["workloads"].append(name)
    wl, cell = runmod.cell_spec(spec, name)
    return harness.run_cell(cell, wl, seed, seconds, False, 0.0,
                            require=lambda chips: CPU)


MOE_CELLS = [("decode-closed", "decode_ms"), ("serve-toy", "served_p90_ms")]


@pytest.mark.parametrize("traffic,metric", MOE_CELLS)
def test_expert_layer_runs_and_is_correct(bench_copy, bench_spec, traffic,
                                          metric):
    res = _run(bench_spec, "toy-moe", traffic, metric)
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["compared"]) == {"y_err_max", "eidx_mismatch",
                                    "unanswered"}
    assert res["compared"]["eidx_mismatch"] == {"value": 0, "limit": 0}
    assert res["metrics"][metric]["value"] > 0


@pytest.mark.parametrize("traffic,metric", MOE_CELLS)
def test_flipped_router_comparison_is_not_correct(bench_copy, bench_spec,
                                                  monkeypatch, traffic,
                                                  metric):
    """The router's ``dot > best`` built as ``dot < best``: no expert
    ever wins, every token goes to expert 0 with a zero gate."""
    from repro.core import hetir

    monkeypatch.setattr(hetir.Value, "__gt__", hetir.Value.__lt__)
    res = _run(bench_spec, "toy-moe", traffic, metric)
    assert res["correct"] is False
    assert res["compared"]["eidx_mismatch"]["value"] > 0
    assert res["compared"]["y_err_max"]["value"] > \
        res["compared"]["y_err_max"]["limit"]


def test_control_of_the_expert_layer_is_not_correct(bench_copy):
    """The reference from bfloat16 inputs, in the program's place, fails
    the limits that the float64 reference itself meets."""
    import harness

    config = harness.load_json("configs", "toy-moe")
    kernel, ref = harness.kernel_modules(config)
    shape = kernel.shape(config)
    recs = [{"seq": 0, "q": i} for i in range(4)]
    n, e, f = shape["tokens"], shape["experts"], shape["width"]
    inputs = ref.draw(11, shape, 1, len(recs))
    wg = inputs["shared"]["Wg"].reshape(e, f)
    we = inputs["shared"]["We"].reshape(e, f, f)
    exact = []
    for r in recs:
        y, eidx = ref.layer(inputs["event"]["X"][r["q"]].reshape(n, f),
                            wg, we)
        exact.append((r, {"Y": y, "Eidx": eidx}))
    limits = config["limits"]
    ok, _ = harness.verdict(harness.compare(exact, shape, ref, 11), 0, 4,
                            limits)
    assert ok is True
    control = harness.compare(exact, shape, ref, 11, control=True)
    assert harness.verdict(control, 0, 4, limits)[0] is False, control


def test_migrate_path_refuses_a_kernel_without_rest_points(bench_copy,
                                                           bench_spec):
    with pytest.raises(ValueError, match="no rest points"):
        _run(bench_spec, "toy-moe", "migrate-closed", "migrate_ms")


def test_path_found_by_name_runs_attention(bench_copy, bench_spec):
    import harness
    import loadgen

    assert "twice" not in loadgen.PATHS
    config = harness.load_json("configs", "tiny-attn")
    kernel, _ = harness.kernel_modules(config)
    path = loadgen.path_for(config, harness.load_json("traffic",
                                                      "twice-closed"),
                            kernel, 7)
    assert type(path).__name__ == "TwicePath"
    res = _run(bench_spec, "tiny-attn", "twice-closed", "decode_ms")
    assert res["correct"] is True, res["compared"]
    assert res["attempted"] > 0
    assert res["compared"]["o_err_max"]["value"] <= \
        res["compared"]["o_err_max"]["limit"]


def test_unknown_path_is_an_error(bench_copy):
    import harness
    import loadgen

    config = harness.load_json("configs", "tiny-attn")
    kernel, _ = harness.kernel_modules(config)
    with pytest.raises(ValueError, match="paths/nowhere.py"):
        loadgen.path_for(config, {"path": "nowhere"}, kernel, 7)
