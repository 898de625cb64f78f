"""One run of one cell: set-up, the measured window, the comparison with
the plain reference, and the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric is found by name under ``bench/``: ``configs/<config>.json``,
``traffic/<mix>.json``, ``metrics/<metric>.py``; the program side and the
reference of a configuration's kernel are ``kernels/<kernel>.py`` and
``reference/<kernel>.py``.
"""
from __future__ import annotations

import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
OUT = BENCH.parent / ".bench_out"
TRACE_DIR = OUT / "trace"


class NoAccelerator(RuntimeError):
    pass


def log(*parts) -> None:
    print(*parts, flush=True)


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(kind: str, name: str) -> Dict:
    return json.loads((BENCH / kind / f"{name}.json").read_text())


def require_accelerator(chips: int) -> Dict[str, object]:
    """The devices JAX finds, which must be ``chips`` TPU chips or more;
    the benchmark never falls back to the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX finds no TPU (platform "
                            f"{devs[0].platform!r})")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX finds "
                            f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> Optional[int]:
    """The peak of device memory in use on the fullest chip, over the
    whole process (JAX's ``peak_bytes_in_use``)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def host_state() -> str:
    """The host's load and the bytes it has yet to write back to disk
    (Linux), for the log: what else competes with the run for the
    host's cores."""
    try:
        load = Path("/proc/loadavg").read_text().split()[0]
        mem = dict(line.split(":", 1) for line in
                   Path("/proc/meminfo").read_text().splitlines())
        dirty = sum(int(mem[k].split()[0]) for k in ("Dirty", "Writeback"))
    except (OSError, KeyError, ValueError, IndexError):
        return "unknown"
    return f"loadavg_1m={load} dirty_kb={dirty}"


def cache_counters() -> Dict[str, object]:
    from repro.core.cache import global_cache

    st = global_cache().stats()
    keep = ("translated", "restored", "aot_restored", "aot_fallback_restores",
            "translate_ms", "restore_ms", "trace_ms", "compile_ms",
            "restore_compile_ms", "export_fallbacks", "aot_export_fallbacks",
            "last_export_error", "last_aot_error", "persist_errors")
    return {k: st.get(k) for k in keep}


def compare(answers, attn: Dict, ref, seed: int, control: bool = False
            ) -> Dict[str, object]:
    """Each answer's O against the reference's, grouped by resident
    sequence so each sequence's keys and values are drawn once.  With
    ``control`` the reference's lower-precision twin is compared in the
    program's place, over the same queries."""
    H, D = int(attn["heads"]), int(attn["head_dim"])
    S, scale = int(attn["keys"]), float(attn["scale"])
    by_seq: Dict[int, List] = {}
    for rec, o in answers:
        by_seq.setdefault(rec["seq"], []).append((rec, o))
    worst, n = 0.0, 0
    for s, items in sorted(by_seq.items()):
        k, v = (np.asarray(x, np.float64)
                for x in ref.sequence(seed, s, H, S, D))
        qs = ref.queries(seed, [rec["q"] for rec, _ in items], H, D)
        if control:
            kc, vc = ref.split_high(k), ref.split_high(v)
        for (rec, o), q in zip(items, qs):
            want = ref.decode(q, k, v, scale)
            got = ref.decode_control(q, kc, vc, scale) if control else o
            worst = max(worst, ref.rel_err(got, want))
            n += 1
    return {"o_err_max": worst, "compared": n}


def verdict(check: Dict, failed: int, attempted: int, limits: Dict):
    """``correct`` and the numbers compared, each with its limit: every
    event of the window answered, each answer compared, and the worst
    within the configuration's limit."""
    compared = {"o_err_max": [check["o_err_max"], limits["o_err_max"]],
                "unanswered": [failed, 0]}
    correct = (check["o_err_max"] <= limits["o_err_max"] and failed == 0
               and check["compared"] == attempted)
    return bool(correct), compared


def metric_reader(name: str) -> Path:
    """The reader of per-layer metric ``name``: ``metrics/<name>.py``, or
    for ``<quantity>.<cells>`` the quantity's shared
    ``metrics/<quantity>.py``."""
    own = BENCH / "metrics" / f"{name}.py"
    return own if own.is_file() else \
        BENCH / "metrics" / f"{name.split('.', 1)[0]}.py"


def read_layer_metrics(names: List[str], ctx) -> Dict[str, Dict]:
    out = {}
    for name in names:
        spec = ctx.spec["per_layer"][name]
        mod = load_module(metric_reader(name),
                          "metric_" + name.replace(".", "_").replace("-", "_"))
        value = mod.read(ctx)
        if value is None:
            continue
        out[name] = {"value": float(value), "unit": spec["unit"]}
    return out


def run_cell(spec: Dict, workload: Dict, seed: int, seconds: float,
             trace: bool, started_s: float, require=require_accelerator,
             control: bool = False) -> Dict[str, object]:
    """Run ``workload`` once and return the result line.  ``spec`` is the
    benchmark's metric lists by name: ``end_to_end`` and ``per_layer``
    (name -> entry), and the names this cell reports of each.  With
    ``control`` (calibration only, never a benchmark run) the result also
    carries the control's reading over the same queries."""
    import end_to_end
    import loadgen
    import trace_reduce
    import work

    t_start = time.perf_counter() - started_s
    device = require(int(workload["chips"]))
    config = load_json("configs", workload["config"])
    traffic = load_json("traffic", workload["traffic"])
    attn = config["attn"]
    kernel = load_module(BENCH / "kernels" / f"{config['kernel']}.py",
                         "bench_kernel")
    ref = load_module(BENCH / "reference" / f"{config['kernel']}.py",
                      "bench_reference")

    path = loadgen.path_for(config, traffic, kernel, seed)
    n_q = path.plan(seconds)
    H, D = int(attn["heads"]), int(attn["head_dim"])
    S = int(attn["keys"])
    marks = [("start", time.perf_counter() - t_start)]
    k, v, q = ref.inputs(seed, H, S, D, path.n_seq, n_q)
    marks.append(("inputs", time.perf_counter() - t_start))
    peak_inputs = memory_peak_bytes()
    path.prepare(k, v, q)
    del k, v, q
    marks.append(("prepare", time.perf_counter() - t_start))
    path.warm()
    setup_counters = cache_counters()
    seg0 = path.segments()
    setup_s = time.perf_counter() - t_start
    marks.append(("warm", setup_s))
    log(f"set-up: setup_s={setup_s:.3f} " + " ".join(
        f"{key}={val}" for key, val in setup_counters.items()))
    log("set-up steps (s since process start): " + " ".join(
        f"{name}={t:.3f}" for name, t in marks))
    log(f"device memory: peak after the input draw={peak_inputs} "
        f"peak after set-up={memory_peak_bytes()}")

    if trace:
        import jax

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        TRACE_DIR.mkdir(parents=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    host_before, cpu_before = host_state(), time.process_time()
    with loadgen.span("window"), loadgen.HostPauses() as pauses:
        records, window = path.window(seconds)
    cpu_s = time.process_time() - cpu_before
    if trace:
        jax.profiler.stop_trace()
    window_counters = cache_counters()
    segs = path.segments() - seg0
    peak = memory_peak_bytes()
    answers = path.answers
    extra = {"inflight": list(getattr(path, "inflight", [])),
             "segments": segs}
    path.release()

    attempted = len(records)
    failed = sum(1 for r in records if not r["ok"])
    late = [r["submitted"] - r["due"] for r in records if "submitted" in r]
    log(f"window: events={attempted} failed={failed} "
        f"window_s={window['t1'] - window['t0']:.3f} segments={segs} "
        + (f"generator_late_ms_max={max(late) * 1e3:.3f} " if late else "")
        + f"translated_in_window="
        f"{window_counters['translated'] - setup_counters['translated']} "
        f"restored_in_window="
        f"{window_counters['restored'] - setup_counters['restored']}")
    in_use = path.bytes_in_use
    log(f"device memory: peak={peak} in use at the window's events: "
        f"max={max(in_use, default=None)} "
        f"min={min(in_use, default=None)}")
    log(f"host in the window: cpu_s={cpu_s:.3f} " + pauses.summary()
        + f"; before: {host_before}; after: {host_state()}")
    OUT.mkdir(exist_ok=True)
    (OUT / f"{workload['name']}.records.json").write_text(json.dumps(
        {"seed": seed, "records": records, "gc": pauses.pauses,
         "pumps_over_ms": getattr(path, "long_pumps", [])}, default=str))

    limits = config["limits"]
    correct, compared = verdict(compare(answers, attn, ref, seed), failed,
                                attempted, limits)
    control_reading = None
    if control:
        control_reading = compare(answers, attn, ref, seed, control=True)
        control_reading["correct"] = verdict(control_reading, failed,
                                             attempted, limits)[0]

    e2e = {name: end_to_end.METRICS[name](records, window, setup_s)
           for name in spec["cell_end_to_end"]}
    ctx = SimpleNamespace(
        spec=spec, records=records, setup_counters=setup_counters, e2e=e2e,
        extra=extra, trace=None,
        bound_s=work.bound_s(getattr(work, config["kernel"])(H, S, D),
                             work.peaks(device["kind"])))
    if trace:
        ctx.trace = trace_reduce.reduce_trace(TRACE_DIR)
        log("trace: " + json.dumps(ctx.trace))
        metrics = read_layer_metrics(spec["cell_per_layer"], ctx)
    else:
        metrics = {}
        for name, value in e2e.items():
            if value is not None:
                metrics[name] = {"value": float(value),
                                 "unit": spec["end_to_end"][name]["unit"]}
    device = dict(device, memory_peak_bytes=peak)
    result = {"correct": correct, "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
        result["breakdown"] = {"device_ops": ctx.trace["top_ops"],
                               "idle_gaps": ctx.trace["idle_by_span"]}
    for name, (value, limit) in compared.items():
        print(f"compared {name}={value!r} limit={limit!r}", file=sys.stderr)
    sys.stderr.flush()
    if control_reading is not None:
        result["control"] = control_reading
    result["compared"] = {name: {"value": value, "limit": limit}
                          for name, (value, limit) in compared.items()}
    log(f"cell {workload['name']} seed={seed} correct={correct} of "
        f"{attempted} events")
    return result
