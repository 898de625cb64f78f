"""One run of one cell: set-up, the measured window, the comparison with
the plain reference, and the result line.

Everything that belongs to one configuration, traffic mix, kernel, path
or per-layer metric is found by name under ``bench/``:
``configs/<config>.json``, ``traffic/<mix>.json``, ``metrics/<metric>.py``,
``kernels/<kernel>.py`` and ``reference/<kernel>.py`` for the
configuration's ``kernel``, and ``paths/<path>.py`` for a mix whose
``path`` is not one of ``loadgen.PATHS``.  The harness and the paths know
nothing of one kernel's shapes or buffers; a new kernel or path is new
files only.

The kernel module (the program side) provides:

* ``shape(config)`` — the configuration's shape block, which the harness
  and the paths hand back to the kernel and reference modules unread;
* ``units(config)`` — how many resident units the run binds; each event
  reads one of them;
* ``program(shape)`` and ``geometry(shape)`` — the hetIR program and the
  launch's ``(grid, block)``;
* ``outputs(shape)`` — each event's output buffers, ``{name: (elements,
  dtype)}``: the buffers the comparison reads;
* ``args(shape, bufs)`` — the launch's arguments from its buffers by
  name: the unit's, the shared ones, the event's inputs and outputs;
* ``answer(shape, outs)`` — from one event's outputs on the host
  (``{name: array}``), the answer in the form the reference's ``check``
  reads;
* ``rests(shape)`` (for the ``migrate`` path) — the segment counts at
  which a launch may rest at a barrier; a kernel without them is refused
  when the path plans.

The reference module (independent of the program) provides:

* ``draw(seed, shape, n_units, n_events)`` — every input of the run from
  the seed: ``{"unit": {name: array with a leading unit axis}, "shared":
  {name: array}, "event": {name: array with a leading event axis}}``;
* ``check(answers, shape, seed, control=False)`` — ``{<limit name>:
  worst, "compared": n}`` over the window's answers, ``(record, answer)``
  pairs whose record names the unit (``seq``) and the drawn event inputs
  (``q``) it used; with ``control`` the reference's lower-precision twin
  is read in the program's place;
* ``work(shape)`` — the algorithm's ``{"flops", "bytes"}`` for one
  launch, from its shapes alone.

``verdict`` holds every key of the configuration's ``limits`` against
the check's value of that key, and every event of the window answered.
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


def kernel_modules(config: Dict):
    """The program side and the reference of the configuration's kernel:
    ``kernels/<kernel>.py`` and ``reference/<kernel>.py``."""
    name = config["kernel"]
    return (load_module(BENCH / "kernels" / f"{name}.py", "bench_kernel"),
            load_module(BENCH / "reference" / f"{name}.py", "bench_reference"))


def compare(answers, shape: Dict, ref, seed: int, control: bool = False
            ) -> Dict[str, object]:
    """The reference's check of the window's answers; with ``control``
    the reference's lower-precision twin is read in the program's place,
    over the same events."""
    return ref.check(answers, shape, seed, control=control)


def verdict(check: Dict, failed: int, attempted: int, limits: Dict):
    """``correct`` and the numbers compared, each with its limit: every
    event of the window answered, each answer compared, and every number
    the configuration limits within its limit."""
    compared = {name: [check[name], limit] for name, limit in limits.items()}
    compared["unanswered"] = [failed, 0]
    correct = (all(check[name] <= limit for name, limit in limits.items())
               and failed == 0 and check["compared"] == attempted)
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
    carries the control's reading over the same events."""
    import end_to_end
    import loadgen
    import trace_reduce
    import work

    t_start = time.perf_counter() - started_s
    device = require(int(workload["chips"]))
    config = load_json("configs", workload["config"])
    traffic = load_json("traffic", workload["traffic"])
    kernel, ref = kernel_modules(config)
    shape = kernel.shape(config)

    path = loadgen.path_for(config, traffic, kernel, seed)
    n_events = path.plan(seconds)
    marks = [("start", time.perf_counter() - t_start)]
    inputs = ref.draw(seed, shape, path.n_units, n_events)
    marks.append(("inputs", time.perf_counter() - t_start))
    peak_inputs = memory_peak_bytes()
    path.prepare(inputs)
    del inputs
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
    correct, compared = verdict(compare(answers, shape, ref, seed), failed,
                                attempted, limits)
    control_reading = None
    if control:
        control_reading = compare(answers, shape, ref, seed, control=True)
        control_reading["correct"] = verdict(control_reading, failed,
                                             attempted, limits)[0]

    e2e = {name: end_to_end.METRICS[name](records, window, setup_s)
           for name in spec["cell_end_to_end"]}
    ctx = SimpleNamespace(
        spec=spec, records=records, setup_counters=setup_counters, e2e=e2e,
        extra=extra, trace=None,
        bound_s=work.bound_s(ref.work(shape), work.peaks(device["kind"])))
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
