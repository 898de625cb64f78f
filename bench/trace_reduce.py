"""Reduce a profiler trace of one window to the numbers the benchmark
reports: device busy time and idle share, kernel time, the device
operations that took most time, and the idle gaps by what the host was
doing in them.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Device
planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one event
per operation run on the device, named by its HLO text
(``%fn.1 = (...) custom-call(...)``), and their ``XLA Modules`` line one
event per executable run (``jit_call(<id>)``: each segment kernel of the
runtime is an executable of its own).  The benchmark's own host spans
(``bench.<step>``, from ``jax.profiler.TraceAnnotation``) are on the
host plane, on the same clock.  The window is the ``bench.window`` span.
"""
from __future__ import annotations

import bisect
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
# ``%name.3 = <shape> opcode(operands), ...``: the shape may be a tuple
# with parentheses, but only the opcode is preceded by a space
HLO_TEXT = re.compile(r"^%([\w.\-]+) = .*? ([\w\-]+)\(")
# a Mosaic (Pallas) kernel is a custom call to the TPU's kernel target
KERNEL_OPCODE = "custom-call"

Interval = Tuple[int, int]


def latest_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge intervals ``(start, end)`` into disjoint sorted ones."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: List[Interval], lo: int, hi: int) -> List[Interval]:
    """The complement of disjoint sorted ``busy`` within ``[lo, hi)``."""
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def innermost(spans: Iterable[Tuple[int, int, str]]
              ) -> List[Tuple[int, int, str]]:
    """Flatten properly nested spans (one thread's annotations) into
    disjoint sorted pieces, each named by the innermost span over it."""
    out: List[Tuple[int, int, str]] = []
    stack: List[Tuple[int, int, str]] = []
    cur = None
    for s, e, n in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            _, te, tn = stack.pop()
            if cur < te:
                out.append((cur, te, tn))
                cur = te
        if stack and cur < s:
            out.append((cur, s, stack[-1][2]))
        cur = s
        stack.append((s, e, n))
    while stack:
        _, te, tn = stack.pop()
        if cur < te:
            out.append((cur, te, tn))
            cur = te
    return out


def attribute(gap_list: List[Interval],
              spans: List[Tuple[int, int, str]]) -> Dict[str, float]:
    """Seconds of idle time under each host span.  Where spans nest, the
    innermost one takes the time; time under no span of the benchmark is
    ``host:other``."""
    pieces = innermost(spans)
    out: Dict[str, float] = {}

    def add(name, a, b):
        if b > a:
            out[name] = out.get(name, 0.0) + (b - a) * 1e-9

    j = 0
    for gs, ge in gap_list:
        while j < len(pieces) and pieces[j][1] <= gs:
            j += 1
        cur, k = gs, j
        while k < len(pieces) and pieces[k][0] < ge:
            ps, pe, name = pieces[k]
            add("host:other", cur, min(ps, ge))
            add(name, max(ps, cur), min(pe, ge))
            cur = max(cur, min(pe, ge))
            k += 1
        add("host:other", cur, ge)
    return out


def _events(plane, line_name: str):
    for line in plane.lines:
        if line.name == line_name:
            yield from line.events


def op_kind(name: str) -> Tuple[str, str]:
    """``(op name without its instance number, opcode)`` of an HLO op
    event, so ``fusion.12`` and ``fusion.3`` add up."""
    m = HLO_TEXT.match(name)
    if m is None:
        return name, ""
    return re.sub(r"\.\d+$", "", m.group(1)), m.group(2)


def _module_of(mods: List[Tuple[int, int, str]], t: int) -> str:
    """The executable running at ``t`` (modules do not overlap on a core)."""
    i = bisect.bisect_right(mods, (t, float("inf"), "")) - 1
    if i >= 0 and mods[i][0] <= t < mods[i][1]:
        return mods[i][2]
    return "?"


def reduce_trace(path: Path, top: int = 10) -> Dict[str, object]:
    """Reduce the ``.xplane.pb`` at ``path`` (or the newest under a
    directory).  Times are seconds, averaged over the device planes."""
    from jax.profiler import ProfileData

    path = Path(path)
    if path.is_dir():
        path = latest_xplane(path)
    pd = ProfileData.from_file(str(path))
    window: Optional[Interval] = None
    spans: List[Tuple[int, int, str]] = []
    devices = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            devices.append(plane)
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith(SPAN_PREFIX):
                    continue
                iv = (int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                if ev.name == WINDOW:
                    window = iv
                else:
                    spans.append((iv[0], iv[1], ev.name))
    if window is None:
        raise ValueError(f"no {WINDOW} span in {path}")
    if not devices:
        raise ValueError(f"no device plane in {path}")
    lo, hi = window
    busy_s = kernel_s = 0.0
    per_op: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    n_ops = 0
    for plane in devices:
        mods = sorted((int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                       ev.name) for ev in _events(plane, MODULES_LINE))
        ivs, kivs = [], []
        for ev in _events(plane, OPS_LINE):
            s = int(ev.start_ns)
            e = int(ev.start_ns + ev.duration_ns)
            if e <= lo or s >= hi:
                continue
            n_ops += 1
            ivs.append((s, e))
            name, opcode = op_kind(ev.name)
            if opcode == KERNEL_OPCODE:
                kivs.append((s, e))
            key = f"{_module_of(mods, s)}/{name}:{opcode}"
            per_op[key] = per_op.get(key, 0.0) + \
                (min(e, hi) - max(s, lo)) * 1e-9
        busy = union(clip(ivs, lo, hi))
        busy_s += sum(e - s for s, e in busy) * 1e-9
        kernel_s += sum(e - s for s, e in union(clip(kivs, lo, hi))) * 1e-9
        for name, sec in attribute(gaps(busy, lo, hi), spans).items():
            idle[name] = idle.get(name, 0.0) + sec
    n = len(devices)
    window_s = (hi - lo) * 1e-9
    busy_s /= n
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "kernel_s": kernel_s / n,
        "device_ops": n_ops,
        "devices": n,
        "top_ops": sorted(([k, v / n] for k, v in per_op.items()),
                          key=lambda kv: -kv[1])[:top],
        "idle_by_span": sorted(([k, v / n] for k, v in idle.items()),
                               key=lambda kv: -kv[1])[:top],
    }
