"""The program's own host spans in a traced window, for the per-layer
metrics that read them.

The runtime marks its steps with ``hetgpu.<step>`` spans on the
profiler's clock (``repro.core.tracing``), beside the benchmark's
``bench.<step>`` spans, with its counters as each event's stats.  This
module reads the newest ``.xplane.pb`` under ``harness.TRACE_DIR`` once
per run and gives, within ``bench.window``:

* ``events`` — every ``hetgpu.*`` event, ``(name, start_ns, end_ns,
  stats)``, in start order;
* ``by_name`` — per span name, its count, total and self seconds (self:
  the span's time less what its child spans cover);
* ``idle_by_span`` — seconds in which the device ran nothing, by the
  innermost ``bench.*`` or ``hetgpu.*`` span on the host (``host:other``
  where none was open), averaged over the device planes.

A program that emits no ``hetgpu.*`` span (an older tree) reads as
``None``, and so does every metric built on it.  Run as a script on a
trace directory or file, it prints the reduction's summary as JSON.
"""
from __future__ import annotations

import bisect
import json
import statistics
import sys
from functools import lru_cache
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import trace_reduce as tr

PREFIX = "hetgpu."

Event = Tuple[str, int, int, Dict[str, object]]


def reduce_spans(path: Path) -> Dict[str, object]:
    """Reduce the ``.xplane.pb`` at ``path`` (or the newest under a
    directory) to the window's program spans."""
    from jax.profiler import ProfileData

    path = Path(path)
    if path.is_dir():
        path = tr.latest_xplane(path)
    pd = ProfileData.from_file(str(path))
    window, ops, host = None, [], []
    for plane in pd.planes:
        if tr.DEVICE_PLANE.match(plane.name):
            ops.append([(int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                        for ev in tr._events(plane, tr.OPS_LINE)])
            continue
        for line in plane.lines:
            for ev in line.events:
                if not ev.name.startswith((PREFIX, tr.SPAN_PREFIX)):
                    continue
                s = int(ev.start_ns)
                e = int(ev.start_ns + ev.duration_ns)
                if ev.name == tr.WINDOW:
                    window = (s, e)
                else:
                    host.append((ev.name, s, e, dict(ev.stats)))
    if window is None:
        raise ValueError(f"no {tr.WINDOW} span in {path}")
    return reduce_events(window, host, ops)


def reduce_events(window: Tuple[int, int], host: List[tuple],
                  ops: List[List[Tuple[int, int]]]) -> Dict[str, object]:
    """The reduction from the window, the host spans ``(name, start_ns,
    end_ns, stats)`` (one thread, properly nested) and each device's
    operation intervals."""
    lo, hi = window
    host = sorted((h for h in host if h[1] >= lo and h[2] <= hi),
                  key=lambda h: (h[1], -h[2]))
    events: List[Event] = [h for h in host if h[0].startswith(PREFIX)]
    spans = [(s, e, n) for n, s, e, _ in host]

    by_name: Dict[str, Dict[str, float]] = {}
    for (n, s, e, _), child_ns in zip(host, _child_time(spans)):
        if n.startswith(PREFIX):
            d = by_name.setdefault(n, {"count": 0, "total_s": 0.0,
                                       "self_s": 0.0})
            d["count"] += 1
            d["total_s"] += (e - s) * 1e-9
            d["self_s"] += (e - s - child_ns) * 1e-9

    idle: Dict[str, float] = {}
    for ivs in ops:
        busy = tr.union(tr.clip(ivs, lo, hi))
        for name, sec in tr.attribute(tr.gaps(busy, lo, hi), spans).items():
            idle[name] = idle.get(name, 0.0) + sec / len(ops)
    return {"window_s": (hi - lo) * 1e-9, "events": events,
            "by_name": by_name, "idle_by_span": idle}


def _child_time(spans: List[Tuple[int, int, str]]) -> List[int]:
    """For spans sorted by (start, -end) and properly nested (one
    thread), the nanoseconds each one's direct children cover."""
    out = [0] * len(spans)
    stack: List[int] = []
    for i, (s, e, _) in enumerate(spans):
        while stack and spans[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1]] += e - s
        stack.append(i)
    return out


@lru_cache(maxsize=4)
def _reduced(path: Path) -> Dict[str, object]:
    return reduce_spans(path)


def read(ctx) -> Optional[Dict[str, object]]:
    """The reduction of this run's trace, memoised; ``None`` in an
    untraced run or when the program emitted no span in the window."""
    if ctx.trace is None:
        return None
    import harness

    r = _reduced(tr.latest_xplane(harness.TRACE_DIR))
    return r if r["events"] else None


def named(r: Dict[str, object], name: str) -> List[Event]:
    return [ev for ev in r["events"] if ev[0] == PREFIX + name]


def inside(outer: List[Event], inner: List[Event]) -> List[float]:
    """For each event of ``outer``, the milliseconds of ``inner`` events
    that lie within it (both lists in start order)."""
    starts = [ev[1] for ev in inner]
    out = []
    for _, s, e, _ in outer:
        i = bisect.bisect_left(starts, s)
        ms = 0.0
        while i < len(inner) and inner[i][1] < e:
            if inner[i][2] <= e:
                ms += (inner[i][2] - inner[i][1]) * 1e-6
            i += 1
        out.append(ms)
    return out


def ms(ev: Event) -> float:
    return (ev[2] - ev[1]) * 1e-6


def median(xs: List[float]) -> Optional[float]:
    return statistics.median(xs) if xs else None


def summary(r: Dict[str, object]) -> Dict[str, object]:
    """What a reader of the log wants: spans by name and idle by span."""
    return {"window_s": r["window_s"],
            "by_name": r["by_name"],
            "idle_by_span": sorted(([k, v] for k, v in
                                    r["idle_by_span"].items()),
                                   key=lambda kv: -kv[1])}


if __name__ == "__main__":
    print(json.dumps(summary(reduce_spans(Path(sys.argv[1])))))
