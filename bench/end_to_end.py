"""End-to-end metrics, each taken by the benchmark itself on the host's
clock from the window's event records.  A metric is computed in a cell
when ``BENCHMARK.json`` lists the cell under it (or lists no cells)."""
from __future__ import annotations

from typing import Dict, List, Optional

from loadgen import percentile


def decode_ms(records: List[Dict], window: Dict, setup_s: float
              ) -> Optional[float]:
    """Time per launch with its output on the host: the whole window,
    from its start to the last launch's end, over every launch in it."""
    done = [r for r in records if r["kind"] == "launch"]
    if not done:
        return None
    return (done[-1]["end"] - window["t0"]) / len(done) * 1e3


def served_p90_ms(records: List[Dict], window: Dict, setup_s: float
                  ) -> Optional[float]:
    """90th percentile, over every request due in the window, of the time
    from its due time to its output on the host.  A refused or failed
    request counts as slower than any that completed."""
    reqs = [r for r in records if r["kind"] == "request"]
    if not reqs:
        return None
    lat = [r["latency_ms"] if r["ok"] else float("inf") for r in reqs]
    p = percentile(lat, 90)
    return None if p == float("inf") else p


def migrate_ms(records: List[Dict], window: Dict, setup_s: float
               ) -> Optional[float]:
    """Mean downtime of a live migration: from the source's pause at a
    barrier to the target's first segment done, over every migration of
    the window."""
    moves = [r for r in records if r["kind"] == "migration"]
    if not moves:
        return None
    return sum(r["downtime_ms"] for r in moves) / len(moves)


def setup_s(records: List[Dict], window: Dict, setup_s: float
            ) -> Optional[float]:
    """Process start to the window's start: imports, inputs, loading,
    translation (or its revival from the store) and warm-up."""
    return setup_s


METRICS = {"decode_ms": decode_ms, "served_p90_ms": served_p90_ms,
           "migrate_ms": migrate_ms, "setup_s": setup_s}
