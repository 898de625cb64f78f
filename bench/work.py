"""The work an algorithm needs, counted from its shapes alone.

These counts belong to the algorithm, not to the program that computes
it: a later change that fuses segments, tiles differently or moves data
twice cannot change them.  :func:`bound_s` turns a count into the least
time the chip could take for it, from the peaks in ``peaks.json``.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def attn_decode(H: int, S: int, D: int, itemsize: int = 4) -> Dict[str, int]:
    """One decode step of one attention layer for one query token, with
    ``H`` heads that each read their own ``S`` keys and values of width
    ``D``.

    Bytes: K and V read once, Q read once, O written once.  FLOPs: the
    two contractions (q.k and p.v, a multiply and an add each:
    ``4*H*S*D``) plus the softmax over the scores (scale, running max,
    subtract, exp, sum: ``5*H*S``) and the final division (``H*D``)."""
    return {"flops": 4 * H * S * D + 5 * H * S + H * D,
            "bytes": itemsize * (2 * H * S * D + 2 * H * D)}


def peaks(device_kind: str) -> Dict[str, object]:
    """The published peaks of ``device_kind``; a kind not in the table is
    an error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}: add them with their source")
    return table[device_kind]


def bound_s(work: Dict[str, int], peak: Dict[str, object]) -> float:
    """Least time for ``work`` at the peaks: the larger of the compute
    term and the memory term."""
    return max(work["flops"] / float(peak["flops_per_s"]),
               work["bytes"] / float(peak["hbm_bytes_per_s"]))
