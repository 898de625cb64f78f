"""The program under test for the ``attn_decode`` configurations: the
zoo's hetIR flash-decode kernel (``repro.zoo.kernels.attn_decode``), run
through the runtime's driver API.

Grid = heads, block = one tile of ``tile`` keys.  A launch executes
``2 + 2 * ntiles + 1`` segments: Q staged, the loop prologue, two per
key tile (scores, then p.v), and the store of O.

A resident unit is one sequence's keys and values (``K``, ``V``); an
event is one query (``Q``) and its output (``O``).  The module's names
are the kernel interface that ``bench/harness.py`` describes.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def shape(config: Dict[str, object]) -> Dict[str, object]:
    return config["attn"]


def units(config: Dict[str, object]) -> int:
    return int(config["resident_sequences"])


def program(attn: Dict[str, object]):
    from repro.zoo.kernels import attn_decode

    prog, _oracle = attn_decode(D=int(attn["head_dim"]), T=int(attn["tile"]))
    return prog


def ntiles(attn: Dict[str, object]) -> int:
    keys, tile = int(attn["keys"]), int(attn["tile"])
    if keys % tile:
        raise ValueError(f"keys ({keys}) must be a multiple of the tile "
                         f"({tile})")
    return keys // tile


def geometry(attn: Dict[str, object]):
    """(grid, block) of one launch."""
    return int(attn["heads"]), int(attn["tile"])


def outputs(attn: Dict[str, object]) -> Dict[str, tuple]:
    """The event's output buffers, each ``(elements, dtype)``."""
    return {"O": (int(attn["heads"]) * int(attn["head_dim"]), np.float32)}


def args(attn: Dict[str, object], bufs: Dict[str, object]
         ) -> Dict[str, object]:
    """The launch's arguments around its four buffers."""
    return {"Q": bufs["Q"], "K": bufs["K"], "V": bufs["V"], "O": bufs["O"],
            "ntiles": ntiles(attn), "scale": np.float32(attn["scale"])}


def answer(attn: Dict[str, object], outs: Dict[str, np.ndarray]
           ) -> np.ndarray:
    """One event's output as the reference reads it: O by head."""
    return np.asarray(outs["O"], np.float32).reshape(int(attn["heads"]), -1)


def after_tile(j: int) -> int:
    """Segments executed when a launch rests at the barrier that closes
    key tile ``j`` (1-based): its next segment scores tile ``j + 1``."""
    return 2 + 2 * j


def rests(attn: Dict[str, object]) -> List[int]:
    """The segment counts at which a launch may rest: the barrier after
    every key tile but the last."""
    return [after_tile(j) for j in range(1, ntiles(attn))]
