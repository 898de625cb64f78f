"""The program under test for the ``attn_decode`` configurations: the
zoo's hetIR flash-decode kernel (``repro.zoo.kernels.attn_decode``), run
through the runtime's driver API.

Grid = heads, block = one tile of ``tile`` keys.  A launch executes
``2 + 2 * ntiles + 1`` segments: Q staged, the loop prologue, two per
key tile (scores, then p.v), and the store of O.
"""
from __future__ import annotations

from typing import Dict

import numpy as np


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


def args(attn: Dict[str, object], q, k, v, o) -> Dict[str, object]:
    """The launch's arguments around its four buffers."""
    return {"Q": q, "K": k, "V": v, "O": o, "ntiles": ntiles(attn),
            "scale": np.float32(attn["scale"])}


def after_tile(j: int) -> int:
    """Segments executed when a launch rests at the barrier that closes
    key tile ``j`` (1-based): its next segment scores tile ``j + 1``."""
    return 2 + 2 * j
