"""The program side of the zoo's ``moe_route_gmm`` (top-1 router and the
routed expert's matvec, one token per thread), in the kernel interface
that ``bench/harness.py`` describes.

The router's and the experts' weights (``Wg``, ``We``) are resident and
shared by every event; an event is a batch of ``tokens`` tokens (``X``)
and has two outputs, ``Y`` and the int32 expert index ``Eidx``.  A
resident unit holds nothing of its own.  The kernel has no barrier, so
it names no rest points.
"""
from typing import Dict

import numpy as np


def shape(config: Dict) -> Dict:
    return config["moe"]


def units(config: Dict) -> int:
    return int(config["resident_units"])


def program(moe: Dict):
    from repro.zoo.kernels import moe_route_gmm

    prog, _oracle = moe_route_gmm()
    return prog


def geometry(moe: Dict):
    n, block = int(moe["tokens"]), int(moe["block"])
    if n % block:
        raise ValueError(f"tokens ({n}) must be a multiple of the block "
                         f"({block})")
    return n // block, block


def outputs(moe: Dict) -> Dict[str, tuple]:
    n, f = int(moe["tokens"]), int(moe["width"])
    return {"Y": (n * f, np.float32), "Eidx": (n, np.int32)}


def args(moe: Dict, bufs: Dict) -> Dict:
    return {"X": bufs["X"], "Wg": bufs["Wg"], "We": bufs["We"],
            "Y": bufs["Y"], "Eidx": bufs["Eidx"],
            "E": int(moe["experts"]), "F": int(moe["width"])}


def answer(moe: Dict, outs: Dict[str, np.ndarray]) -> Dict[str, np.ndarray]:
    n = int(moe["tokens"])
    return {"Y": np.asarray(outs["Y"], np.float32).reshape(n, -1),
            "Eidx": np.asarray(outs["Eidx"], np.int32).reshape(n)}
