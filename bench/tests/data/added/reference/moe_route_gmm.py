"""Plain reference for a top-1 routed expert layer: route each token to
the expert whose gate logit is largest, and scale that expert's matvec
by the sigmoid of the logit.  In float64 with numpy; nothing here
imports the program under test.  Inputs are drawn from the seed, each
tensor from a stream of its own, so one event's tokens are redrawn
alone."""
from typing import Dict

import numpy as np

# streams of the run's seed: router weights, expert weights, tokens
_WG, _WE, _X = 1, 2, 3


def _normal(seed: int, stream: int, index: int, n: int) -> np.ndarray:
    rng = np.random.default_rng([seed, stream, index])
    return rng.standard_normal(n).astype(np.float32)


def _sizes(moe: Dict):
    return int(moe["tokens"]), int(moe["experts"]), int(moe["width"])


def draw(seed: int, moe: Dict, n_units: int, n_events: int) -> Dict:
    n, e, f = _sizes(moe)
    return {"unit": {},
            "shared": {"Wg": _normal(seed, _WG, 0, e * f),
                       "We": _normal(seed, _WE, 0, e * f * f)},
            "event": {"X": np.stack([_normal(seed, _X, i, n * f)
                                     for i in range(n_events)])}}


def layer(x, wg, we, dtype=np.float64):
    """``(Y, Eidx)`` for tokens ``x`` ``(N, F)``: route, then the routed
    expert's matvec gated by the sigmoid of its logit."""
    x, wg, we = (np.asarray(a, dtype) for a in (x, wg, we))
    logits = x @ wg.T
    eidx = logits.argmax(axis=1)
    best = logits[np.arange(len(x)), eidx]
    y = np.einsum("nfk,nk->nf", we[eidx], x)
    return y / (1.0 + np.exp(-best))[:, None], eidx


def _bf16(a) -> np.ndarray:
    import ml_dtypes

    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16) \
        .astype(np.float32)


def check(answers, moe: Dict, seed: int, control: bool = False) -> Dict:
    """The worst relative gap of ``Y`` and the count of tokens routed to
    another expert than the reference's, over every answer.  With
    ``control`` the layer is computed from bfloat16 inputs in the
    program's place."""
    n, e, f = _sizes(moe)
    wg = _normal(seed, _WG, 0, e * f).reshape(e, f)
    we = _normal(seed, _WE, 0, e * f * f).reshape(e, f, f)
    worst, wrong = 0.0, 0
    for rec, got in answers:
        x = _normal(seed, _X, rec["q"], n * f).reshape(n, f)
        y_ref, e_ref = layer(x, wg, we)
        if control:
            y, eidx = layer(_bf16(x), _bf16(wg), _bf16(we), np.float32)
        else:
            y, eidx = got["Y"], got["Eidx"]
        gap = np.abs(np.asarray(y, np.float64) - y_ref)
        worst = max(worst, float(gap.max() / max(np.abs(y_ref).max(),
                                                 1e-30))
                    if np.all(np.isfinite(gap)) else float("inf"))
        wrong += int(np.count_nonzero(np.asarray(eidx) != e_ref))
    return {"y_err_max": worst, "eidx_mismatch": wrong,
            "compared": len(answers)}


def work(moe: Dict) -> Dict[str, int]:
    """Per token: the router's E dot products and the routed expert's F;
    bytes: the tokens, both weights once, Y and Eidx."""
    n, e, f = _sizes(moe)
    return {"flops": n * (2 * e * f + 2 * f * f),
            "bytes": 4 * (2 * n * f + e * f + e * f * f + n)}
