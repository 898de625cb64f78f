"""Public op: RG-LRU scan with custom VJP (backward via the oracle —
linear recurrences transpose cleanly, and the fwd kernel already bounds
activation traffic)."""
from __future__ import annotations

import functools

import jax

from .kernel import rglru_scan_fwd
from .ref import rglru_scan_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def rglru_scan(a, x, h0, interpret: bool | None = None):
    return rglru_scan_fwd(a, x, h0, interpret=interpret)


def _fwd(a, x, h0, interpret):
    out = rglru_scan_fwd(a, x, h0, interpret=interpret)
    return out, (a, x, h0)


def _bwd(interpret, res, cts):
    a, x, h0 = res
    _, vjp = jax.vjp(lambda a_, x_, h_: rglru_scan_ref(a_, x_, h_),
                     a, x, h0)
    return vjp(cts)


rglru_scan.defvjp(_fwd, _bwd)
