"""Public op: grouped expert matmul with oracle VJP."""
from __future__ import annotations

import functools

import jax

from .kernel import moe_gmm_fwd
from .ref import moe_gmm_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def moe_gmm(x, w, counts, interpret: bool | None = None):
    return moe_gmm_fwd(x, w, counts, interpret=interpret)


def _fwd(x, w, counts, interpret):
    return moe_gmm_fwd(x, w, counts, interpret=interpret), (x, w, counts)


def _bwd(interpret, res, ct):
    x, w, counts = res
    _, vjp = jax.vjp(lambda x_, w_: moe_gmm_ref(x_, w_, counts), x, w)
    dx, dw = vjp(ct)
    return dx, dw, None


moe_gmm.defvjp(_fwd, _bwd)
