"""Public op: chunked gated linear attention (mLSTM core) with oracle VJP."""
from __future__ import annotations

import functools

import jax

from .kernel import mlstm_chunk_fwd
from .ref import mlstm_chunk_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def mlstm_chunk(q, k, v, lf, gi, interpret: bool | None = None):
    return mlstm_chunk_fwd(q, k, v, lf, gi, interpret=interpret)


def _fwd(q, k, v, lf, gi, interpret):
    return mlstm_chunk_fwd(q, k, v, lf, gi, interpret=interpret), \
        (q, k, v, lf, gi)


def _bwd(interpret, res, cts):
    _, vjp = jax.vjp(lambda *a: mlstm_chunk_ref(*a), *res)
    return vjp(cts)


mlstm_chunk.defvjp(_fwd, _bwd)
