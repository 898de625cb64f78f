"""Public op: flash attention with custom VJP.

Forward runs the Pallas kernel; backward recomputes through the jnp oracle
(flash backward kernel is a further optimization — the recompute keeps
activation memory at flash levels, which is the main point on TPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import flash_attention_fwd
from .ref import attention_ref


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def flash_attention(q, k, v, causal: bool = True, window=None,
                    interpret: bool | None = None):
    """q,k,v: [B,H,S,d] (repeat GQA kv to H heads first)."""
    return flash_attention_fwd(q, k, v, causal=causal, window=window,
                               interpret=interpret)


def _fwd(q, k, v, causal, window, interpret):
    o = flash_attention_fwd(q, k, v, causal=causal, window=window,
                            interpret=interpret)
    return o, (q, k, v)


def _bwd(causal, window, interpret, res, do):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: attention_ref(q_, k_, v_, causal=causal,
                                         window=window), q, k, v)
    return vjp(do)


flash_attention.defvjp(_fwd, _bwd)
