"""Chunked gated linear attention — the mLSTM matrix-memory core.

Semantics (per batch-head, unstabilized, f32):

    C_t = f_t * C_{t-1} + i_t * k_t v_t^T          (C: [dk, dv])
    y_t = q_t @ C_t

The chunked form processes time in tiles of ``bt``: within a tile the
intra-chunk term is a decay-masked attention ``(q k^T ∘ Λ) v`` and the
inter-chunk term is ``(λ_t q_t) @ C_in``, with the state updated once per
tile — turning a length-S scan into S/bt MXU-dense steps.  This is the
TPU-native adaptation of mLSTM: matrix units do the heavy lifting, the
recurrence only crosses tile boundaries through VMEM scratch.

Grid ``(BH, nt)`` with nt innermost-sequential; state C [dk, dv] persists
in VMEM scratch.  Gates arrive as per-step log-decay ``lf`` and input gate
``i`` (precomputed by the layer).  The numerically-stabilized variant
(running max) stays in the XLA layer; this kernel is the compute core.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...device import pallas_interpret


def _kernel(q_ref, k_ref, v_ref, lf_ref, i_ref, o_ref, cend_ref, c_ref, *,
            bt: int, nt: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        c_ref[...] = jnp.zeros_like(c_ref)

    q = q_ref[0].astype(jnp.float32)          # [bt, dk]
    k = k_ref[0].astype(jnp.float32)          # [bt, dk]
    v = v_ref[0].astype(jnp.float32)          # [bt, dv]
    lf = lf_ref[0].astype(jnp.float32)        # [bt, 1] log forget
    gi = i_ref[0].astype(jnp.float32)         # [bt, 1] input gate

    # cumulative log-decay within the tile: L[t] = sum_{u<=t} lf[u]
    lcum = jnp.cumsum(lf, axis=0)             # [bt, 1]
    total = lcum[bt - 1]                      # [1]

    # inter-chunk: y_inter[t] = exp(L[t]) * q[t] @ C_in
    c_in = c_ref[...]
    y_inter = jnp.exp(lcum) * jax.lax.dot_general(
        q, c_in, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)   # [bt, dv]

    # intra-chunk: decay-masked attention
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [bt,bt]
    # decay weight exp(L[t] - L[u]) * i[u] for u <= t
    ldiff = lcum - lcum.reshape(1, bt)        # [bt, bt] = L[t]-L[u]
    tpos = jax.lax.broadcasted_iota(jnp.int32, (bt, bt), 0)
    upos = jax.lax.broadcasted_iota(jnp.int32, (bt, bt), 1)
    w = jnp.where(upos <= tpos, jnp.exp(ldiff) * gi.reshape(1, bt), 0.0)
    y_intra = jax.lax.dot_general(s * w, v, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    o_ref[0] = (y_inter + y_intra).astype(o_ref.dtype)

    # state update: C_out = exp(total) * C_in + sum_u exp(L_end - L[u]) i_u k_u v_u^T
    kw = k * (jnp.exp(total.reshape(1, 1) - lcum) * gi)   # [bt, dk]
    c_ref[...] = jnp.exp(total)[0] * c_in + jax.lax.dot_general(
        kw, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ti == nt - 1)
    def _flush():
        cend_ref[0] = c_ref[...].astype(cend_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def mlstm_chunk_fwd(q, k, v, lf, gi, *, bt: int = 128,
                    interpret: bool | None = None):
    """q,k: [BH, S, dk]; v: [BH, S, dv]; lf, gi: [BH, S, 1].
    Returns (y [BH,S,dv], C_final [BH,dk,dv])."""
    BH, S, dk = q.shape
    dv = v.shape[-1]
    bt = min(bt, S)
    nt = pl.cdiv(S, bt)

    kernel = functools.partial(_kernel, bt=bt, nt=nt)
    return pl.pallas_call(
        kernel,
        grid=(BH, nt),
        in_specs=[
            pl.BlockSpec((1, bt, dk), lambda b, ti: (b, ti, 0)),
            pl.BlockSpec((1, bt, dk), lambda b, ti: (b, ti, 0)),
            pl.BlockSpec((1, bt, dv), lambda b, ti: (b, ti, 0)),
            pl.BlockSpec((1, bt, 1), lambda b, ti: (b, ti, 0)),
            pl.BlockSpec((1, bt, 1), lambda b, ti: (b, ti, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bt, dv), lambda b, ti: (b, ti, 0)),
            pl.BlockSpec((1, dk, dv), lambda b, ti: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, dv), q.dtype),
            jax.ShapeDtypeStruct((BH, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=pallas_interpret() if interpret is None else interpret,
    )(q, k, v, lf, gi)
