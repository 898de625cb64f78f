"""Flash attention forward kernel (Pallas TPU).

Grid ``(B, H, nq, nk)`` — the two outer dims pick a (batch, head), ``nq``
picks a q tile and ``nk`` sweeps kv tiles *innermost* (TPU grids execute
sequentially in row-major order, so the online-softmax state for a q tile
lives in VMEM scratch across the nk sweep and flushes at ``nk == last``).

BlockSpecs stage ``[bq, d]`` / ``[bk, d]`` tiles in VMEM — d is the lane
axis (128 for all assigned archs), bq/bk default 128 so every matmul hits
the MXU at full tile.  Causal masking skips fully-masked kv tiles via
``pl.when`` (no wasted MXU work past the diagonal); sliding windows skip
tiles left of the band.

The kernel computes in f32 regardless of input dtype (TPU MXU accumulates
f32) and casts on the final flush.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...device import pallas_interpret

NEG_INF = -1e30


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                bq: int, bk: int, causal: bool, window, scale: float,
                nk: int, seq_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * bq
    k_start = ki * bk

    # tile-level skip: strictly-future kv tiles (causal) and tiles left of
    # the sliding-window band never touch the MXU
    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, k_start <= q_start + bq - 1)
    if window is not None:
        run = jnp.logical_and(run, k_start + bk - 1
                              > q_start - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)      # [bq, d]
        k = k_ref[0, 0].astype(jnp.float32)      # [bk, d]
        v = v_ref[0, 0].astype(jnp.float32)      # [bk, d]
        # partial tail tile: padded kv rows hold garbage — replace (a
        # multiply would propagate NaNs through 0*NaN)
        kvalid = (k_start + jax.lax.broadcasted_iota(
            jnp.int32, (bk, 1), 0)) < seq_k
        v = jnp.where(kvalid, v, 0.0)
        k = jnp.where(kvalid, k, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = kpos < seq_k
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                       # [bq, 1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                    # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)           # [bq, 1]
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1,
                                                  keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == nk - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention_fwd(q, k, v, *, causal: bool = True, window=None,
                        bq: int = 128, bk: int = 128,
                        interpret: bool | None = None):
    """q,k,v: [B, H, S, d] (kv pre-repeated for GQA).  Returns [B,H,S,d]."""
    B, H, Sq, d = q.shape
    Sk = k.shape[2]
    bq = min(bq, Sq)
    bk = min(bk, Sk)
    nq = pl.cdiv(Sq, bq)
    nk = pl.cdiv(Sk, bk)
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        _fwd_kernel, bq=bq, bk=bk, causal=causal, window=window,
        scale=scale, nk=nk, seq_k=Sk)

    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, qi, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b, h, qi, ki: (b, h, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),   # acc
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running denom
        ],
        interpret=pallas_interpret() if interpret is None else interpret,
    )(q, k, v)
