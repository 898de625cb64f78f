"""RG-LRU linear-recurrence kernel (Pallas TPU).

Computes ``h_t = a_t * h_{t-1} + x_t`` along time for gate/input streams
that were precomputed by the surrounding layer (recurrentgemma's RG-LRU
after its input/recurrence gates).

Grid ``(B, nd, ns)`` — time tiles (``ns``) iterate innermost/sequentially,
so the carry ``h`` lives in VMEM scratch across time tiles for each
(batch, channel-tile).  Within a tile a ``fori_loop`` steps ``bs`` rows;
each step is an elementwise FMA over the [1, bd] lane vector (VPU work —
this kernel is memory-bound by design, its job is to stream a/x exactly
once from HBM instead of lax.scan's per-step roundtrips).

Channel tiles are 128 lanes wide; time tiles default 256 rows (8-sublane
multiples).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...device import pallas_interpret


def _kernel(a_ref, x_ref, h0_ref, o_ref, hend_ref, carry_ref, *,
            bs: int, ns: int, seq: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        carry_ref[...] = h0_ref[...].astype(jnp.float32)

    a = a_ref[0].astype(jnp.float32)   # [bs, bd]
    x = x_ref[0].astype(jnp.float32)   # [bs, bd]

    def step(t, h):
        # partial tail tile: rows past seq hold garbage — keep the carry
        valid = (si * bs + t) < seq
        h = jnp.where(valid, a[t] * h + x[t], h)
        o_ref[0, t, :] = h.astype(o_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, bs, step, carry_ref[0])
    carry_ref[0] = h

    @pl.when(si == ns - 1)
    def _flush():
        hend_ref[0] = h.astype(hend_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bs", "bd", "interpret"))
def rglru_scan_fwd(a, x, h0, *, bs: int = 256, bd: int = 128,
                   interpret: bool | None = None):
    """a, x: [B, S, D] (decay, gated input); h0: [B, D].
    Returns (h [B,S,D], h_final [B,D])."""
    B, S, D = a.shape
    bs = min(bs, S)
    bd = min(bd, D)
    ns = pl.cdiv(S, bs)
    nd = pl.cdiv(D, bd)

    kernel = functools.partial(_kernel, bs=bs, ns=ns, seq=S)
    return pl.pallas_call(
        kernel,
        grid=(B, nd, ns),
        in_specs=[
            pl.BlockSpec((1, bs, bd), lambda b, di, si: (b, si, di)),
            pl.BlockSpec((1, bs, bd), lambda b, di, si: (b, si, di)),
            pl.BlockSpec((1, bd), lambda b, di, si: (b, di)),
        ],
        out_specs=[
            pl.BlockSpec((1, bs, bd), lambda b, di, si: (b, si, di)),
            pl.BlockSpec((1, bd), lambda b, di, si: (b, di)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, D), a.dtype),
            jax.ShapeDtypeStruct((B, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, bd), jnp.float32)],
        interpret=pallas_interpret() if interpret is None else interpret,
    )(a, x, h0)
