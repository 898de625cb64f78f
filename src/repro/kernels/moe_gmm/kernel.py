"""Grouped (per-expert) matmul kernel — the MoE expert GEMM.

``x [E, C, D] @ w [E, D, F] -> [E, C, F]`` with a row-count vector
``counts [E]`` so tiles past an expert's real token count skip the MXU
entirely (capacity buckets are padded; dispatch guarantees rows >= counts
are zero, so skipped tiles just stay zero).

Grid ``(E, nc, nf, nd)`` with the contraction tiles (nd) innermost; the
f32 accumulator lives in VMEM scratch and flushes on the last nd step.
Tile sizes default to the 128×128 MXU shape.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...device import pallas_interpret


def _kernel(x_ref, w_ref, counts_ref, o_ref, acc_ref, *, bc: int, nd: int):
    e = pl.program_id(0)
    ci = pl.program_id(1)
    di = pl.program_id(3)

    @pl.when(di == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = ci * bc < counts_ref[e]   # any real token rows in this c-tile?

    @pl.when(live)
    def _compute():
        x = x_ref[0].astype(jnp.float32)
        w = w_ref[0].astype(jnp.float32)
        acc_ref[...] += jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(di == nd - 1)
    def _flush():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bc", "bf", "bd", "interpret"))
def moe_gmm_fwd(x, w, counts, *, bc: int = 128, bf: int = 128,
                bd: int = 128, interpret: bool | None = None):
    """x: [E,C,D]; w: [E,D,F]; counts: [E] int32.  Returns [E,C,F]."""
    E, C, D = x.shape
    F = w.shape[-1]
    bc, bf, bd = min(bc, C), min(bf, F), min(bd, D)
    nc, nf, nd = pl.cdiv(C, bc), pl.cdiv(F, bf), pl.cdiv(D, bd)

    kernel = functools.partial(_kernel, bc=bc, nd=nd)
    return pl.pallas_call(
        kernel,
        grid=(E, nc, nf, nd),
        in_specs=[
            pl.BlockSpec((1, bc, bd), lambda e, ci, fi, di: (e, ci, di)),
            pl.BlockSpec((1, bd, bf), lambda e, ci, fi, di: (e, di, fi)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, bc, bf),
                               lambda e, ci, fi, di: (e, ci, fi)),
        out_shape=jax.ShapeDtypeStruct((E, C, F), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        interpret=pallas_interpret() if interpret is None else interpret,
    )(x, w, counts)
