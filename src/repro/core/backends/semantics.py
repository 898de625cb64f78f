"""Shared lane-vector semantics for hetIR ops.

Both the vectorized backend (arrays shaped ``[num_blocks, block_size]``) and
the Pallas backend (arrays shaped ``[1, block_size]`` inside one grid step)
evaluate segments through this module.  All per-thread values carry the lane
axis last; collectives reduce over the lane axis only (i.e. within a block),
matching hetIR's definition of collectives over the *active threads of the
block*.

Predication (`@PRED`) is realized as an explicit active-mask stack — the
paper's "software-managed predication" (§4.4): both branch outcomes share a
single instruction stream and inactive lanes are masked at register writes,
memory stores, and collective participation.

Every inexact floating-point result (``ADD``/``SUB``/``MUL``/``DIV`` and
both roundings of ``FMA``) is pinned to its own IEEE rounding (see
:func:`_pin`): XLA otherwise applies graph-shape-dependent inexact
rewrites — FMA contraction, constant reassociation across adds — which
make results differ between two semantically identical programs (e.g. a
rolled loop vs its IR-unrolled form — found by the differential fuzz
harness).  hetIR's contract is one-op-one-rounding on every backend.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import hetir as ir
from .portable_math import exp_jnp


class Env:
    """Mutable evaluation environment for one segment."""

    def __init__(self, regs: Dict[str, Any], shared, globals_: Dict[str, Any],
                 scalars: Dict[str, Any], num_blocks: int, block_size: int,
                 block_offset: Any = 0):
        self.regs = regs
        self.shared = shared
        self.globals = globals_
        self.scalars = scalars
        self.num_blocks = num_blocks      # total blocks in the launch
        self.block_size = block_size
        self.block_offset = block_offset  # first block id of the lane arrays
        self.lane_shape: Optional[Tuple[int, ...]] = None  # set on first use
        # Block-tiled fast path: when set, lane arrays are flat element
        # tiles ``[1, BLOCK]`` starting at this flat global id instead of
        # ``[rows, block_size]`` — thread identity is reconstructed from
        # ``gid = flat_base + iota`` (sound only for segments proven
        # lane-independent by ``passes.block_lower``).
        self.flat_base: Optional[Any] = None
        # Memory accessor for the pallas kernel body
        # (:class:`~repro.core.backends.pallas_mem.RefMemory`): global and
        # shared accesses go through VMEM refs with lowerable gathers and
        # scatters.  ``None`` keeps the whole-array semantics below.
        self.mem = None
        # float division (see _fdiv): the divisor's optimization_barrier
        # exists for XLA's simplifier (Mosaic kernel bodies cannot lower
        # it and never run that simplifier), and the quotient needs a
        # software correction only where the divider is not IEEE — every
        # target but the CPU
        self.div_barrier = True
        self.ieee_div = jax.default_backend() == "cpu"

    def write_reg(self, reg: ir.Reg, value, mask):
        value = jnp.asarray(value, dtype=ir.np_dtype(reg.dtype))
        value = jnp.broadcast_to(value, self.lane_shape)
        if mask is not None:
            old = self.regs.get(reg.name)
            if old is None:
                # hetIR registers read as zero until first written; a
                # masked first write must leave inactive lanes at zero
                # (matches the interpreter's per-lane zero-fill).
                old = _full(self.lane_shape, 0, ir.np_dtype(reg.dtype))
            else:
                old = jnp.broadcast_to(
                    jnp.asarray(old, dtype=ir.np_dtype(reg.dtype)),
                    self.lane_shape)
            value = _select(mask, value, old)
        self.regs[reg.name] = value

    def read_reg(self, reg: ir.Reg):
        v = self.regs.get(reg.name)
        if v is None:  # never-written register: reads as zero
            return _full(self.lane_shape, 0, ir.np_dtype(reg.dtype))
        return jnp.broadcast_to(jnp.asarray(v, ir.np_dtype(reg.dtype)),
                                self.lane_shape)


def _full(shape, value, dtype):
    """``jnp.full``; boolean vectors come from an int32 compare (Mosaic
    cannot materialise a boolean vector constant)."""
    if np.dtype(dtype) == np.bool_:
        return jnp.full(shape, int(bool(value)), jnp.int32) != 0
    return jnp.full(shape, value, dtype)


def _select(c, a, b):
    """``jnp.where``; boolean operands select through logic ops (Mosaic
    cannot select between boolean vectors)."""
    if jnp.result_type(a) == jnp.bool_ and jnp.result_type(b) == jnp.bool_:
        return (c & a) | (~c & b)
    return jnp.where(c, a, b)


def _lane_ids(env: Env):
    """[rows, block_size] thread / block index arrays (or, in flat block
    mode, identities reconstructed from the flat global id)."""
    if env.flat_base is not None:
        gid = jax.lax.broadcasted_iota(jnp.int32, env.lane_shape, 1) \
            + jnp.asarray(env.flat_base, jnp.int32)
        t = jnp.int32(env.block_size)
        return gid // t, gid % t
    tid = jax.lax.broadcasted_iota(jnp.int32, env.lane_shape, 1)
    bid = jax.lax.broadcasted_iota(jnp.int32, env.lane_shape, 0)
    bid = bid + jnp.asarray(env.block_offset, jnp.int32)
    return bid, tid


def _arg(env: Env, a, dtype=None):
    if isinstance(a, ir.Reg):
        return env.read_reg(a)
    return jnp.asarray(a, dtype)


def eval_stmts(stmts: Sequence[ir.Stmt], env: Env, mask) -> None:
    for s in stmts:
        if isinstance(s, ir.Op):
            eval_op(s, env, mask)
        elif isinstance(s, ir.Pred):
            cond = env.read_reg(s.cond)
            inner = cond if mask is None else jnp.logical_and(mask, cond)
            eval_stmts(s.body, env, inner)
        elif isinstance(s, ir.Loop):
            count = s.count if isinstance(s.count, int) \
                else int(env.scalars[s.count])
            for it in range(count):  # trace-time unroll (uniform count)
                env.regs[s.var.name] = jnp.full(
                    env.lane_shape, it, dtype=jnp.int32)
                eval_stmts(s.body, env, mask)
        elif isinstance(s, ir.Barrier):
            raise AssertionError(
                "barrier inside a segment — segmentation bug")
        else:  # pragma: no cover
            raise TypeError(type(s))


def eval_op(op: ir.Op, env: Env, mask) -> None:
    oc = op.opcode
    d = op.dest

    # ---- identity ---------------------------------------------------------
    if oc == ir.GET_GLOBAL_ID:
        bid, tid = _lane_ids(env)
        env.write_reg(d, bid * env.block_size + tid, mask)
    elif oc == ir.GET_BLOCK_ID:
        bid, _ = _lane_ids(env)
        env.write_reg(d, bid, mask)
    elif oc == ir.GET_THREAD_ID:
        _, tid = _lane_ids(env)
        env.write_reg(d, tid, mask)
    elif oc == ir.GET_BLOCK_DIM:
        env.write_reg(d, jnp.full(env.lane_shape, env.block_size,
                                  jnp.int32), mask)
    elif oc == ir.GET_NUM_BLOCKS:
        env.write_reg(d, jnp.full(env.lane_shape, env.num_blocks,
                                  jnp.int32), mask)

    # ---- constants / moves ------------------------------------------------
    elif oc == ir.CONST:
        env.write_reg(d, _full(env.lane_shape, op.args[0],
                               ir.np_dtype(d.dtype)), mask)
    elif oc == ir.LD_PARAM:
        env.write_reg(d, _full(env.lane_shape, env.scalars[op.args[0]],
                               ir.np_dtype(d.dtype)), mask)
    elif oc == ir.MOV:
        env.write_reg(d, _arg(env, op.args[0]), mask)
    elif oc == ir.CVT:
        env.write_reg(d, _convert(_arg(env, op.args[0]),
                                  ir.np_dtype(d.dtype)), mask)

    # ---- ALU ---------------------------------------------------------------
    elif oc == ir.DIV:
        a = _arg(env, op.args[0])
        b = _arg(env, op.args[1])
        env.write_reg(d, _int_or_float(
            a, b, lambda x, y: x // y,
            lambda x, y: _fdiv(x, y, env.div_barrier, env.ieee_div)), mask)
    elif oc in _BINOPS:
        a = _arg(env, op.args[0])
        b = _arg(env, op.args[1])
        env.write_reg(d, _BINOPS[oc](a, b), mask)
    elif oc in _UNOPS:
        env.write_reg(d, _UNOPS[oc](_arg(env, op.args[0])), mask)
    elif oc == ir.FMA:
        a, b, c = (_arg(env, x) for x in op.args)
        env.write_reg(d, _pin(_mul_exact(a, b) + c), mask)
    elif oc == ir.SELECT:
        c, a, b = (_arg(env, x) for x in op.args)
        env.write_reg(d, _select(c, a, b), mask)

    # ---- global memory -----------------------------------------------------
    # BLOCK_LD/BLOCK_ST evaluate exactly like their scalar forms — the tile
    # geometry in their attrs steers BlockSpec construction in the pallas
    # backend (whose memory accessor reads a "tiled" buffer's own tile),
    # not the per-lane value semantics.
    elif oc in (ir.LD_GLOBAL, ir.BLOCK_LD):
        idx = _arg(env, op.args[1]).astype(jnp.int32)
        if env.mem is not None:
            env.write_reg(d, env.mem.load(op, idx, mask), mask)
        else:
            buf = env.globals[op.args[0]]
            safe = idx if mask is None else jnp.where(mask, idx, 0)
            env.write_reg(d, jnp.take(buf, safe.reshape(-1), axis=0)
                          .reshape(env.lane_shape), mask)
    elif oc in (ir.ST_GLOBAL, ir.BLOCK_ST):
        idx = _arg(env, op.args[1]).astype(jnp.int32)
        val = jnp.broadcast_to(_arg(env, op.args[2]), env.lane_shape)
        if env.mem is not None:
            env.mem.store(op, idx, val, mask)
        else:
            buf = env.globals[op.args[0]]
            oob = jnp.int32(buf.shape[0])
            safe = idx if mask is None else jnp.where(mask, idx, oob)
            env.globals[op.args[0]] = buf.at[safe.reshape(-1)].set(
                val.astype(buf.dtype).reshape(-1), mode="drop")
    elif oc == ir.ATOMIC_ADD:
        idx = _arg(env, op.args[1]).astype(jnp.int32)
        val = jnp.broadcast_to(_arg(env, op.args[2]), env.lane_shape)
        if env.mem is not None:
            old = env.mem.atomic_add(op, idx, val, mask)
        else:
            buf = env.globals[op.args[0]]
            val = val.astype(buf.dtype)
            oob = jnp.int32(buf.shape[0])
            safe = idx if mask is None else jnp.where(mask, idx, oob)
            old = jnp.take(buf, jnp.where(safe >= oob, 0, safe).reshape(-1),
                           axis=0).reshape(env.lane_shape)
            env.globals[op.args[0]] = buf.at[safe.reshape(-1)].add(
                val.reshape(-1), mode="drop")
        if d is not None:
            env.write_reg(d, old, mask)

    # ---- shared memory -----------------------------------------------------
    elif oc == ir.LD_SHARED:
        idx = _arg(env, op.args[0]).astype(jnp.int32)
        if env.mem is not None:
            env.write_reg(d, env.mem.load_shared(idx, mask), mask)
        else:
            safe = idx if mask is None else jnp.where(mask, idx, 0)
            env.write_reg(d, jnp.take_along_axis(env.shared, safe, axis=1),
                          mask)
    elif oc == ir.ST_SHARED:
        idx = _arg(env, op.args[0]).astype(jnp.int32)
        val = jnp.broadcast_to(_arg(env, op.args[1]), env.lane_shape)
        if env.mem is not None:
            env.mem.store_shared(idx, val, mask)
        else:
            val = val.astype(env.shared.dtype)
            oob = jnp.int32(env.shared.shape[1])
            safe = idx if mask is None else jnp.where(mask, idx, oob)
            rows = jax.lax.broadcasted_iota(jnp.int32, env.lane_shape, 0)
            env.shared = env.shared.at[rows.reshape(-1),
                                       safe.reshape(-1)].set(
                val.reshape(-1), mode="drop")

    # ---- collectives (within block, over active lanes) ----------------------
    elif oc == ir.VOTE_ANY:
        p = _active(_arg(env, op.args[0]), mask).astype(jnp.int32)
        env.write_reg(d, jnp.max(p, axis=-1, keepdims=True) > 0, mask)
    elif oc == ir.VOTE_ALL:
        p = _arg(env, op.args[0])
        p = p if mask is None else jnp.logical_or(p, jnp.logical_not(mask))
        env.write_reg(d, jnp.min(p.astype(jnp.int32), axis=-1,
                                 keepdims=True) > 0, mask)
    elif oc == ir.VOTE_BALLOT:
        p = _active(_arg(env, op.args[0]), mask)
        env.write_reg(d, jnp.sum(p.astype(jnp.int32), axis=-1,
                                 keepdims=True), mask)
    elif oc == ir.REDUCE_ADD:
        v = _arg(env, op.args[0])
        v = v if mask is None else jnp.where(mask, v, jnp.zeros_like(v))
        env.write_reg(d, _seq_reduce_add(v), mask)
    elif oc == ir.REDUCE_MAX:
        v = _arg(env, op.args[0])
        neg = jnp.full_like(v, _min_value(v.dtype))
        v = v if mask is None else jnp.where(mask, v, neg)
        env.write_reg(d, jnp.max(v, axis=-1, keepdims=True), mask)
    elif oc == ir.SCAN_ADD:
        v = _arg(env, op.args[0])
        v = v if mask is None else jnp.where(mask, v, jnp.zeros_like(v))
        env.write_reg(d, _seq_scan_add(v), mask)
    elif oc == ir.SHUFFLE:
        v = _arg(env, op.args[0])
        src = _arg(env, op.args[1]).astype(jnp.int32)
        src = jnp.clip(src, 0, env.block_size - 1)
        take = jnp.take_along_axis if env.mem is None else env.mem.lane_take
        env.write_reg(d, take(v, src, axis=-1), mask)

    else:  # pragma: no cover
        raise NotImplementedError(oc)


def _seq_reduce_add(v):
    """Lane-order sequential sum, one pinned rounding per add.

    ``jnp.sum`` lets XLA pick the reduction tree (and numpy's ``sum`` on
    the interp side used pairwise summation), so float REDUCE_ADD results
    disagreed across backends in the low bits — the documented
    inclusive_scan/nn_layer ULP divergence.  The portable contract is the
    same one every scalar op follows: strict IEEE-sequential over lane
    order, one rounding per ADD (:func:`_pin`).  Masked-off lanes were
    already zeroed by the caller, and ``x + 0.0`` is exact, so inactive
    lanes never perturb the fold.  Integers are exact under any
    association and keep the vectorized path."""
    if not jnp.issubdtype(v.dtype, jnp.floating):
        return jnp.sum(v, axis=-1, keepdims=True)
    acc = v[..., 0:1]
    for t in range(1, v.shape[-1]):
        acc = _pin(acc + v[..., t:t + 1])
    return acc


def _seq_scan_add(v):
    """Lane-order sequential inclusive prefix sum (see _seq_reduce_add).

    Unrolled at trace time: lane t's prefix is the pinned fold of lanes
    0..t, so every partial matches the interpreter's sequential
    accumulator bit for bit."""
    if not jnp.issubdtype(v.dtype, jnp.floating):
        return jnp.cumsum(v, axis=-1)
    cols = [v[..., 0:1]]
    for t in range(1, v.shape[-1]):
        cols.append(_pin(cols[-1] + v[..., t:t + 1]))
    return jnp.concatenate(cols, axis=-1)


def _convert(v, dt):
    """``v.astype(dt)``, with u32 -> float built from two exact i32 halves
    and one rounding of their sum (the same correctly rounded value): the
    TPU's Mosaic compiler has no unsigned-to-float conversion."""
    if v.dtype == jnp.uint32 and jnp.issubdtype(dt, jnp.floating):
        hi = (v >> 16).astype(jnp.int32).astype(dt)
        lo = (v & 0xFFFF).astype(jnp.int32).astype(dt)
        return _pin(_pin(hi * dt.type(65536.0)) + lo)
    return v.astype(dt)


def _active(pred, mask):
    return pred if mask is None else jnp.logical_and(pred, mask)


def _min_value(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return -jnp.inf
    return jnp.iinfo(dtype).min


def _int_or_float(a, b, fi, ff):
    return ff(a, b) if jnp.issubdtype(a.dtype, jnp.floating) else fi(a, b)


def _pin(v):
    """Pin a floating intermediate to its own IEEE rounding.

    XLA CPU applies inexact algebraic rewrites whose firing depends on the
    *surrounding graph shape*: mul+add contracts into a hardware FMA
    inside fused loops, and constant operands reassociate across adds
    (``(x + c1) + c2 → x + (c1 + c2)``) — so two semantically identical
    programs can differ in their low bits, which breaks the pass
    pipeline's bit-identical O0-vs-OPT_MAX contract (both found by the
    differential fuzz harness).  ``lax.optimization_barrier`` and
    ``reduce_precision`` are erased before fusion (verified on jax 0.4.x);
    ``nextafter(v, v)`` is a *bitwise identity* for every input (equal
    arguments return ``y``; NaN/±inf/±0 round-trip exactly) that lowers
    to bit manipulation the compiler cannot rewrite through.  Every
    inexact float op (ADD/SUB/MUL/DIV and both halves of FMA) pins its
    result, making the jit backends exactly IEEE-sequential — the same
    one-op-one-rounding semantics the interpreter defines.  Integer
    values pass through untouched."""
    if jnp.issubdtype(jnp.result_type(v), jnp.floating):
        return jnp.nextafter(v, v)
    return v


def _mul_exact(a, b):
    return _pin(a * b)


def _split(a):
    """Dekker split: ``a == hi + lo`` exactly, each half 12 bits wide."""
    c = _pin(a * a.dtype.type(4097.0))
    hi = _pin(c - _pin(c - a))
    return hi, _pin(a - hi)


def _prod_err(a, b, p):
    """``a*b - p`` exactly, for ``p = RN(a*b)`` (Dekker's TwoProduct)."""
    ah, al = _split(a)
    bh, bl = _split(b)
    e = _pin(_pin(ah * bh) - p)
    e = _pin(e + _pin(ah * bl))
    e = _pin(e + _pin(al * bh))
    return _pin(e + _pin(al * bl))


#: |x|, |y| and |x/y| inside this range keep every intermediate of the
#: division correction a normal float (no overflow, no flushed subnormal)
_DIV_SAFE = (2.0 ** -60, 2.0 ** 60)


def _fdiv(x, y, barrier: bool, ieee: bool):
    """Correctly rounded float division from the hardware quotient.

    The divisor hides behind an optimization_barrier under XLA (which
    strength-reduces division by a *constant* into multiply-by-reciprocal,
    ~15% of inputs 1 ULP off; found by the attention-profile fuzz corpus,
    seed 20260860: x / 3.1415927).  Mosaic kernel bodies cannot lower the
    barrier and never run that simplifier (``barrier=False``).

    A TPU has no IEEE divider (up to 2 ULP off, measured on a v5 lite),
    so unless ``ieee`` the hardware quotient ``q`` is corrected twice
    toward the round-to-nearest-even result: the residual ``r = x - q*y``
    is exact (Sterbenz for ``x - RN(q*y)``, Dekker for the product's
    error), and ``q`` moves to its neighbour when ``|r|`` exceeds half
    the gap times ``|y|`` (ties to even).  Operands or quotients outside
    :data:`_DIV_SAFE`, zeros and non-finite values keep the hardware
    quotient."""
    q = _pin(x / (jax.lax.optimization_barrier(y) if barrier else y))
    return q if ieee else _round_quotient(x, y, q)


def _round_quotient(x, y, q):
    """``RN(x / y)`` from a quotient ``q`` within two ULP of it (see
    :func:`_fdiv`)."""
    lo, hi = (x.dtype.type(v) for v in _DIV_SAFE)
    ax, ay = jnp.abs(x), jnp.abs(y)
    safe = (ax >= lo) & (ax <= hi) & (ay >= lo) & (ay <= hi)
    inf = x.dtype.type(np.inf)
    for _ in range(2):
        aq = jnp.abs(q)
        ok = safe & (aq >= lo) & (aq <= hi)
        p = _pin(q * y)
        r = _pin(_pin(x - p) - _prod_err(q, y, p))
        up = (r > 0) == (y > 0)            # the true quotient lies above q
        nb = jnp.where(up, jnp.nextafter(q, inf), jnp.nextafter(q, -inf))
        t = _pin(_pin(jnp.abs(_pin(nb - q)) * x.dtype.type(0.5)) * ay)
        ar = jnp.abs(r)
        odd = (jax.lax.bitcast_convert_type(q, jnp.int32) & 1) != 0
        move = ok & (r != 0) & ((ar > t) | ((ar == t) & odd))
        q = jnp.where(move, nb, q)
    return q


_BINOPS = {
    ir.ADD: lambda a, b: _pin(a + b),
    ir.SUB: lambda a, b: _pin(a - b),
    ir.MUL: _mul_exact,
    ir.MOD: lambda a, b: a % b,
    ir.MIN: jnp.minimum,
    ir.MAX: jnp.maximum,
    ir.AND: lambda a, b: (jnp.logical_and(a, b) if a.dtype == jnp.bool_
                          else a & b),
    ir.OR: lambda a, b: (jnp.logical_or(a, b) if a.dtype == jnp.bool_
                         else a | b),
    ir.XOR: lambda a, b: (jnp.logical_xor(a, b) if a.dtype == jnp.bool_
                          else a ^ b),
    ir.SHL: lambda a, b: a << b,
    ir.SHR: lambda a, b: a >> b,
    ir.LT: lambda a, b: a < b,
    ir.LE: lambda a, b: a <= b,
    ir.GT: lambda a, b: a > b,
    ir.GE: lambda a, b: a >= b,
    ir.EQ: lambda a, b: a == b,
    ir.NE: lambda a, b: a != b,
}

_UNOPS = {
    ir.NEG: lambda a: -a,
    ir.ABS: jnp.abs,
    ir.SQRT: jnp.sqrt,
    # EXP is the portable software exponential (one pinned rounding per
    # primitive op), bit-identical to the interpreter's exp_np — jnp.exp
    # would diverge from np.exp in the low bits (portable_math.py)
    ir.EXP: exp_jnp,
    ir.NOT: lambda a: (jnp.logical_not(a) if a.dtype == jnp.bool_ else ~a),
    ir.MOV: lambda a: a,
}
