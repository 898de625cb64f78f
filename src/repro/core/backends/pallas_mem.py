"""Memory layout and access for the pallas emitter's kernel bodies.

Two halves, both shared by the interpreted (CPU) and the Mosaic (TPU)
kernels so that the tests exercise the very code the chip runs:

* **Layout planning** (host side, at translate time).  Every global
  buffer enters a segment kernel as a 3-D view ``(L // W, W // 128, 128)``
  whose BlockSpec hands each grid step one *window* of ``W`` elements —
  the whole (padded) buffer when nothing smaller is provable.  A window
  comes from an index-range analysis over the whole program
  (:func:`entry_forms` / :func:`plan_buffers`): each integer register is
  summarised as ``Σ c·sym + [lo, hi]`` over the launch-uniform symbols
  (the grid step and the engine's barrier-loop counters, which reach the
  kernel as scalar-prefetch operands), so a K/V tile addressed by
  ``(head, tile)`` moves in alone instead of the whole cache.  Buffers
  too large for VMEM with no provable window are refused with
  :class:`PallasRefusal` on the chip.

* **Access** (:class:`RefMemory`, inside the kernel).  Loads gather from
  a window ref 8 rows at a time with two lane/sublane
  ``take_along_axis`` calls; stores and atomics scatter lane by lane in
  lane order (last writer wins, atomics see earlier lanes' adds — the
  interpreter's sequential semantics).  Both lower through Mosaic, which
  refuses the 1-D ``jnp.take`` / ``.at[].set`` forms.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .. import hetir as ir
from ..segments import resolve_trip_count

LANES = 128           #: TPU vreg lane count
ROWS = 8              #: f32 vreg sublane count
CHUNK = LANES * ROWS  #: window granule: one vreg of elements

#: named refusals of the pallas emitter on the chip (sibling vocabulary of
#: :data:`repro.core.passes.REFUSAL_REASONS`)
MOSAIC_REFUSALS = ("vmem-budget",)

#: bytes of window blocks one segment kernel may stage in VMEM (inputs
#: and outputs, double-buffered), under the 16 MiB scoped default of v5e
VMEM_BUDGET = 12 << 20


class PallasRefusal(RuntimeError):
    """A segment the pallas emitter cannot lower for the chip.  ``reason``
    is ``category[:detail]`` with the category in :data:`MOSAIC_REFUSALS`."""

    def __init__(self, reason: str):
        super().__init__(f"pallas cannot lower this segment: {reason}")
        self.reason = reason


# ---------------------------------------------------------------------------
# index-range analysis
# ---------------------------------------------------------------------------
# A form is ``(terms, lo, hi)``: the value of a register at every lane lies
# in ``Σ c·sym + [lo, hi]`` where ``terms`` is a sorted tuple of
# ``(sym, c)``; ``None`` is "unknown".  Symbols are ``"g"`` (the grid step)
# and ``"loop:<var>"`` (a barrier loop's counter).  A register absent from
# the map was never written and reads as zero.

Form = Optional[Tuple[Tuple[Tuple[str, int], ...], int, int]]
_ZERO: Form = ((), 0, 0)
_I32 = 1 << 31


def _mk(terms, lo: int, hi: int) -> Form:
    if lo < -_I32 or hi >= _I32:
        return None
    return tuple(sorted((k, c) for k, c in dict(terms).items() if c)), lo, hi


def _const(c: int) -> Form:
    return _mk((), c, c)


def _is_const(f: Form) -> bool:
    return f is not None and not f[0] and f[1] == f[2]


def _add(a: Form, b: Form, sign: int = 1) -> Form:
    if a is None or b is None:
        return None
    terms = dict(a[0])
    for k, c in b[0]:
        terms[k] = terms.get(k, 0) + sign * c
    if sign > 0:
        return _mk(terms, a[1] + b[1], a[2] + b[2])
    return _mk(terms, a[1] - b[2], a[2] - b[1])


def _scale(a: Form, k: int) -> Form:
    if a is None:
        return None
    lo, hi = sorted((a[1] * k, a[2] * k))
    return _mk({s: c * k for s, c in a[0]}, lo, hi)


def _join(a: Form, b: Form) -> Form:
    if a is None or b is None or a[0] != b[0]:
        return None
    return a[0], min(a[1], b[1]), max(a[2], b[2])


def _mul(a: Form, b: Form) -> Form:
    if _is_const(a):
        return _scale(b, a[1])
    if _is_const(b):
        return _scale(a, b[1])
    if a is None or b is None or a[0] or b[0]:
        return None
    ps = [x * y for x in (a[1], a[2]) for y in (b[1], b[2])]
    return _mk((), min(ps), max(ps))


def _div(a: Form, k: int) -> Form:
    # floor division distributes over Σ c·sym when every c is a multiple
    if a is None or k <= 0 or a[1] < 0 or any(c % k for _, c in a[0]):
        return None
    return _mk({s: c // k for s, c in a[0]}, a[1] // k, a[2] // k)


def _mod(a: Form, k: int) -> Form:
    if a is None or k <= 0 or a[1] < 0 or any(c % k for _, c in a[0]):
        return None
    if a[2] - a[1] < k and a[1] % k <= a[2] % k:
        return _mk((), a[1] % k, a[2] % k)
    return _mk((), 0, k - 1)


_INT = (ir.I32, ir.U32)


class _Forms:
    """Abstract evaluation of hetIR statements over :data:`Form` values."""

    def __init__(self, num_blocks: int, block_size: int, scalars,
                 grid_block: Optional[int] = None):
        self.T = block_size
        self.B = num_blocks
        self.scalars = dict(scalars)
        # flat (block-tiled) kernels step over tiles of ``grid_block``
        # elements rather than over hetIR blocks
        self.flat = grid_block

    def arg(self, env, a) -> Form:
        if isinstance(a, ir.Reg):
            if a.dtype not in _INT:
                return None
            return env.get(a.name, _ZERO)
        if isinstance(a, bool) or not isinstance(a, (int, np.integer)):
            return None
        return _const(int(a))

    def identity(self, oc) -> Form:
        T = self.T
        if oc == ir.GET_THREAD_ID:
            return _mk((), 0, T - 1)
        if self.flat is None:
            if oc == ir.GET_BLOCK_ID:
                return _mk({"g": 1}, 0, 0)
            return _mk({"g": T}, 0, T - 1)          # GET_GLOBAL_ID
        if oc == ir.GET_GLOBAL_ID:
            return _mk({"g": self.flat}, 0, self.flat - 1)
        if self.flat % T == 0:                      # GET_BLOCK_ID
            per = self.flat // T
            return _mk({"g": per}, 0, per - 1)
        return None

    def op(self, op: ir.Op, env) -> Form:
        oc, a = op.opcode, op.args
        if op.dest is None or op.dest.dtype not in _INT:
            return None
        if oc in (ir.GET_THREAD_ID, ir.GET_BLOCK_ID, ir.GET_GLOBAL_ID):
            return self.identity(oc)
        if oc == ir.GET_BLOCK_DIM:
            return _const(self.T)
        if oc == ir.GET_NUM_BLOCKS:
            return _const(self.B)
        if oc == ir.CONST:
            return self.arg(env, a[0])
        if oc == ir.LD_PARAM:
            v = self.scalars.get(a[0])
            return None if v is None else self.arg(env, int(v))
        if oc == ir.MOV:
            return self.arg(env, a[0])
        if oc == ir.CVT:    # i32 <-> u32: the same bits when non-negative
            f = self.arg(env, a[0])
            return f if f is not None and f[1] >= 0 else None
        if oc in (ir.ADD, ir.SUB):
            return _add(self.arg(env, a[0]), self.arg(env, a[1]),
                        1 if oc == ir.ADD else -1)
        if oc == ir.MUL:
            return _mul(self.arg(env, a[0]), self.arg(env, a[1]))
        if oc == ir.SELECT:
            return _join(self.arg(env, a[1]), self.arg(env, a[2]))
        if oc in (ir.MIN, ir.MAX):
            x, y = self.arg(env, a[0]), self.arg(env, a[1])
            if x is None or y is None or x[0] != y[0]:
                return None
            f = min if oc == ir.MIN else max
            return x[0], f(x[1], y[1]), f(x[2], y[2])
        k = self.arg(env, a[1]) if len(a) > 1 else None
        if not _is_const(k):
            return None
        k = k[1]
        x = self.arg(env, a[0])
        if oc == ir.SHL and 0 <= k < 31:
            return _scale(x, 1 << k)
        if oc == ir.SHR and 0 <= k < 31:
            return _div(x, 1 << k)
        if oc == ir.DIV:
            return _div(x, k)
        if oc == ir.MOD:
            return _mod(x, k)
        if oc == ir.AND and k >= 0:
            return _mk((), 0, k)
        return None

    def stmts(self, body: Sequence[ir.Stmt], env: Dict[str, Form],
              masked: bool, on_access=None) -> None:
        for s in body:
            if isinstance(s, ir.Op):
                if on_access is not None and s.opcode in _GLOBAL_ACCESS:
                    on_access(s, self.arg(env, s.args[1]))
                if s.dest is not None:
                    f = self.op(s, env)
                    if masked:
                        f = _join(env.get(s.dest.name, _ZERO), f)
                    env[s.dest.name] = f
            elif isinstance(s, ir.Pred):
                self.stmts(s.body, env, True, on_access)
            elif isinstance(s, ir.Loop):
                trips = resolve_trip_count(s.count, self.scalars)
                if trips is not None and trips <= 0:
                    continue
                for r in ir.body_defs_uses(s.body)[0]:
                    env[r.name] = None
                env[s.var.name] = None if trips is None \
                    else _mk((), 0, trips - 1)
                self.stmts(s.body, env, masked, on_access)


_GLOBAL_ACCESS = (ir.LD_GLOBAL, ir.ST_GLOBAL, ir.ATOMIC_ADD, ir.BLOCK_LD,
                  ir.BLOCK_ST)


def entry_forms(prog: ir.Program, num_blocks: int, block_size: int,
                scalars) -> List[Dict[str, Form]]:
    """Register forms at the entry of every segment of ``prog``, in the
    order :func:`~repro.core.segments.segment_program` emits them.
    Barrier-loop counters become symbols; registers a barrier loop
    defines are unknown on entry to its body (they carry the previous
    iteration's value) and after it."""
    fm = _Forms(num_blocks, block_size, scalars)
    entries: List[Dict[str, Form]] = []

    def walk(body, env):
        pending = None
        for s in body:
            if isinstance(s, ir.Barrier):
                if pending is not None:
                    entries.append(pending)
                pending = None
            elif isinstance(s, ir.Loop) and ir._contains_barrier(s.body):
                if pending is not None:
                    entries.append(pending)
                pending = None
                defined = [r.name for r in ir.body_defs_uses(s.body)[0]]
                for r in defined:
                    env[r] = None
                env[s.var.name] = _mk({"loop:" + s.var.name: 1}, 0, 0)
                walk(s.body, env)
                for r in defined + [s.var.name]:
                    env[r] = None
            else:
                if pending is None:
                    pending = dict(env)
                fm.stmts([s], env, masked=False)
        if pending is not None:
            entries.append(pending)

    walk(prog.body, {})
    return entries


# ---------------------------------------------------------------------------
# buffer layouts
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class BufLayout:
    """How one global buffer enters a segment kernel.

    ``tiled``: the flat kernel's per-tile ``(1, block)`` view (every access
    is the flat global id).  Otherwise a 3-D ``(padded // window,
    window // 128, 128)`` view: grid step ``g`` (with barrier-loop
    counters ``loops``) sees window ``clip(Σ terms·sym + off)``."""

    length: int
    padded: int
    window: int
    terms: Tuple[Tuple[str, int], ...] = ()
    off: int = 0
    tiled: bool = False

    @property
    def per_step(self) -> bool:
        return any(s == "g" for s, _ in self.terms)

    def index(self, g, loops: Dict[str, object]):
        """Window index for grid step ``g`` (scalar, traced)."""
        idx = jnp.int32(self.off)
        for s, c in self.terms:
            v = g if s == "g" else loops[s[len("loop:"):]]
            idx = idx + jnp.int32(c // self.window) * v
        return jnp.clip(idx, 0, self.padded // self.window - 1)


def pad_len(n: int) -> int:
    return max(CHUNK, -(-n // CHUNK) * CHUNK)


def _choose_window(forms: List[Form], length: int) -> BufLayout:
    padded = pad_len(length)
    whole = BufLayout(length, padded, padded)
    if not forms or any(f is None for f in forms) \
            or len({f[0] for f in forms}) != 1:
        return whole
    terms = forms[0][0]
    lo, hi = min(f[1] for f in forms), max(f[2] for f in forms)
    w = CHUNK
    while w < padded:
        if padded % w == 0 and all(c % w == 0 for _, c in terms) \
                and lo // w == hi // w:
            return BufLayout(length, padded, w, terms, lo // w)
        w *= 2
    return whole


def plan_buffers(stmts: Sequence[ir.Stmt], entry: Dict[str, Form],
                 glb_lens: Dict[str, int], num_blocks: int, block_size: int,
                 scalars, tiled=frozenset(),
                 flat_block: Optional[int] = None) -> Dict[str, BufLayout]:
    """Layout of every global buffer for one segment kernel."""
    fm = _Forms(num_blocks, block_size, scalars, grid_block=flat_block)
    env = dict(entry)
    if flat_block is not None:
        env = {k: _to_flat(f, flat_block, block_size) for k, f in env.items()}
    forms: Dict[str, List[Form]] = {}

    def on_access(op, f):
        forms.setdefault(op.args[0], []).append(f)

    fm.stmts(stmts, env, masked=False, on_access=on_access)
    out = {}
    for name, n in glb_lens.items():
        if name in tiled:
            out[name] = BufLayout(n, n, flat_block, tiled=True)
        else:
            out[name] = _choose_window(forms.get(name, []), n)
    return out


def _to_flat(f: Form, block: int, T: int) -> Form:
    """Re-express a scalar-path form (symbol ``g`` = block id) for a flat
    kernel whose step ``g`` covers ``block`` lanes."""
    if f is None or not any(s == "g" for s, _ in f[0]):
        return f
    if block % T:
        return None
    per = block // T
    c = dict(f[0])["g"]
    return _add(_mk({s: (k * per if s == "g" else k) for s, k in f[0]},
                    f[1], f[2]), _scale(_mk((), 0, per - 1), c))


def vmem_bytes(layouts: Dict[str, BufLayout], dtypes: Dict[str, str],
               written) -> int:
    """Double-buffered VMEM bytes of the global windows of one kernel."""
    total = 0
    for n, lay in layouts.items():
        blk = lay.window * np.dtype(dtypes[n]).itemsize
        total += 2 * blk * (2 if n in written else 1)
    return total


# ---------------------------------------------------------------------------
# in-kernel access
# ---------------------------------------------------------------------------

def _vreg(x):
    """``(1, n)`` lane vector, ``n <= 128`` -> ``(8, 128)`` (lanes padded
    with zeros, sublanes replicated): the shape Mosaic gathers in."""
    n = x.shape[-1]
    if n < LANES:
        x = jnp.concatenate([x, jnp.zeros((1, LANES - n), x.dtype)], axis=1)
    return jnp.broadcast_to(x, (ROWS, LANES))


def _lane_groups(x):
    return [x[:, i:i + LANES] for i in range(0, x.shape[-1], LANES)]


def _gather_group(ref, idx):
    """``ref[(R, 128)]`` at ``idx (1, n <= 128)`` (in range)."""
    n = idx.shape[-1]
    i8 = _vreg(idx)
    row, col = i8 // LANES, i8 % LANES

    def body(c, acc):
        base = pl.multiple_of(c * ROWS, ROWS)
        g = jnp.take_along_axis(ref[pl.ds(base, ROWS), :], col, axis=1)
        rr = row - base
        h = jnp.take_along_axis(g, jnp.clip(rr, 0, ROWS - 1), axis=0)
        return jnp.where((rr >= 0) & (rr < ROWS), h, acc)

    acc = jax.lax.fori_loop(0, ref.shape[0] // ROWS, body,
                            jnp.zeros((ROWS, LANES), ref.dtype))
    return acc[0:1, :n]


def gather(ref, idx):
    """Lane gather ``ref.flat[idx]`` for a ``(R, 128)`` ref, ``R % 8 == 0``
    and ``idx`` a ``(1, n)`` vector of in-range element offsets."""
    parts = [_gather_group(ref, g) for g in _lane_groups(idx)]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _lane(x, t):
    """Lane ``t`` of a ``(1, n)`` vector on every element of a vreg (a
    lane gather: Mosaic cannot broadcast across sublanes and lanes at
    once)."""
    g = t // LANES * LANES
    src = jnp.full((ROWS, LANES), t - g, jnp.int32)
    return jnp.take_along_axis(_vreg(x[:, g:g + LANES]), src, axis=1)


def scatter(ref, idx, val, active, add: bool = False):
    """Sequential lane-order store (or add) of ``val`` at ``idx`` into a
    ``(R, 128)`` ref; lanes with ``active == 0`` or an offset outside the
    ref are dropped.  With ``add``, returns each lane's old value."""
    n = idx.shape[-1]
    pos = (jax.lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 0) * LANES
           + jax.lax.broadcasted_iota(jnp.int32, (ROWS, LANES), 1))
    lane_ids = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
    bits = jnp.int32 if val.dtype.itemsize == 4 else None

    def body(c, old):
        base = pl.multiple_of(c * ROWS, ROWS)
        chunk = ref[pl.ds(base, ROWS), :]
        p = pos + base * LANES
        for t in range(n):
            hit = (p == _lane(idx, t)) & (_lane(active, t) != 0)
            v = _lane(val, t)
            if add:
                # the lane's old value: the one hit element, summed
                # exactly as integer bits
                got = jnp.sum(jnp.where(hit, jax.lax.bitcast_convert_type(
                    chunk, bits), 0), keepdims=True)
                got = jax.lax.bitcast_convert_type(got, chunk.dtype)
                old = jnp.where(lane_ids == t,
                                jnp.broadcast_to(got, (1, n)), old)
                v = chunk + v
            chunk = jnp.where(hit, v, chunk)
        ref[pl.ds(base, ROWS), :] = chunk
        return old

    return jax.lax.fori_loop(0, ref.shape[0] // ROWS, body,
                             jnp.zeros((1, n), ref.dtype))


def lane_take(v, src, axis=-1):
    """``take_along_axis`` over the lanes of a ``(1, n)`` vector (``src``
    in range), one 128-lane group pair at a time."""
    out = []
    for o in _lane_groups(src):
        got = None
        for g in range(0, v.shape[-1], LANES):
            part = jnp.take_along_axis(
                _vreg(v[:, g:g + LANES]),
                _vreg(jnp.clip(o - g, 0, LANES - 1)), axis=1)
            part = part[0:1, :o.shape[-1]]
            got = part if got is None else \
                jnp.where((o >= g) & (o < g + LANES), part, got)
        out.append(got)
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=1)


class RefMemory:
    """hetIR memory ops on VMEM refs inside one pallas grid step.

    ``bufs`` maps a buffer to ``(ref, base, layout)``: the window ref
    (``(R, 128)``, or ``(1, block)`` when tiled) and the element offset of
    the window's start.  ``shared`` is the block's ``(Rs, 128)`` shared
    scratchpad ref (or None)."""

    def __init__(self, bufs, shared, lane_shape):
        self.bufs = bufs
        self.shared = shared
        self.lane_shape = lane_shape
        self.lane_take = lane_take

    def _rel(self, op, idx):
        ref, base, lay = self.bufs[op.args[0]]
        return ref, idx - base, lay

    def load(self, op, idx, mask):
        ref, rel, lay = self._rel(op, idx)
        if lay.tiled:
            return ref[...]
        if mask is not None:
            rel = jnp.where(mask, rel, 0)
        return gather(ref, jnp.clip(rel, 0, lay.window - 1))

    def store(self, op, idx, val, mask):
        ref, rel, lay = self._rel(op, idx)
        val = val.astype(ref.dtype)
        if lay.tiled:
            ref[...] = val if mask is None else jnp.where(mask, val,
                                                          ref[...])
            return
        scatter(ref, rel, val, self._active(mask, rel, lay.window))

    def atomic_add(self, op, idx, val, mask):
        ref, rel, lay = self._rel(op, idx)
        return scatter(ref, rel, val.astype(ref.dtype),
                       self._active(mask, rel, lay.window), add=True)

    def load_shared(self, idx, mask):
        if mask is not None:
            idx = jnp.where(mask, idx, 0)
        return gather(self.shared,
                      jnp.clip(idx, 0, self.shared.shape[0] * LANES - 1))

    def store_shared(self, idx, val, mask):
        scatter(self.shared, idx, val.astype(self.shared.dtype),
                self._active(mask, idx, self.shared.shape[0] * LANES))

    def _active(self, mask, rel, size):
        ok = (rel >= 0) & (rel < size)
        if mask is not None:
            ok = ok & mask
        return jnp.broadcast_to(ok, self.lane_shape).astype(jnp.int32)
