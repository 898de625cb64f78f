"""Pallas backend — hetIR segments lowered to TPU kernels.

This is the "SIMT hardware" target: one ``pl.pallas_call`` per segment
(the paper: *"each segment is a separate kernel"*).  One emitter serves
two plans:

* **scalar-per-thread**: grid ``(num_blocks,)``, one grid step per hetIR
  thread block.  Registers travel as ``[num_blocks, 1, block_size]``
  arrays BlockSpec'd ``(1, block_size)`` per step — the
  register-file-in-memory handoff the paper uses between segment kernels —
  and hetIR shared memory as the block's ``(rows, 128)`` VMEM slab.
* **block-tiled** (when :func:`~repro.core.passes.block_lower` proves a
  segment *lane-independent*): the grid tiles the flat element domain
  ``N = num_blocks * block_size`` into constexpr ``BLOCK`` chunks — the
  Triton vector-addition idiom.  Registers travel as ``[1, N]`` arrays
  BlockSpec'd ``(1, BLOCK)``; buffers whose every access is exactly the
  flat global id are tiled the same way and read or written as whole
  tiles.  ``PallasBackend.block_stats`` counts both plans and the proof's
  refusal reasons.  ``HETGPU_BLOCK_LOWER=0`` disables this plan;
  ``HETGPU_BLOCK_MAX`` caps the tile size (default 1024).

Every other global buffer enters as a window of a 3-D view chosen by the
index-range analysis of :mod:`.pallas_mem`: a K/V tile addressed by
``(head, tile)`` moves into VMEM alone, and only buffers with no provable
window are staged whole.  Barrier-loop counters reach the kernel as
scalar-prefetch operands, so window index maps can follow them.

Kernels compile through Mosaic on a TPU and are interpreted on the CPU
backend (how the tests run); the emitted kernel body is the same in both.
On the chip a segment the emitter cannot lower raises
:class:`~.pallas_mem.PallasRefusal` at translate time — never an
interpreted kernel.
"""
from __future__ import annotations

import os
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...device import pallas_interpret
from .. import hetir as ir
from ..cache import TranslationCache
from ..passes import block_lower, choose_block, refusal_category
from ..segments import LoopStart, SegNode, segment_program
from ..tracing import span
from .base import (Backend, HostState, Launch, export_translation,
                   scalar_signature, state_signature)
from .pallas_mem import (LANES, VMEM_BUDGET, BufLayout, PallasRefusal,
                         RefMemory, entry_forms, pad_len, plan_buffers,
                         vmem_bytes)
from .semantics import Env, eval_stmts


def _kdt(dt) -> np.dtype:
    """Kernel-side dtype: Mosaic has no boolean memrefs."""
    dt = np.dtype(dt)
    return np.dtype(np.int32) if dt == np.bool_ else dt


def _program_nodes(prog: ir.Program):
    nodes = getattr(prog, "_nodes_cache", None)
    return nodes if nodes is not None else segment_program(prog)


def _segment_entry(launch: Launch, seg: SegNode):
    """Index-range forms of the registers at ``seg``'s entry."""
    prog = launch.program
    segs = [n for n in _program_nodes(prog) if isinstance(n, SegNode)]
    entries = entry_forms(prog, launch.num_blocks, launch.block_size,
                          launch.scalars)
    pos = [i for i, n in enumerate(segs) if n.index == seg.index]
    if len(entries) == len(segs) and pos:
        return entries[pos[0]]
    return {r.name: None for r in ir.body_defs_uses(prog.body)[0]}


def emit_segment(seg: SegNode, launch: Launch, reg_sig: Tuple,
                 glb_sig: Tuple, shared_sig, block_cand: Optional[int],
                 interpret: bool):
    """Emit the segment's ``pl.pallas_call`` wrapped in its layout
    conversion.  Returns ``(fn, meta, example)``: ``fn(loop_counters,
    *regs, [shared], *globals)`` over host-state shaped arrays, the
    run-time I/O plan, and ShapeDtypeStructs of ``fn``'s arguments.

    Tries the block-tiled plan first (``block_cand`` is the candidate
    tile size, None when disabled); the scalar-per-thread plan is the
    fallback, with the refusal reason in ``meta["block_reason"]``."""
    B, T = launch.num_blocks, launch.block_size
    N = B * T
    plan, block_reason = None, "disabled"
    if block_cand is not None:
        plan, block_reason = block_lower(
            seg.stmts, B, T, block_cand,
            buffer_lens={n: shape[0] for n, shape, _ in glb_sig
                         if len(shape) == 1})
        if plan is not None and plan.block % LANES and plan.block != N:
            # a (1, BLOCK) tile must be lane-aligned or the whole domain
            plan, block_reason = None, "bad-block:lanes"
    flat = plan is not None
    stmts = plan.stmts if flat else seg.stmts
    lanes = plan.block if flat else T
    grid = plan.grid if flat else B
    lane_shape = (1, lanes)

    reg_dt = {n: np.dtype(dt) for n, _, dt in reg_sig}
    seg_defs = {r.name for r in seg.defs}
    loop_vars = tuple(sorted(
        n.var.name for n in _program_nodes(launch.program)
        if isinstance(n, LoopStart) and n.var.name in reg_dt
        and n.var.name not in seg_defs))
    reg_io = tuple(n for n, _, _ in reg_sig if n not in loop_vars)
    new_regs = tuple(sorted(r.name for r in seg.defs if r.name not in reg_dt))
    new_dt = {r.name: ir.np_dtype(r.dtype) for r in seg.defs
              if r.name in new_regs}
    out_dt = {**{n: reg_dt[n] for n in reg_io}, **new_dt}
    # buffers the segment never touches bypass the kernel
    touched = seg.greads | seg.gwrites
    glb_sig = tuple(e for e in glb_sig if e[0] in touched)
    glb_names = tuple(n for n, _, _ in glb_sig)
    glb_len = {n: shape[0] for n, shape, _ in glb_sig}
    glb_dt = {n: np.dtype(dt) for n, _, dt in glb_sig}
    written = tuple(sorted(seg.gwrites))
    has_shared = shared_sig is not None and not flat

    layouts = plan_buffers(stmts, _segment_entry(launch, seg), glb_len, B,
                           T, launch.scalars,
                           tiled=plan.tiled if flat else frozenset(),
                           flat_block=plan.block if flat else None)
    syms = {"g"} | {"loop:" + v for v in loop_vars}
    for n, lay in layouts.items():
        if any(s not in syms for s, _ in lay.terms):   # stage it whole
            layouts[n] = BufLayout(lay.length, lay.padded, lay.padded)
    if not interpret:
        need = vmem_bytes(layouts, {n: _kdt(glb_dt[n]) for n in glb_names},
                          written)
        if need > VMEM_BUDGET:
            raise PallasRefusal(f"vmem-budget:{seg.label}:{need}B")

    if flat:
        def reg_spec():
            return pl.BlockSpec((1, lanes), lambda g, s: (0, g))
    else:
        def reg_spec():
            return pl.BlockSpec((None, 1, T), lambda g, s: (g, 0, 0))

    def glb_spec(n):
        lay = layouts[n]
        if lay.tiled:
            return pl.BlockSpec((1, lanes), lambda g, s: (0, g))

        def index_map(g, s, lay=lay):
            loops = {v: s[j] for j, v in enumerate(loop_vars)}
            return lay.index(g, loops), 0, 0
        return pl.BlockSpec((None, lay.window // LANES, LANES), index_map)

    def glb_shape(n):
        lay = layouts[n]
        shape = (1, lay.length) if lay.tiled else \
            (lay.padded // lay.window, lay.window // LANES, LANES)
        return jax.ShapeDtypeStruct(shape, _kdt(glb_dt[n]))

    reg_shape = (1, N) if flat else (B, 1, T)
    if has_shared:
        S = shared_sig[0][1]
        Sp = pad_len(S)
        sh_dt = _kdt(shared_sig[1])
        sh_spec = pl.BlockSpec((None, Sp // LANES, LANES),
                               lambda g, s: (g, 0, 0))
    in_specs = [reg_spec() for _ in reg_io]
    in_specs += [sh_spec] if has_shared else []
    in_specs += [glb_spec(n) for n in glb_names]
    out_names = reg_io + new_regs
    out_specs = [reg_spec() for _ in out_names]
    out_shapes = [jax.ShapeDtypeStruct(reg_shape, _kdt(out_dt[n]))
                  for n in out_names]
    if has_shared:
        out_specs.append(sh_spec)
        out_shapes.append(jax.ShapeDtypeStruct((B, Sp // LANES, LANES),
                                               sh_dt))
    out_specs += [glb_spec(n) for n in written]
    out_shapes += [glb_shape(n) for n in written]
    n_in = len(in_specs)
    first_glb_in = 1 + len(reg_io) + int(has_shared)
    first_glb_out = len(out_names) + int(has_shared)
    aliases = {first_glb_in + glb_names.index(n): first_glb_out + k
               for k, n in enumerate(written)}
    scalars = dict(launch.scalars)

    def kernel(s_ref, *refs):
        ins, outs = iter(refs[:n_in]), iter(refs[n_in:])
        g = pl.program_id(0)
        loops = {v: s_ref[j] for j, v in enumerate(loop_vars)}
        reg_in = {n: next(ins) for n in reg_io}
        sh_in = next(ins) if has_shared else None
        glb_in = {n: next(ins) for n in glb_names}
        reg_out = {n: next(outs) for n in out_names}
        sh_out = next(outs) if has_shared else None
        glb_out = {n: next(outs) for n in written}

        # written buffers are updated in place in the output window,
        # seeded from the input window: every step when the window moves
        # with the step, once when it stays resident across steps
        for n in written:
            lay = layouts[n]
            if lay.tiled or lay.per_step:
                glb_out[n][...] = glb_in[n][...]
            else:
                @pl.when(g == 0)
                def _init(n=n):
                    glb_out[n][...] = glb_in[n][...]
        if has_shared:
            sh_out[...] = sh_in[...]
        bufs = {}
        for n in glb_names:
            lay = layouts[n]
            ref = glb_out[n] if n in written else glb_in[n]
            base = 0 if lay.tiled else lay.index(g, loops) * lay.window
            bufs[n] = (ref, base, lay)

        regs = {}
        for n in reg_io:
            v = reg_in[n][...]
            regs[n] = v != 0 if reg_dt[n] == np.bool_ else v
        for v in loop_vars:
            regs[v] = jnp.full(lane_shape, loops[v], reg_dt[v])
        env = Env(regs=regs, shared=None, globals_={}, scalars=scalars,
                  num_blocks=B, block_size=T,
                  block_offset=0 if flat else g)
        env.lane_shape = lane_shape
        if flat:
            env.flat_base = g * lanes
        env.mem = RefMemory(bufs, sh_out, lane_shape)
        env.div_barrier = env.ieee_div = interpret
        eval_stmts(stmts, env, mask=None)

        for n, ref in reg_out.items():
            if n in env.regs:
                v = jnp.broadcast_to(env.regs[n], lane_shape)
            elif n in reg_in:  # untouched register: pass through
                v = reg_in[n][...]
            else:  # defined only in a zero-trip loop: zeros
                v = jnp.zeros(lane_shape, ref.dtype)
            ref[...] = v.astype(ref.dtype)

    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(grid,), in_specs=in_specs,
            out_specs=out_specs),
        out_shape=out_shapes,
        input_output_aliases=aliases,
        interpret=interpret,
        name=f"{launch.program.name}.s{seg.index}",
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )

    def fn(loop_counters, *args):
        args = list(args)
        kin = []
        for n in reg_io:
            kin.append(args.pop(0).astype(_kdt(reg_dt[n])).reshape(reg_shape))
        if has_shared:
            sh = args.pop(0).astype(sh_dt)
            sh = jnp.pad(sh, ((0, 0), (0, Sp - S)))
            kin.append(sh.reshape(B, Sp // LANES, LANES))
        for n in glb_names:
            lay = layouts[n]
            a = args.pop(0).astype(_kdt(glb_dt[n]))
            if not lay.tiled:
                a = jnp.pad(a, (0, lay.padded - lay.length))
            kin.append(a.reshape(glb_shape(n).shape))
        outs = list(call(loop_counters, *kin))
        res = [outs.pop(0).reshape(B, T).astype(out_dt[n])
               for n in out_names]
        if has_shared:
            res.append(outs.pop(0).reshape(B, Sp)[:, :S]
                       .astype(shared_sig[1]))
        for n in written:
            res.append(outs.pop(0).reshape(-1)[:glb_len[n]]
                       .astype(glb_dt[n]))
        return tuple(res)

    meta = dict(reg_io=reg_io, new_regs=new_regs, loop_vars=loop_vars,
                glb_names=glb_names, written=written, has_shared=has_shared,
                block=None if not flat else dict(
                    block=plan.block, grid=plan.grid,
                    tiled=tuple(sorted(plan.tiled))),
                block_reason=None if flat else block_reason,
                windows={n: lay.window for n, lay in layouts.items()
                         if not lay.tiled and lay.window < lay.padded})
    example = tuple(
        [jax.ShapeDtypeStruct((max(1, len(loop_vars)),), np.int32)]
        + [jax.ShapeDtypeStruct(shape, np.dtype(dt))
           for n, shape, dt in reg_sig if n not in loop_vars]
        + ([jax.ShapeDtypeStruct(shared_sig[0], np.dtype(shared_sig[1]))]
           if has_shared else [])
        + [jax.ShapeDtypeStruct(shape, np.dtype(dt))
           for _, shape, dt in glb_sig])
    return fn, meta, example


class PallasBackend(Backend):
    name = "pallas"

    def __init__(self, cache: "TranslationCache" = None):
        super().__init__(cache)
        # fast-path observability: how many segment executions took the
        # block-tiled vs scalar-per-thread plan, and why refusals happened
        self.block_stats: Dict[str, object] = \
            {"tiled": 0, "scalar": 0, "reasons": {}}

    @staticmethod
    def _block_enabled() -> bool:
        return os.environ.get("HETGPU_BLOCK_LOWER", "1").lower() \
            not in ("0", "off", "false")

    # ------------------------------------------------------------------
    def _translate(self, seg: SegNode, launch: Launch, reg_sig: Tuple,
                   glb_sig: Tuple, shared_sig):
        # geometry, scalars, and the register/buffer shape+dtype signatures
        # all specialize the emitted kernel, so they join the shared key
        # (on top of the base key's launch-time specialization vector —
        # a scalar-specialized segment emits from a different body).  The
        # candidate tile size joins too: it folds in HETGPU_BLOCK_LOWER /
        # HETGPU_BLOCK_MAX, so flipping either can never revive a
        # translation emitted under the other setting.
        cand = choose_block(launch.num_blocks * launch.block_size) \
            if self._block_enabled() else None
        key = self._cache_key(seg, launch, launch.num_blocks,
                              launch.block_size, scalar_signature(launch),
                              reg_sig, glb_sig, shared_sig, ("block", cand))

        def translate():
            return self._build(seg, launch, reg_sig, glb_sig, shared_sig,
                               cand)

        return self.cache.get_or_translate(key, translate)

    def _build(self, seg: SegNode, launch: Launch, reg_sig: Tuple,
               glb_sig: Tuple, shared_sig, block_cand: Optional[int] = None):
        """Emit, trace, and export the segment's kernel.  Returns
        ``((compiled fn, meta), persist)`` for the translation cache; the
        persisted payload is the serialized ``jax.export`` artifact plus
        ``meta``, so a warm process skips re-emitting and re-tracing."""
        fn, meta, example = emit_segment(seg, launch, reg_sig, glb_sig,
                                         shared_sig, block_cand,
                                         interpret=pallas_interpret())
        live, payload = export_translation(jax.jit(fn), example,
                                           cache=self.cache)
        persist = None if payload is None \
            else ("jax-aot-meta", (payload, meta))
        return (live, meta), persist

    # ------------------------------------------------------------------
    def run_segment(self, seg: SegNode, state: HostState,
                    launch: Launch) -> None:
        with span("segment.lookup", seq=launch.launch_seq):
            reg_sig, glb_sig, shared_sig = state_signature(state)
            call, meta = self._translate(seg, launch, reg_sig, glb_sig,
                                         shared_sig)
        if meta["block"] is not None:
            self.block_stats["tiled"] += 1
        else:
            self.block_stats["scalar"] += 1
            reason = meta.get("block_reason")
            if reason:
                # histogram on the *stable category* only (passes.
                # REFUSAL_REASONS); the free-form detail suffix stays in
                # meta["block_reason"] for diagnostics
                rs = self.block_stats["reasons"]
                cat = refusal_category(reason)
                rs[cat] = rs.get(cat, 0) + 1

        loops = np.array([np.asarray(state.regs[v]).reshape(-1)[0]
                          for v in meta["loop_vars"]] or [0], np.int32)
        # globals stay device-resident between segments: only those still
        # on the host (a launch's first segment, or after a restore) move
        host = [n for n in meta["glb_names"]
                if isinstance(state.globals_[n], np.ndarray)]
        if host:
            with span("segment.upload", seq=launch.launch_seq):
                for n in host:
                    state.globals_[n] = jnp.asarray(state.globals_[n])
        args = [loops] + [state.regs[n] for n in meta["reg_io"]]
        if meta["has_shared"]:
            args.append(state.shared)
        args += [state.globals_[n] for n in meta["glb_names"]]
        outs = list(call(*args))
        regs = {v: state.regs[v] for v in meta["loop_vars"]}
        for n in meta["reg_io"] + meta["new_regs"]:
            regs[n] = outs.pop(0)  # stays on device between segments
        state.regs = regs
        if meta["has_shared"]:
            state.shared = outs.pop(0)
        for n in meta["written"]:
            state.globals_[n] = outs.pop(0)
