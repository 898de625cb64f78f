"""Vectorized-warp backend — software SIMT under ``jax.jit``.

The Tenstorrent "vectorized warp on a core" strategy (paper §4.4): every
block's threads become lanes of dense arrays ``[num_blocks, block_size]``;
divergence is an explicit active-mask; one traced instruction stream serves
all threads.  Each segment is staged and traced once per
(segment, launch-geometry, uniform-scalars, state-signature) key — the
runtime's translation cache (paper §4.2 "the runtime caches these
translated kernels").  Translation goes through ``jax.export``: the trace
is recorded as a StableHLO artifact whose serialized bytes ride into the
cache's disk tier, so a warm process re-compiles the recorded program
instead of re-tracing the Python IR evaluator (the dominant cost).

Register contract (shared with interp and pallas via
:mod:`~repro.core.backends.semantics`): hetIR registers read as **zero**
until first written — a register defined only inside a zero-trip loop, or
first written under a mask, yields zeros for the lanes never reached.
Registers in the incoming state that the segment does not touch pass
through unchanged (``env.regs`` starts as the live register dict).
"""
from __future__ import annotations

import jax

from ..segments import SegNode
from ..tracing import span
from .base import (Backend, HostState, Launch, export_translation,
                   scalar_signature, state_signature)
from .semantics import Env, eval_stmts


class VectorizedBackend(Backend):
    name = "vectorized"

    def _translate(self, seg: SegNode, launch: Launch, state: HostState):
        # content-addressed (fingerprint, not object identity): rebuilding
        # an identical program still hits the shared cache.  The incoming
        # state signature joins the key because the exported artifact is
        # shape/dtype-exact.  For a specialized launch the base key already
        # carries the bound-scalar vector; the scalar signature below stays
        # in the key regardless — generic launches still bake scalars into
        # the trace as constants.
        reg_sig, glb_sig, shared_sig = state_signature(state)
        key = self._cache_key(seg, launch, launch.num_blocks,
                              launch.block_size, scalar_signature(launch),
                              reg_sig, glb_sig, shared_sig)

        scalars = dict(launch.scalars)
        B, T = launch.num_blocks, launch.block_size

        def translate():
            @jax.jit
            def run(regs: dict, shared, glbs: dict):
                env = Env(dict(regs), shared, dict(glbs), scalars, B, T)
                env.lane_shape = (B, T)
                eval_stmts(seg.stmts, env, mask=None)
                return env.regs, env.shared, env.globals

            fn, payload = export_translation(
                run, (dict(state.regs), state.shared, dict(state.globals_)),
                cache=self.cache)
            return fn, (None if payload is None else ("jax-aot", payload))

        return self.cache.get_or_translate(key, translate)

    def run_segment(self, seg: SegNode, state: HostState,
                    launch: Launch) -> None:
        with span("segment.lookup", seq=launch.launch_seq):
            run = self._translate(seg, launch, state)
        regs, shared, glbs = run(state.regs, state.shared, state.globals_)
        # keep state on-device between segments (registers are only pulled
        # to host numpy at snapshot time — Engine.snapshot)
        state.regs = dict(regs)
        state.shared = shared
        state.globals_ = dict(glbs)
