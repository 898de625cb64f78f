"""Backend interface: a backend executes one *segment* at a time.

Between segments all state lives in host numpy arrays (:class:`HostState`) —
this is the paper's design where segment kernels communicate registers and
shared memory "via memory", and it is what makes snapshots backend-neutral
for free.

This module also owns the *persistence contract* for jitted translations
(paper §4.2's cluster-lifetime JIT amortization): the vectorized and pallas
backends trace their segments through ``jax.export`` at translate time and
AOT-compile them, so the translation cache persists both the portable
StableHLO artifact *and* the serialized XLA executable
(``jax.experimental.serialize_executable``) to its
:class:`~repro.core.cache.DiskStore` / :class:`~repro.core.cache.SharedStore`
tiers.  A warm process deserializes the executable directly — skipping the
expensive Python re-trace *and* the XLA compile — and falls back to
recompiling the StableHLO only when executable revival is impossible.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, Optional, Tuple

import numpy as np

from .. import hetir as ir
from ..cache import TranslationCache, global_cache, register_reviver
from ..segments import SegNode


@dataclass
class Launch:
    program: ir.Program
    num_blocks: int
    block_size: int
    scalars: Dict[str, object] = field(default_factory=dict)
    opt_level: int = 0  # pass-pipeline level the body was optimized at
    # launch-time specialization key: the (name, value) uniform scalars
    # bound into the optimized body, () for the generic variant.  Part of
    # every translation-cache key (a specialized segment must never be
    # served a generic translation or vice versa, even though their
    # program fingerprints already differ — the key stays self-describing
    # for store scans and debugging)
    spec_key: Tuple = ()
    # buffer name -> shape tuple for every global buffer bound at launch
    # (the PR 5 remainder: the policy and cache keys were shape-blind).
    # Feeds SpecializationPolicy.consider (two launches differing only in
    # buffer length are distinct specialization candidates) and the block
    # lowering's tiled-buffer legality check (a buffer may only be
    # BlockSpec-tiled when its length is exactly num_blocks * block_size)
    buffer_shapes: Dict[str, Tuple[int, ...]] = field(default_factory=dict)
    # stream-scheduler metadata, set by the session when the launch is
    # enqueued/materialized.  Diagnostic only — NEVER part of a
    # translation-cache key: a translated segment is stream-agnostic, and
    # keying on these would shatter the shared cache per launch
    stream_id: Optional[int] = None
    launch_seq: Optional[int] = None


@dataclass
class HostState:
    regs: Dict[str, np.ndarray]            # [num_blocks, block_size]
    shared: Optional[np.ndarray]           # [num_blocks, shared_size]
    globals_: Dict[str, np.ndarray]        # 1-D buffers


class Backend:
    name = "abstract"

    def __init__(self, cache: Optional[TranslationCache] = None):
        # all backends share one translation cache (paper §4.2: "the runtime
        # caches these translated kernels") unless handed a private one
        self.cache = cache if cache is not None else global_cache()

    def run_segment(self, seg: SegNode, state: HostState,
                    launch: Launch) -> None:
        raise NotImplementedError

    def _cache_key(self, seg: SegNode, launch: Launch,
                   *extra) -> Tuple:
        """Content-addressed translation key: backend, program fingerprint,
        opt level, segment index, the launch-time specialization's
        bound-scalar vector (() = generic), plus backend-specific
        specialization.  ``preload`` filters on the first two components,
        so warm-up and migration revive specialized entries exactly like
        generic ones."""
        return (self.name, ir.program_fingerprint(launch.program),
                launch.opt_level, seg.index,
                tuple(launch.spec_key)) + tuple(extra)

    # Cached per-segment compiled artifacts; exposed for the
    # translation-cost benchmark (the paper's JIT-cost table).
    def translation_cache_size(self) -> int:
        return self.cache.size(self.name)

    def cache_stats(self) -> Dict[str, object]:
        return self.cache.stats()


def scalar_signature(launch: Launch) -> Tuple:
    """Uniform scalars as a hashable, dtype-insensitive key component
    (scalars are baked into traced code as constants)."""
    return tuple(sorted((k, float(v)) for k, v in launch.scalars.items()))


def state_signature(state: HostState) -> Tuple[Tuple, Tuple, Optional[Tuple]]:
    """(reg, global, shared) shape+dtype signatures of the incoming state.
    Jit-compiling backends fold these into the cache key: the exported
    artifact is shape-exact, so the key must be too."""
    reg_sig = tuple((n, tuple(np.shape(state.regs[n])),
                     np.dtype(state.regs[n].dtype).str)
                    for n in sorted(state.regs))
    glb_sig = tuple((n, tuple(np.shape(state.globals_[n])),
                     np.dtype(state.globals_[n].dtype).str)
                    for n in sorted(state.globals_))
    shared_sig = None if state.shared is None else \
        (tuple(np.shape(state.shared)), np.dtype(state.shared.dtype).str)
    return reg_sig, glb_sig, shared_sig


# ---------------------------------------------------------------------------
# jax.export + AOT persistence: serialize the traced StableHLO *and* the
# XLA-compiled executable, so a warm process skips both Python re-tracing
# and the XLA compile (store format v2, the cluster-fabric contract).
# ---------------------------------------------------------------------------

def export_translation(
        jitted, example_args: Tuple,
        cache: Optional[TranslationCache] = None) -> Tuple[Any,
                                                           Optional[Tuple]]:
    """Trace ``jitted`` over ``example_args`` (arrays or ShapeDtypeStructs,
    any pytree) with ``jax.export``, AOT-compile it, and return
    ``(live fn, payload)`` where the live fn is the *compiled* executable
    (ready to call, no deferred first-launch compile) and the payload is
    the ``jax-aot`` triple ``(hlo_blob, exe, argspec)``:

    * ``hlo_blob`` — the portable serialized StableHLO (always present in
      a payload; survives jaxlib upgrades since the runtime tag retires
      version-skewed stores anyway),
    * ``exe`` — ``jax.experimental.serialize_executable.serialize`` output
      for the compiled executable, or ``None`` when executable
      serialization failed (counted via ``cache.note_aot_fallback``; warm
      starts then recompile from the HLO),
    * ``argspec`` — ``(treedef, [(shape, dtype_str), ...])`` of the
      example args, so the HLO-fallback reviver can eagerly AOT-compile
      and the compile cost lands in ``restore_compile_ms`` instead of
      hiding in the first launch.

    Translate-side wall time is split into trace/export vs XLA-compile on
    ``cache`` (``stats()['trace_ms']`` / ``['compile_ms']``).  If export
    itself is unsupported for this computation, fall back to the plain
    jitted fn with no payload (the entry then lives in memory only) and
    record the failure (``stats()['export_fallbacks']`` /
    ``['last_export_error']``) so the lost persistence is diagnosable."""
    import jax

    try:
        from jax import export as jexport
        t0 = time.perf_counter()
        structs = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), np.dtype(a.dtype)),
            example_args)
        exported = jexport.export(jitted)(*structs)
        hlo_blob = exported.serialize()
        t1 = time.perf_counter()
    except Exception as exc:
        if cache is not None:
            cache.note_export_fallback(f"{type(exc).__name__}: {exc}")
        return jitted, None

    flat, treedef = jax.tree.flatten(structs)
    argspec = (treedef, [(tuple(s.shape), np.dtype(s.dtype).str)
                         for s in flat])
    fn, exe = jitted, None
    try:
        # compile the exported module itself: re-lowering ``jitted`` would
        # trace the Python body a second time, which costs as much as the
        # export did (seconds per segment kernel at decode width)
        compiled = jax.jit(exported.call).lower(*structs).compile()
        t2 = time.perf_counter()
        fn = compiled
        try:
            from jax.experimental import serialize_executable
            exe = serialize_executable.serialize(compiled)
        except Exception as exc:
            if cache is not None:
                cache.note_aot_fallback(f"{type(exc).__name__}: {exc}")
    except Exception as exc:
        # AOT lowering failed outright: stay on the lazily-compiling
        # jitted fn; the persisted HLO still spares warm re-traces
        t2 = time.perf_counter()
        if cache is not None:
            cache.note_aot_fallback(f"{type(exc).__name__}: {exc}")
    if cache is not None:
        cache.note_translate_detail(trace_ms=(t1 - t0) * 1e3,
                                    compile_ms=(t2 - t1) * 1e3)
    return fn, (hlo_blob, exe, argspec)


def _revive_exported(blob: bytes):
    import jax
    from jax import export as jexport

    return jax.jit(jexport.deserialize(blob).call)


def _revive_exported_with_meta(payload: Tuple[bytes, Dict]):
    blob, meta = payload
    return _revive_exported(blob), meta


def _revive_aot(payload: Tuple):
    """Revive a ``jax-aot`` payload: deserialize the pickled executable
    (no XLA compile — the fabric's warm-start guarantee) or, when that
    fails (absent / host-topology skew), eagerly recompile from the
    portable StableHLO so the compile cost is attributed to the restore
    (``restore_compile_ms``), not smeared into the first launch."""
    import jax
    from jax import export as jexport
    from ..cache import note_restore_detail

    hlo_blob, exe, argspec = payload
    if exe is not None:
        try:
            from jax.experimental import serialize_executable
            fn = serialize_executable.deserialize_and_load(*exe)
            note_restore_detail(aot=True)
            return fn
        except Exception:
            pass  # fall through to the HLO recompile below
    exported = jexport.deserialize(hlo_blob)
    jitted = jax.jit(exported.call)
    t0 = time.perf_counter()
    try:
        treedef, flat_spec = argspec
        structs = jax.tree.unflatten(
            treedef, [jax.ShapeDtypeStruct(shape, np.dtype(dt))
                      for shape, dt in flat_spec])
        fn = jitted.lower(*structs).compile()
    except Exception:
        fn = jitted  # compile lazily on first launch
    note_restore_detail(aot=False,
                        compile_ms=(time.perf_counter() - t0) * 1e3)
    return fn


def _revive_aot_with_meta(payload: Tuple):
    inner, meta = payload
    return _revive_aot(inner), meta


register_reviver("jax-export", _revive_exported)
register_reviver("jax-export-meta", _revive_exported_with_meta)
register_reviver("jax-aot", _revive_aot)
register_reviver("jax-aot-meta", _revive_aot_with_meta)
