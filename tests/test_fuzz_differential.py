"""Differential fuzz harness: structural guarantees of the generator, a
fast smoke subset and the hypothesis profile.  The fixed-seed corpora run
in ``test_fuzz_corpus_*.py`` (see :mod:`fuzz_corpus`)."""
from __future__ import annotations

import os

import pytest

from repro.core import TranslationCache
from repro.core import hetir as ir

from fuzz_corpus import (ATTN_SEED0, MEM_EXAMPLES, MEM_SEED0, N_EXAMPLES,
                         SEED0, _attn_corpus_case, _check_differential,
                         _corpus_case, _DrawChooser, _mem_corpus_case,
                         _ProgramGen, hypothesis)

if hypothesis is not None:
    from hypothesis import strategies as st


def test_fuzz_attention_corpus_actually_emits_softmax_shapes():
    """Structural guarantee for the attention profile: across a sample,
    programs contain EXP, the float collectives (REDUCE_MAX/REDUCE_ADD/
    SCAN_ADD), shared-memory traffic and barriers *inside* loops."""
    import repro.core.hetir as hir

    opcodes = set()
    barrier_in_loop = 0

    def walk(body, in_loop):
        nonlocal barrier_in_loop
        for s in body:
            if isinstance(s, hir.Op):
                opcodes.add(s.opcode)
            elif isinstance(s, hir.Barrier):
                barrier_in_loop += bool(in_loop)
            elif isinstance(s, hir.Loop):
                walk(s.body, True)
            elif isinstance(s, hir.Pred):
                walk(s.body, in_loop)

    for i in range(30):
        prog, _, _, _, _ = _attn_corpus_case(ATTN_SEED0 + i)
        walk(prog.body, False)
    assert {ir.EXP, ir.REDUCE_MAX, ir.SCAN_ADD,
            ir.ST_SHARED, ir.LD_SHARED} <= opcodes, opcodes
    assert barrier_in_loop >= 10, "no barriered shared-tile loops emitted"


def test_fuzz_memory_corpus_meets_acceptance_size():
    if "HETGPU_FUZZ_MEM_EXAMPLES" in os.environ and MEM_EXAMPLES < 200:
        pytest.skip("memory corpus size deliberately overridden below "
                    "the acceptance bar (local iteration)")
    assert MEM_EXAMPLES >= 200, \
        "acceptance: >= 200 memory-op programs through the differential"


def test_fuzz_memory_corpus_actually_emits_memory_patterns():
    """Structural guarantee that the profile does what it claims: across
    a sample of the corpus there are loops whose body stores AND loads
    the same buffer (the read-after-write-in-loop pattern), and both F/I
    (input) and OutF/OutI (output) buffers get written."""
    import repro.core.hetir as hir

    raw_loops = 0
    written = set()
    for i in range(40):
        prog, _, _, _, _ = _mem_corpus_case(MEM_SEED0 + i)

        def loop_bodies(body):
            for s in body:
                if isinstance(s, hir.Loop):
                    yield s.body
                    yield from loop_bodies(s.body)
                elif isinstance(s, hir.Pred):
                    yield from loop_bodies(s.body)

        for body in loop_bodies(prog.body):
            reads, writes = hir.body_global_accesses(body)
            written |= writes
            if reads & writes:
                raw_loops += 1
    assert raw_loops >= 5, "no same-buffer read-after-write loops emitted"
    assert {"F", "I"} & written and {"OutF", "OutI"} & written


@pytest.mark.fast
def test_fuzz_differential_smoke():
    """Ten seeds, interp only — the seconds-fast marker subset."""
    cache = TranslationCache()
    for i in range(10):
        seed = SEED0 + 10_000 + i
        prog, args, grid, block, outs = _corpus_case(seed)
        _check_differential(prog, args, grid, block, outs, cache,
                            backends=("interp",), note=f"seed {seed}")


def test_fuzz_generator_is_deterministic():
    """Same seed → same program (the corpus is a *fixed* corpus: a CI
    failure reproduces locally from the seed in the message alone)."""
    a = _corpus_case(SEED0)[0]
    b = _corpus_case(SEED0)[0]
    assert ir.program_fingerprint(a) == ir.program_fingerprint(b)


def test_fuzz_corpus_meets_acceptance_size():
    if "HETGPU_FUZZ_EXAMPLES" in os.environ and N_EXAMPLES < 200:
        pytest.skip("corpus size deliberately overridden below the "
                    "acceptance bar (local iteration)")
    assert N_EXAMPLES >= 200, \
        "acceptance: >= 200 fuzzed programs through the differential check"


# ---------------------------------------------------------------------------
# hypothesis strategy over the same generator (shrinks; CI installs it)
# ---------------------------------------------------------------------------

if hypothesis is not None:

    @st.composite
    def hetir_programs(draw):
        """Strategy producing (program, args, grid, block, outs)."""
        return _ProgramGen(_DrawChooser(draw), "hyp").build()

    @hypothesis.settings(max_examples=25, deadline=None,
                         derandomize=True, database=None)
    @hypothesis.given(case=hetir_programs())
    def test_fuzz_differential_hypothesis(case):
        prog, args, grid, block, outs = case
        _check_differential(prog, args, grid, block, outs,
                            TranslationCache(), backends=("interp",),
                            note="hypothesis")
