"""Generative differential fuzz harness for the hetIR pass pipeline (the
generator and the check; the tests are ``test_fuzz_*.py``).

Random *well-formed* hetIR programs — loops with constant and dynamic trip
counts, power-of-two and odd multiplies/divides/mods, shifts, predication,
barriers (cross-segment reuse), shared memory, collectives and atomics —
are executed at O0 and at OPT_MAX on the interp and vectorized backends
(where OPT_MAX also exercises launch-time specialization: the auto policy
binds the launch scalars of any program with a barrier-free dynamic-trip
loop, which the generator emits routinely).  A second *memory-op corpus*
(``mem=True`` profile) stresses the alias-aware passes with overlapping
and disjoint LOAD/STORE patterns, including same-buffer read-after-write
inside loops.
The property: **outputs are bit-identical per backend across opt levels**.
The pipeline may remove or rearrange work; it may never change a computed
bit.

The generator is written against a tiny "chooser" interface so the same
program-construction logic backs two harnesses:

* a **fixed-seed corpus** (``_RngChooser`` over ``np.random.Generator``)
  — fully deterministic, no third-party dependency, sized by
  ``HETGPU_FUZZ_EXAMPLES`` (default 210 ≥ the 200-program acceptance bar);
  this is the CI profile;
* a **hypothesis strategy** (``_DrawChooser`` over ``st.data()``) — when
  hypothesis is installed, the same generator becomes a shrinking
  property-based test (``derandomize=True`` keeps it reproducible).

Generator legality rules (what makes a random program *well-formed*):
global/shared indices are always wrapped by a power-of-two bound, so no
backend ever sees an out-of-range access; integer divisors, moduli and
shift amounts are non-zero constants in range; barriers only appear at the
top level (never under @PRED); a value defined under a predicate or
inside a possibly-zero-trip loop only escapes its region when a write is
*guaranteed* before the first read (the predicated loop-carry pattern
below, whose iteration-0 write is unconditional) — otherwise mutation of
pre-declared accumulators is how divergent writes become visible, exactly
the discipline the kernel suite follows.

Bugs this harness (or its construction) has already caught: numpy folding
integer ``x/0`` to 0 while XLA computes a platform value (fold guard in
``passes.fold_constants``), and XLA CPU contracting mul+add chains into
hardware FMAs *graph-shape-dependently*, so a rolled loop and its unrolled
form disagreed in the low bits (product pinning in
``backends/semantics._mul_exact``).
"""
from __future__ import annotations

import os

import numpy as np

from repro.core import Engine, OPT_MAX, TranslationCache, get_backend
from repro.core import hetir as ir
from repro.core.hetir import Builder, Ptr, Scalar

try:
    import hypothesis
    from hypothesis import strategies as st
except ImportError:  # container without hypothesis: corpus still runs
    hypothesis = None

N_EXAMPLES = int(os.environ.get("HETGPU_FUZZ_EXAMPLES", "210"))
MEM_EXAMPLES = int(os.environ.get("HETGPU_FUZZ_MEM_EXAMPLES", "210"))
ATTN_EXAMPLES = int(os.environ.get("HETGPU_FUZZ_ATTN_EXAMPLES", "70"))
CHUNKS = 7
SEED0 = 20260728
MEM_SEED0 = 20270115
ATTN_SEED0 = 20260807
BACKENDS = ("interp", "vectorized")


# ---------------------------------------------------------------------------
# chooser interface: one generator, two harnesses
# ---------------------------------------------------------------------------


class _RngChooser:
    def __init__(self, rng: np.random.Generator):
        self.rng = rng

    def randint(self, lo: int, hi: int) -> int:
        """Uniform int in [lo, hi] inclusive."""
        return int(self.rng.integers(lo, hi + 1))

    def pick(self, seq):
        return seq[self.randint(0, len(seq) - 1)]

    def chance(self, p: float) -> bool:
        return float(self.rng.random()) < p


class _DrawChooser:
    def __init__(self, draw):
        self.draw = draw

    def randint(self, lo: int, hi: int) -> int:
        return self.draw(st.integers(min_value=lo, max_value=hi))

    def pick(self, seq):
        return seq[self.randint(0, len(seq) - 1)]

    def chance(self, p: float) -> bool:
        return self.draw(st.booleans()) if 0 < p < 1 else p >= 1


# ---------------------------------------------------------------------------
# program generator
# ---------------------------------------------------------------------------

_INT_CONSTS = (1, 2, 3, 4, 5, 7, 8, 15, 16, 31, 32, 100, -1, -3, -8, 0)
_ODD_DIVS = (3, 5, 6, 7, 9)
_POW2_DIVS = (2, 4, 8, 16)
_F32_CONSTS = (0.0, 1.0, -1.0, 0.5, 2.0, 4.0, -0.25, 3.1415927,
               1e6, 1e-6, -3.0, 8.0)


class _ProgramGen:
    """Builds one random well-formed hetIR program via a chooser.

    With ``mem=True`` the generator shifts into its *memory-op profile*:
    programs are salted with LOAD/STORE statements over overlapping and
    disjoint buffer access patterns — including same-buffer
    read-after-write inside loops, the exact shapes that make the
    alias-aware ``hoist_invariant_loads`` pass dangerous — and the input
    buffers ``F``/``I`` join the compared outputs so a misplaced store is
    caught even when no later load observes it.  Plain (non-atomic) store
    indices are always *launch-injective* — a bijection of the global
    thread id (odd-stride affine or xor mod the pow-2 launch size),
    optionally shifted by a uniform loop-term — because colliding plain
    stores have no defined winner across backends (XLA scatter picks an
    arbitrary duplicate; the interpreter is last-thread-wins).  Loads may
    target anything in range.  ``G`` is never stored to, keeping a
    provably alias-free invariant-load candidate in every program."""

    def __init__(self, ch, tag: str, mem: bool = False, attn: bool = False):
        self.ch = ch
        self.tag = tag
        self.mem = mem
        self.attn = attn
        self.ops_budget = 60

    # -- expression pools (scoped: regions push/pop their additions) -------
    def _push_scope(self):
        return len(self.ints), len(self.floats), len(self.bools)

    def _pop_scope(self, mark):
        del self.ints[mark[0]:]
        del self.floats[mark[1]:]
        del self.bools[mark[2]:]

    def _spend(self, n: int = 1) -> bool:
        self.ops_budget -= n
        return self.ops_budget > 0

    # -- leaves ------------------------------------------------------------
    def _wrap_idx(self, v):
        """In-range non-negative index: floor-mod by a power of two ≤ N."""
        b, ch = self.b, self.ch
        bound = ch.pick([p for p in (4, 8, 16, 32, 64) if p <= self.N]
                        or [self.N])
        return v % b.const(bound)

    def int_expr(self, depth: int = 0):
        b, ch = self.b, self.ch
        if depth >= 3 or not self._spend() or ch.chance(0.35):
            if ch.chance(0.25):
                return b.const(ch.pick(_INT_CONSTS))
            return ch.pick(self.ints)
        kind = ch.pick(["add", "sub", "mul", "divmod", "shift", "bit",
                        "minmax", "neg", "select", "cvt", "u32", "load"])
        if kind in ("add", "sub", "mul"):
            a, c = self.int_expr(depth + 1), self.int_expr(depth + 1)
            return a + c if kind == "add" else \
                (a - c if kind == "sub" else a * c)
        if kind == "divmod":
            a = self.int_expr(depth + 1)
            d = b.const(ch.pick(_ODD_DIVS + _POW2_DIVS))
            return a / d if ch.chance(0.5) else a % d
        if kind == "shift":
            a = self.int_expr(depth + 1)
            k = b.const(ch.randint(0, 8))
            return a << k if ch.chance(0.5) else a >> k
        if kind == "bit":
            a, c = self.int_expr(depth + 1), self.int_expr(depth + 1)
            w = ch.pick("&|^")
            return a & c if w == "&" else (a | c if w == "|" else a ^ c)
        if kind == "minmax":
            a, c = self.int_expr(depth + 1), self.int_expr(depth + 1)
            return b.minimum(a, c) if ch.chance(0.5) else b.maximum(a, c)
        if kind == "neg":
            return -self.int_expr(depth + 1)
        if kind == "select":
            return b.select(self.bool_expr(depth + 1),
                            self.int_expr(depth + 1),
                            self.int_expr(depth + 1))
        if kind == "cvt":
            return self.float_expr(depth + 1).astype(ir.I32)
        if kind == "u32":
            u = self.int_expr(depth + 1).astype(ir.U32)
            w = ch.pick(["add", "shr", "div", "mul"])
            if w == "add":
                u = u + b.const(ch.pick((1, 2, 8)), ir.U32)
            elif w == "shr":
                u = u >> b.const(ch.randint(0, 8), ir.U32)
            elif w == "div":
                u = u / b.const(ch.pick(_POW2_DIVS), ir.U32)
            else:
                u = u * b.const(ch.pick((3, 4)), ir.U32)
            return u.astype(ir.I32)
        # load from the int buffer at a wrapped index
        return self.b.load("I", self._wrap_idx(self.int_expr(depth + 1)))

    def float_expr(self, depth: int = 0):
        b, ch = self.b, self.ch
        if depth >= 3 or not self._spend() or ch.chance(0.35):
            if ch.chance(0.25):
                return b.const(ch.pick(_F32_CONSTS), ir.F32)
            return ch.pick(self.floats)
        kind = ch.pick(["add", "sub", "mul", "div", "minmax", "un",
                        "select", "cvt", "fma", "load"])
        if kind in ("add", "sub", "mul", "div"):
            a, c = self.float_expr(depth + 1), self.float_expr(depth + 1)
            if kind == "div" and ch.chance(0.5):
                c = b.const(ch.pick((2.0, 4.0, 0.5, 3.0, -8.0)), ir.F32)
            return a + c if kind == "add" else \
                (a - c if kind == "sub" else
                 (a * c if kind == "mul" else a / c))
        if kind == "minmax":
            a, c = self.float_expr(depth + 1), self.float_expr(depth + 1)
            return b.minimum(a, c) if ch.chance(0.5) else b.maximum(a, c)
        if kind == "un":
            a = self.float_expr(depth + 1)
            w = ch.pick(["neg", "sqrt", "exp"])
            return -a if w == "neg" else \
                (b.sqrt(a) if w == "sqrt" else b.exp(a))
        if kind == "select":
            return b.select(self.bool_expr(depth + 1),
                            self.float_expr(depth + 1),
                            self.float_expr(depth + 1))
        if kind == "cvt":
            return self.int_expr(depth + 1).astype(ir.F32)
        if kind == "fma":
            return b.fma(self.float_expr(depth + 1),
                         self.float_expr(depth + 1),
                         self.float_expr(depth + 1))
        return b.load(ch.pick(("F", "G")),
                      self._wrap_idx(self.int_expr(depth + 1)))

    def bool_expr(self, depth: int = 0):
        b, ch = self.b, self.ch
        if depth >= 3 or not self._spend() or (self.bools
                                               and ch.chance(0.4)):
            if self.bools and ch.chance(0.6):
                return ch.pick(self.bools)
            a, c = self.int_expr(depth + 1), self.int_expr(depth + 1)
            w = ch.pick(["lt", "le", "gt", "ge", "eq", "ne"])
            return {"lt": lambda: a < c, "le": lambda: a <= c,
                    "gt": lambda: a > c, "ge": lambda: a >= c,
                    "eq": lambda: a.eq(c), "ne": lambda: a.ne(c)}[w]()
        if ch.chance(0.4):
            a, c = self.float_expr(depth + 1), self.float_expr(depth + 1)
            return a < c if ch.chance(0.5) else a >= c
        p, q = self.bool_expr(depth + 1), self.bool_expr(depth + 1)
        w = ch.pick("&|^")
        return p & q if w == "&" else (p | q if w == "|" else p ^ q)

    def _store_idx(self, j=None):
        """Launch-injective store index: a bijection of gid over the pow-2
        launch size (odd-stride affine / xor mask), optionally shifted by
        a uniform multiple of the loop variable — overlapping *across*
        iterations (read-after-write), never colliding across threads."""
        b, ch = self.b, self.ch
        N = self.N
        w = ch.pick(["id", "aff", "xor"])
        if w == "aff":
            idx = self.gid * b.const(ch.pick((3, 5, 7))) \
                + b.const(ch.randint(0, 7))
        elif w == "xor":
            idx = self.gid ^ b.const(ch.randint(0, N - 1))
        else:
            idx = self.gid
        if j is not None and ch.chance(0.5):
            idx = idx + j * b.const(ch.pick((1, 2, 4)))
        return idx % b.const(N)

    def gen_memrw(self, j=None) -> None:
        """Same-buffer store-then-load (RAW when indices overlap, disjoint
        when they don't — the chooser decides per program)."""
        b, ch = self.b, self.ch
        if ch.chance(0.5):
            buf = ch.pick(("OutF", "F"))
            b.store(buf, self._store_idx(j), self.float_expr())
            self.floats.append(
                b.load(buf, self._wrap_idx(self.int_expr())))
        else:
            buf = ch.pick(("OutI", "I"))
            b.store(buf, self._store_idx(j), self.int_expr())
            self.ints.append(
                b.load(buf, self._wrap_idx(self.int_expr())))

    def gen_memloop(self, depth: int) -> None:
        """A loop (constant or dynamic trip) whose body stores and loads
        the same buffer each iteration, plus an invariant-load candidate
        over the never-stored ``G`` — the memory-motion torture shape."""
        b, ch = self.b, self.ch
        count = "t" if ch.chance(0.3) else ch.randint(1, 10)
        mark = self._push_scope()
        with b.loop(count, hint="M") as j:
            self.gen_memrw(j)
            if ch.chance(0.5):
                inv = b.load("G",
                             b.const(ch.randint(0, min(3, self.N - 1))))
                b.assign(ch.pick(self.mut_f), ch.pick(self.mut_f) + inv)
            if self.use_shared and ch.chance(0.4):
                tid = b.thread_id()
                b.store_shared(tid, self.float_expr())
                self.floats.append(b.load_shared(
                    (tid + b.const(ch.randint(0, 3)))
                    % b.const(self.block)))
            if ch.chance(0.5):
                self.gen_stmts(1, depth + 1, top=False)
        self._pop_scope(mark)

    def gen_attn_tile(self) -> None:
        """The attention inner shape (``attn=True`` profile): a shared
        score tile, an EXP/REDUCE_MAX online-softmax fold with running
        max/correction carried across iterations, and a barriered
        PV-style accumulate — the exact collective-over-shared-tile
        composition the zoo's ``attn_decode`` is built from, which the
        general profile never emits (its collectives are int-typed and
        top-level only).  Collectives stay outside @PRED so every lane
        is active: the cross-backend property below depends on the
        lane-order folds seeing identical active sets."""
        b, ch = self.b, self.ch
        tid = b.thread_id()
        count = "t" if ch.chance(0.25) else ch.randint(1, 3)
        m = ch.pick(self.mut_f)
        mark = self._push_scope()
        with b.loop(count, hint="A"):
            sv = self.float_expr()
            mn = b.maximum(m, b.reduce_max(sv))
            p = b.exp(sv - mn)
            corr = b.exp(m - mn)
            b.assign(m, mn)
            b.store_shared(tid, p)
            b.barrier("attn-p")
            pv = b.load_shared((tid + b.const(ch.randint(0, 3)))
                               % b.const(self.block))
            acc = ch.pick(self.mut_f)
            b.assign(acc, acc * corr + pv)
            if ch.chance(0.5):
                other = ch.pick(self.mut_f)
                b.assign(other, other + b.scan_add(p))
            b.barrier("attn-c")
        self._pop_scope(mark)

    # -- statements --------------------------------------------------------
    def gen_stmts(self, n: int, depth: int, top: bool) -> None:
        for _ in range(n):
            if self.ops_budget <= 0:
                return
            self.gen_stmt(depth, top)

    def gen_stmt(self, depth: int, top: bool) -> None:
        b, ch = self.b, self.ch
        kinds = ["assign", "assign", "store", "pred"]
        if self.mem:
            kinds += ["memrw", "memrw"]
        if depth == 0:
            kinds += ["loop", "collective"]
            if not self.attn:
                # the attention corpus is compared *across* backends,
                # and an atomic racing a later plain store to the same
                # slot has no defined winner between block-serial
                # (interp) and lockstep (vectorized) execution — a real
                # GPU gives it no defined order either
                kinds += ["atomic"]
            if self.mem:
                kinds += ["memloop"]
            if self.attn:
                kinds += ["attn_tile", "attn_tile", "fcollective"]
        kind = ch.pick(kinds)
        if kind == "assign":
            if ch.chance(0.5):
                b.assign(ch.pick(self.mut_f), self.float_expr())
            else:
                b.assign(ch.pick(self.mut_i), self.int_expr())
        elif kind == "store":
            if ch.chance(0.5):
                b.store("OutF", self.gid, self.float_expr())
            else:
                b.store("OutI", self.gid, self.int_expr())
        elif kind == "pred":
            cond = self.bool_expr()
            mark = self._push_scope()
            with b.when(cond):
                self.gen_stmts(ch.randint(1, 2), depth + 1, top=False)
            self._pop_scope(mark)
        elif kind == "memrw":
            self.gen_memrw()
        elif kind == "memloop":
            self.gen_memloop(depth)
        elif kind == "attn_tile":
            self.gen_attn_tile()
        elif kind == "fcollective":
            # float collectives (the zoo's softmax/normalizer primitives)
            w = ch.pick(["reduce_add", "reduce_max", "scan_add"])
            v = self.float_expr()
            self.floats.append({"reduce_add": b.reduce_add,
                                "reduce_max": b.reduce_max,
                                "scan_add": b.scan_add}[w](v))
        elif kind == "loop":
            self.gen_loop(depth, top)
        elif kind == "atomic":
            b.atomic_add("OutI", self._wrap_idx(self.int_expr()),
                         self.int_expr())
        else:  # collective
            w = ch.pick(["reduce", "ballot", "vote"])
            if w == "reduce":
                v = b.reduce_add(self.int_expr())
            elif w == "ballot":
                v = b.ballot(self.bool_expr())
            else:
                v = b.vote_any(self.bool_expr()).astype(ir.I32)
            self.ints.append(v)

    def gen_loop(self, depth: int, top: bool) -> None:
        b, ch = self.b, self.ch
        kind = ch.pick(["const", "const", "dyn"] + (["barrier"] if top
                                                    else []))
        count = ch.randint(1, 10) if kind != "dyn" else "t"
        mark = self._push_scope()
        with b.loop(count, hint="L") as j:
            if kind != "dyn":
                self.ints.append(j)  # defined through and after the loop
            if ch.chance(0.4):
                # predicated loop-carry: the write is guaranteed in
                # iteration 0 (j == 0) and may be skipped later, so reads
                # after the @PRED observe the carried previous-iteration
                # value — the pattern a buggy unroll renames apart
                # (review-found miscompile, now a generator staple)
                cond = j.eq(b.const(0)) | self.bool_expr()
                pmark = self._push_scope()
                with b.when(cond):
                    carried = self.float_expr()
                self._pop_scope(pmark)
                b.assign(ch.pick(self.mut_f),
                         ch.pick(self.mut_f) + carried)
            self.gen_stmts(ch.randint(1, 3), depth + 1, top=False)
            if kind == "barrier":
                b.store("OutF", self.gid,
                        ch.pick(self.mut_f) + j.astype(ir.F32))
                b.barrier("iter")
        self._pop_scope(mark)
        if kind == "const":
            self.ints.append(j)  # post-loop read sees the final value

    # -- whole program -----------------------------------------------------
    def build(self):
        ch = self.ch
        grid = ch.pick((1, 2))
        block = ch.pick((4, 8, 16))
        self.N = grid * block
        self.block = block
        # the attention profile is *about* shared-tile traffic: always on
        use_shared = self.use_shared = True if self.attn else ch.chance(0.3)
        b = Builder(f"fuzz_{self.tag}",
                    [Ptr("F"), Ptr("G"), Ptr("I", ir.I32), Ptr("OutF"),
                     Ptr("OutI", ir.I32), Scalar("s"), Scalar("t"),
                     Scalar("fs", ir.F32)],
                    shared_size=block if use_shared else 0)
        self.b = b
        self.gid = b.global_id(0)
        self.ints = [self.gid, b.thread_id(), b.block_id(), b.param("s"),
                     b.block_dim()]
        self.floats = [b.param("fs"),
                       b.load("F", self._wrap_idx(self.gid))]
        self.bools = []
        # pre-declared accumulators: the only values divergent writes may
        # mutate, so every read is defined on every path
        self.mut_f = [b.var(self.floats[ch.randint(0, 1)], hint="mf"),
                      b.var(b.const(0.0, ir.F32), hint="mf")]
        self.mut_i = [b.var(b.const(ch.pick(_INT_CONSTS)), hint="mi"),
                      b.var(self.gid, hint="mi")]
        phases = ch.randint(1, 3)
        for p in range(phases):
            self.gen_stmts(ch.randint(2, 5), depth=0, top=True)
            if use_shared and ch.chance(0.6):
                tid = b.thread_id()
                b.store_shared(tid, self.float_expr())
                b.barrier(f"sh{p}")
                self.floats.append(b.load_shared(
                    (tid + b.const(ch.randint(0, 3))) % b.const(block)))
            elif p < phases - 1:
                b.barrier(f"ph{p}")  # cross-segment value reuse
        b.store("OutF", self.gid, ch.pick(self.mut_f) + self.float_expr())
        b.store("OutI", self.gid, ch.pick(self.mut_i) ^ self.int_expr())
        prog = b.done()

        args_seed = ch.randint(0, 2 ** 31 - 1)
        rng = np.random.default_rng(args_seed)
        args = {
            "F": rng.normal(size=self.N).astype(np.float32),
            "G": rng.normal(size=self.N).astype(np.float32),
            "I": rng.integers(-100, 100, size=self.N).astype(np.int32),
            "OutF": np.zeros(self.N, np.float32),
            "OutI": np.zeros(self.N, np.int32),
            "s": ch.randint(1, 5),
            "t": ch.randint(0, 4),   # dynamic trip counts include zero
            "fs": np.float32(rng.normal()),
        }
        # the memory profile stores into F / I too: compare them as well,
        # so a misplaced (hoisted/reordered) store is caught even when no
        # later load happens to observe it
        outs = ("OutF", "OutI", "F", "I") if self.mem \
            else ("OutF", "OutI")
        return prog, args, grid, block, outs


# ---------------------------------------------------------------------------
# the differential property
# ---------------------------------------------------------------------------


def _check_differential(prog, args, grid, block, outs, cache,
                        backends=BACKENDS, note="", cross=False):
    """O0 vs OPT_MAX must be bit-identical per backend (NaNs compare
    positionally equal).  With ``cross=True``, O0 results must also be
    bit-identical *across* backends — the portable-exp / lane-order-fold
    contract the zoo's bit-exact oracles rely on."""
    per_backend = {}
    for backend in backends:
        results = []
        for level in (0, OPT_MAX):
            eng = Engine(prog, get_backend(backend, cache=cache),
                         grid, block, dict(args), opt_level=level)
            assert eng.run(), f"{note}: {backend} O{level} did not finish"
            results.append([np.asarray(eng.result(o)) for o in outs])
        for o, r0, r1 in zip(outs, results[0], results[1]):
            np.testing.assert_array_equal(
                r0, r1,
                err_msg=(f"{note}: {backend} O0 vs O{OPT_MAX} differ in "
                         f"{o}\n{prog.to_text()}"))
        per_backend[backend] = results[0]
    if cross and len(backends) > 1:
        base = backends[0]
        for backend in backends[1:]:
            for o, r0, r1 in zip(outs, per_backend[base],
                                 per_backend[backend]):
                np.testing.assert_array_equal(
                    r0, r1,
                    err_msg=(f"{note}: {base} vs {backend} differ in "
                             f"{o}\n{prog.to_text()}"))


def _corpus_case(seed: int):
    gen = _ProgramGen(_RngChooser(np.random.default_rng(seed)), str(seed))
    return gen.build()


def _mem_corpus_case(seed: int):
    gen = _ProgramGen(_RngChooser(np.random.default_rng(seed)),
                      f"m{seed}", mem=True)
    return gen.build()


def _attn_corpus_case(seed: int):
    gen = _ProgramGen(_RngChooser(np.random.default_rng(seed)),
                      f"a{seed}", attn=True)
    return gen.build()


def run_corpus_chunk(corpus: str, chunk: int) -> None:
    """Run one chunk of a fixed-seed corpus through the differential
    check.  ``corpus`` is ``"main"`` (N_EXAMPLES programs), ``"mem"``
    (the memory-op profile, MEM_EXAMPLES) or ``"attn"`` (the
    attention-shaped profile, ATTN_EXAMPLES, also checked *across*
    backends: interp and vectorized must produce the same bits, which is
    exactly the property the portable software EXP exists to provide —
    np.exp vs XLA's exp diverge on ~40% of float32 inputs).  Each corpus
    is split into CHUNKS chunks, each its own test case, spread over
    several test files so that parallel test workers share them."""
    total, seed0, make, kw = {
        "main": (N_EXAMPLES, SEED0, _corpus_case, {}),
        "mem": (MEM_EXAMPLES, MEM_SEED0, _mem_corpus_case, {}),
        "attn": (ATTN_EXAMPLES, ATTN_SEED0, _attn_corpus_case,
                 {"cross": True}),
    }[corpus]
    per = (total + CHUNKS - 1) // CHUNKS
    cache = TranslationCache(capacity=4 * per)
    for i in range(per):
        seed = seed0 + chunk * per + i
        prog, args, grid, block, outs = make(seed)
        _check_differential(prog, args, grid, block, outs, cache,
                            note=f"{corpus} seed {seed}", **kw)
