"""Model-zoo conformance, migration and fabric tests (ISSUE 10).

The zoo kernels (`repro.zoo`) are faithful hetIR reductions of the
repo's real workloads, each with a *bit-exact* NumPy oracle — so unlike
the reference-model tests, everything here asserts
``np.testing.assert_array_equal``: same bits on interp, vectorized and
pallas, at O0 and OPT_MAX, before and after a mid-kernel migration.
"""
import itertools

import numpy as np
import pytest

import repro.zoo as zoo  # noqa: F401  (import registers the zoo kernels)
from repro.core import Engine, HetSession, Snapshot, get_backend
from repro.core import kernels_suite as ks
from repro.core.backends.pallas_backend import PallasBackend
from repro.core.backends.portable_math import (EXP_MAX_INPUT, EXP_MIN_INPUT,
                                               exp_jnp, exp_np)
from repro.core.passes import OPT_MAX, REFUSAL_REASONS, refusal_category

ZOO_NAMES = sorted(zoo.ZOO)
BACKENDS = ["interp", "vectorized", "pallas"]


def _launch(name, seed=0):
    return ks.example_launch(name, rng=np.random.default_rng(seed))


# ---------------------------------------------------------------------------
# conformance sweep: 4 kernels x 3 backends x {O0, OPT_MAX}, bit-identical
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,backend,opt",
                         [(n, b, o) for n in ZOO_NAMES for b in BACKENDS
                          for o in (0, OPT_MAX)])
def test_zoo_conformance(name, backend, opt):
    prog, oracle, grid, block, args, outs = _launch(name)
    expect = oracle({k: (np.array(v, copy=True)
                         if isinstance(v, np.ndarray) else v)
                     for k, v in args.items()})
    eng = Engine(prog, get_backend(backend), grid, block, dict(args),
                 opt_level=opt)
    assert eng.run()
    for o in outs:
        np.testing.assert_array_equal(
            np.asarray(eng.result(o)), np.asarray(expect[o]),
            err_msg=f"{name} on {backend} at O{opt}: {o} not bit-identical")


# ---------------------------------------------------------------------------
# mid-kernel checkpoint / migrate of attn_decode, both directions
# ---------------------------------------------------------------------------

def _attn_expect(args, oracle):
    return oracle({k: (np.array(v, copy=True)
                       if isinstance(v, np.ndarray) else v)
                   for k, v in args.items()})["O"]


@pytest.mark.parametrize("src,dst",
                         list(itertools.permutations(BACKENDS, 2)))
def test_attn_decode_migrates_mid_softmax(src, dst):
    """Pause attn_decode inside the online-softmax tile loop (its m/l/acc
    state lives in the regfile, the probability tile in shared memory),
    snapshot, resume on the other backend — output stays bit-identical
    to the oracle."""
    prog, oracle, grid, block, args, _outs = _launch("attn_decode")
    expect = _attn_expect(args, oracle)

    eng = Engine(prog, get_backend(src), grid, block, dict(args))
    assert not eng.run(max_segments=3), "should pause mid-decode"
    blob = eng.snapshot().to_bytes()
    eng2 = Engine.resume(prog, get_backend(dst), Snapshot.from_bytes(blob))
    assert eng2.run()
    np.testing.assert_array_equal(np.asarray(eng2.result("O")), expect)


def test_attn_decode_double_migration_chain():
    """interp -> vectorized -> pallas across two tile boundaries (the
    serve_decode --zoo demo's exact path), still bit-identical."""
    prog, oracle, grid, block, args, _outs = _launch("attn_decode")
    expect = _attn_expect(args, oracle)
    e1 = Engine(prog, get_backend("interp"), grid, block, dict(args))
    assert not e1.run(max_segments=2)
    e2 = Engine.resume(prog, get_backend("vectorized"), e1.snapshot())
    assert not e2.run(max_segments=2)
    e3 = Engine.resume(prog, get_backend("pallas"), e2.snapshot())
    assert e3.run()
    np.testing.assert_array_equal(np.asarray(e3.result("O")), expect)


# ---------------------------------------------------------------------------
# SharedStore fabric: a fresh node warm-starts the whole zoo
# ---------------------------------------------------------------------------

def test_zoo_sharedstore_warm_start(tmp_path):
    fabric = str(tmp_path / "fabric")
    hot = HetSession("vectorized", shared=fabric)
    for name in ZOO_NAMES:
        prog, _oracle, grid, block, args, _outs = _launch(name)
        rep = hot.warmup([(prog, args)], grids=((grid, block),))
        assert rep["errors"] == 0, rep
        assert rep["translated"] > 0, f"{name}: nothing translated to publish"

    cold = HetSession("vectorized", shared=fabric)
    for name in ZOO_NAMES:
        prog, _oracle, grid, block, args, _outs = _launch(name)
        rep = cold.warmup([(prog, args)], grids=((grid, block),))
        assert rep["errors"] == 0, rep
        assert rep["translated"] == 0, \
            f"{name}: warm node re-translated instead of fetching"
        assert rep["restored"] > 0 and rep["fetched"] == rep["restored"], rep


# ---------------------------------------------------------------------------
# block_lower refusal reasons: stable names, every zoo kernel accounted for
# ---------------------------------------------------------------------------

def test_zoo_block_stats_refusals_are_named():
    """Each zoo kernel either block-tiles or refuses for a *documented*
    reason — block_stats histogram keys must come from the stable
    REFUSAL_REASONS vocabulary (satellite: no more free-form strings)."""
    for name in ZOO_NAMES:
        prog, _oracle, grid, block, args, _outs = _launch(name)
        # the process-wide cache: the conformance runs above already
        # translated these kernels, and block_stats count executions
        backend = PallasBackend()
        eng = Engine(prog, backend, grid, block, dict(args))
        assert eng.run()
        stats = backend.block_stats
        assert stats["tiled"] or stats["reasons"], \
            f"{name}: scalar fallback with no recorded reason"
        unknown = set(stats["reasons"]) - set(REFUSAL_REASONS)
        assert not unknown, f"{name}: undocumented refusal names {unknown}"


def test_refusal_category_contract():
    assert refusal_category("collective:REDUCE_ADD") == "collective"
    assert refusal_category("shared-memory") == "shared-memory"
    for r in REFUSAL_REASONS:
        assert refusal_category(r) == r  # canonical names are categories


# ---------------------------------------------------------------------------
# the portable EXP that makes the above possible
# ---------------------------------------------------------------------------

def test_portable_exp_bit_identity():
    """exp_np (interp) and exp_jnp (vectorized/pallas trace) agree bit
    for bit across the full float32 input range, including the overflow
    and flush-to-zero thresholds and non-finite inputs."""
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(7)
    xs = np.concatenate([
        rng.uniform(-110.0, 95.0, size=50_000).astype(np.float32),
        rng.standard_normal(20_000).astype(np.float32) * 10,
        np.array([0.0, -0.0, np.inf, -np.inf, np.nan,
                  float(EXP_MAX_INPUT), float(EXP_MIN_INPUT),
                  np.nextafter(np.float32(EXP_MAX_INPUT), np.float32(200)),
                  np.nextafter(np.float32(EXP_MIN_INPUT), np.float32(-200)),
                  -87.33655, -87.4, -103.9, 88.72, 1.0, -1.0], np.float32),
    ])
    got_np = exp_np(xs)
    got_jit = np.asarray(jax.jit(exp_jnp)(jnp.asarray(xs)))
    np.testing.assert_array_equal(got_np.view(np.uint32),
                                  got_jit.view(np.uint32))
    # sanity: accurate, not just self-consistent
    finite = np.isfinite(xs) & (xs > -80) & (xs < 80)
    ref = np.exp(xs[finite].astype(np.float64))
    rel = np.abs(got_np[finite].astype(np.float64) - ref) / ref
    assert float(rel.max()) < 1e-6


# ---------------------------------------------------------------------------
# registry hygiene: the zoo never leaks into the closed suite
# ---------------------------------------------------------------------------

def test_zoo_registry_is_namespaced():
    assert set(ks.registered_examples("zoo")) == set(ZOO_NAMES)
    assert not set(ZOO_NAMES) & set(ks.SUITE)
    assert not set(ZOO_NAMES) & set(ks.EXAMPLES)
    for name in ZOO_NAMES:
        assert ks.lookup(name) is zoo.ZOO[name]
    with pytest.raises(ValueError):
        ks.register_kernel("rogue", zoo.attn_decode, registry="suite")
