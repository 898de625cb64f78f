"""Units of what the TPU kernels rest on: the pallas emitter's memory
layer (``backends/pallas_mem.py``) — the lane gather/scatter primitives
against NumPy, run as interpreted Pallas kernels, and the index-range
analysis that picks buffer windows — and the correctly rounded division
of ``backends/semantics.py``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.core import hetir as ir
from repro.core.backends import pallas_mem as pm
from repro.core.backends import semantics
from repro.core.passes import get_optimized
from repro.core.segments import SegNode, segment_program
from repro.device import pallas_interpret
from repro.zoo import kernels as zoo


def _run(body, ref_in, *vecs):
    """Interpret ``body(ref, *vecs) -> (1, n)`` with ``ref`` updated in
    place; returns (ref after, result)."""
    n = vecs[0].shape[-1]

    def kernel(buf_ref, *refs):
        ins, (o_ref, r_ref) = refs[:-2], refs[-2:]
        o_ref[...] = buf_ref[...]
        r_ref[...] = body(o_ref, *(r[...] for r in ins))

    out, res = pl.pallas_call(
        kernel, interpret=True,
        out_shape=(jax.ShapeDtypeStruct(ref_in.shape, ref_in.dtype),
                   jax.ShapeDtypeStruct((1, n), ref_in.dtype)))(
        ref_in, *vecs)
    return np.asarray(out), np.asarray(res)


@pytest.mark.parametrize("n", [32, 128, 256])
@pytest.mark.parametrize("dtype", [np.float32, np.int32, np.uint32])
def test_gather_and_lane_ordered_scatter_match_numpy(n, dtype):
    rng = np.random.default_rng(n)
    buf = rng.integers(0, 1000, (16, 128)).astype(dtype)
    idx = rng.integers(0, buf.size, (1, n)).astype(np.int32)
    idx[0, 3] = idx[0, 5] = idx[0, 9]          # duplicates: last lane wins
    idx[0, 7] = buf.size + 4                    # out of range: dropped
    val = rng.integers(0, 1000, (1, n)).astype(dtype)
    act = (rng.random((1, n)) < 0.7).astype(np.int32)

    def body(ref, i, v, a):
        got = pm.gather(ref, jnp.clip(i, 0, ref.shape[0] * 128 - 1))
        pm.scatter(ref, i, v, a)
        return got

    out, got = _run(body, buf, idx, val, act)
    flat = buf.reshape(-1).copy()
    np.testing.assert_array_equal(got[0],
                                  flat[np.clip(idx[0], 0, flat.size - 1)])
    for t in range(n):
        if act[0, t] and idx[0, t] < flat.size:
            flat[idx[0, t]] = val[0, t]
    np.testing.assert_array_equal(out.reshape(-1), flat)


def test_atomic_add_sees_earlier_lanes_and_returns_old_values():
    buf = np.zeros((8, 128), np.float32)
    buf[0, 0] = -0.0
    idx = np.zeros((1, 32), np.int32)
    idx[0, 16:] = 5
    val = np.arange(1, 33, dtype=np.float32)[None]
    act = np.ones((1, 32), np.int32)

    out, old = _run(lambda r, i, v, a: pm.scatter(r, i, v, a, add=True),
                    buf, idx, val, act)
    flat = buf.reshape(-1).copy()
    want_old = np.zeros(32, np.float32)
    for t in range(32):
        want_old[t] = flat[idx[0, t]]
        flat[idx[0, t]] = flat[idx[0, t]] + val[0, t]
    np.testing.assert_array_equal(old[0].view(np.int32),
                                  want_old.view(np.int32))
    np.testing.assert_array_equal(out.reshape(-1), flat)


def test_lane_take_crosses_lane_groups():
    v = np.arange(256, dtype=np.float32)[None]
    src = ((np.arange(256) * 37 + 5) % 256).astype(np.int32)[None]

    def kernel(v_ref, s_ref, o_ref):
        o_ref[...] = pm.lane_take(v_ref[...], s_ref[...])

    got = pl.pallas_call(kernel, interpret=True,
                         out_shape=jax.ShapeDtypeStruct(v.shape, v.dtype))(
        v, src)
    np.testing.assert_array_equal(np.asarray(got)[0], v[0, src[0]])


def test_windows_follow_head_and_tile_at_decode_width():
    """K is addressed ((h*ntiles + kt)*T + t)*D + d: the range analysis
    must prove one 128x128 window per (head, tile), not the whole 25 MB
    buffer."""
    prog, _ = zoo.attn_decode(D=128, T=128)
    opt, _ = get_optimized(prog, 3)
    scalars = {"ntiles": np.int32(16), "scale": np.float32(0.1)}
    segs = [n for n in segment_program(opt) if isinstance(n, SegNode)]
    entries = pm.entry_forms(opt, 24, 128, scalars)
    assert len(entries) == len(segs)
    lens = {"Q": 3072, "K": 6291456, "V": 6291456, "O": 3072}
    windows = {}
    for seg, entry in zip(segs, entries):
        lay = pm.plan_buffers(seg.stmts, entry,
                              {n: lens[n] for n in seg.greads | seg.gwrites},
                              24, 128, scalars)
        windows.update({n: (l.window, l.terms) for n, l in lay.items()
                        if l.window < l.padded})
    assert set(windows) == {"K", "V"}
    for window, terms in windows.values():
        assert window == 128 * 128
        (g, cg), (kt, ck) = terms          # head and tile-counter strides
        assert (g, cg, ck) == ("g", 16 * 128 * 128, 128 * 128)
        assert kt.startswith("loop:")


def test_data_dependent_index_stages_the_whole_buffer():
    b = ir.Builder("gather", [ir.Ptr("Idx", ir.I32), ir.Ptr("Src"),
                              ir.Ptr("Out")])
    i = b.global_id(0)
    b.store("Out", i, b.load("Src", b.load("Idx", i)))
    prog = b.done()
    seg = [n for n in segment_program(prog) if isinstance(n, SegNode)][0]
    lay = pm.plan_buffers(seg.stmts, {}, {"Idx": 256, "Src": 5000,
                                          "Out": 256}, 2, 128, {})
    assert lay["Src"].window == lay["Src"].padded == 5120
    assert lay["Out"].window == lay["Out"].padded


def test_interpret_mode_is_derived_from_the_platform():
    assert pallas_interpret() == (jax.default_backend() == "cpu")


@pytest.mark.parametrize("ulps", [-2, -1, 0, 1, 2])
def test_division_correction_recovers_round_to_nearest(ulps):
    """A divider up to two ULP off (the TPU has no IEEE divider) is
    corrected to numpy's round-to-nearest-even quotient; a correct one is
    left alone."""
    rng = np.random.default_rng(5)
    n = 20000
    x = (rng.standard_normal(n) * np.exp(rng.uniform(-18, 18, n))) \
        .astype(np.float32)
    y = (rng.standard_normal(n) * np.exp(rng.uniform(-18, 18, n))) \
        .astype(np.float32)
    x[:500] = rng.integers(1, 64, 500)      # exact quotients and ties
    y[:500] = rng.integers(1, 64, 500)
    want = x / y
    q0 = want.copy()
    for _ in range(abs(ulps)):
        q0 = np.nextafter(q0, np.float32(np.sign(ulps) * np.inf))
    got = np.asarray(jax.jit(semantics._round_quotient)(x, y, q0))
    np.testing.assert_array_equal(got.view(np.int32), want.view(np.int32))
