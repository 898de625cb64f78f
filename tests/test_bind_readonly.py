"""Launch binding: a DeviceBuffer the program never writes is bound by
reference (no copy at bind, no write-back at finish); a host write
through the handle while a launch holds it gives the launch a private
copy first (copy-on-write), so the launch still sees the buffer as it
was at bind.  Every case runs on interp, vectorized and pallas (CPU
interpret)."""
import numpy as np
import pytest

from repro.core import HetSession, TranslationCache, migrate
from repro.core import hetir as ir
from repro.core.hetir import Builder, Ptr, Scalar

BACKENDS = ("interp", "vectorized", "pallas")
N, GRID, BLOCK = 64, 2, 32
OLD = np.random.default_rng(11).standard_normal(N).astype(np.float32)
NEW = np.random.default_rng(12).standard_normal(N).astype(np.float32)


def _read_twice() -> ir.Program:
    """Y[i] = X[i] + 2·X[n-1-i]: X is read on both sides of a barrier,
    Y is written."""
    b = Builder("read_twice", [Ptr("X"), Ptr("Y"), Scalar("n")])
    i = b.global_id(0)
    n = b.param("n")
    a = b.var(b.const(0.0, ir.F32), hint="a")
    with b.when(i < n):
        b.assign(a, b.load("X", i))
    b.barrier("first-read")
    with b.when(i < n):
        far = b.load("X", n - b.const(1) - i)
        b.store("Y", i, a + far * b.const(2.0, ir.F32))
    return b.done()


PROG = _read_twice()
_CACHES = {}


def _expected(x: np.ndarray) -> np.ndarray:
    return x + x[::-1] * np.float32(2.0)


def _session(backend: str) -> HetSession:
    # one translation cache per backend: every case after the first
    # revives the two segments instead of translating them again
    return HetSession(backend,
                      cache=_CACHES.setdefault(backend, TranslationCache()))


def _setup(backend: str):
    s = _session(backend)
    fn = s.load(PROG).function()
    x = s.alloc(N).copy_from_host(OLD)
    y = s.alloc(N)
    return s, fn, x, y


def _args(x, y):
    return {"X": x, "Y": y, "n": N}


@pytest.fixture(params=BACKENDS)
def backend(request):
    return request.param


# ---------------------------------------------------------------------------
# binding
# ---------------------------------------------------------------------------

def test_read_only_buffer_is_bound_by_reference(backend):
    s, fn, x, y = _setup(backend)
    rec = fn.launch_async(GRID, BLOCK, _args(x, y))
    eng = rec.engine                      # bound, no segment run yet
    assert eng.written == {"Y"}
    assert np.shares_memory(eng.state.globals_["X"], x.data)
    assert not np.shares_memory(eng.state.globals_["Y"], y.data)
    assert eng.copied_bytes == y.nbytes
    assert rec.wait()
    np.testing.assert_array_equal(y.data, _expected(OLD))


def test_written_buffer_is_copied_and_written_back_at_finish(backend):
    s, fn, x, y = _setup(backend)
    y.fill(5.0)
    rec = fn.launch_async(GRID, BLOCK, _args(x, y))
    assert not rec.advance(max_segments=1)
    # the kernel's writes land only at finish, as before
    np.testing.assert_array_equal(y.data, np.full(N, 5.0, np.float32))
    assert rec.wait()
    np.testing.assert_array_equal(y.data, _expected(OLD))
    assert s.stats["bind_cow_copies"] == 0


def test_buffer_bound_to_a_written_and_a_read_parameter(backend):
    """Bound as both X (read) and Y (written), the buffer counts as
    written for both: each parameter gets its own copy at bind, and the
    write-back runs in parameter order as before, Y's last."""
    s, fn, x, _ = _setup(backend)
    rec = fn.launch_async(GRID, BLOCK, {"X": x, "Y": x, "n": N})
    eng = rec.engine
    assert eng.written == {"X", "Y"}
    assert not any(np.shares_memory(eng.state.globals_[n], x.data)
                   for n in ("X", "Y"))
    assert eng.copied_bytes == 2 * x.nbytes
    assert not x._readers
    assert rec.wait()
    np.testing.assert_array_equal(x.data, _expected(OLD))
    assert s.stats["bind_cow_copies"] == 0


def test_read_only_buffer_keeps_its_contents_and_identity(backend):
    s, fn, x, y = _setup(backend)
    xd, yd = x.data, y.data
    rec = fn.launch(GRID, BLOCK, _args(x, y))
    assert rec.finished
    assert x.data is xd and y.data is yd
    np.testing.assert_array_equal(x.data, OLD)
    np.testing.assert_array_equal(y.data, _expected(OLD))
    assert not x._readers


# ---------------------------------------------------------------------------
# copy-on-write: every in-place host write through the handle
# ---------------------------------------------------------------------------

def _copy_from_host(s, fn, x, prep):
    x.copy_from_host(NEW)


def _async_h2d(s, fn, x, prep):
    assert x.copy_from_host_async(NEW, stream=s.stream()).wait()


def _fill(s, fn, x, prep):
    x.fill(3.0)


def _other_launch_writeback(s, fn, x, prep):
    src = s.alloc(N).copy_from_host(NEW)
    other = fn.launch(GRID, BLOCK, {"X": src, "Y": x, "n": N},
                      stream=s.stream())
    assert other.finished


def _restore_rebind_prep(s, fn, x):
    """A checkpoint that binds ``x`` and holds OLD; ``x`` then holds NEW
    when the launch under test binds it."""
    st = s.stream()
    rec = fn.launch_async(GRID, BLOCK, _args(x, s.alloc(N)), stream=st)
    blob = s.checkpoint(rec)
    rec.cancel()
    x.copy_from_host(NEW)
    return blob


def _restore_rebind(s, fn, x, blob):
    restored = s.restore(fn, blob, stream=s.stream())
    assert restored.bindings["X"] is x
    np.testing.assert_array_equal(x.data, OLD)


def _free_then_alloc(s, fn, x, prep):
    backing = x._backing
    x.free()
    z = s.alloc(N)                    # the pool hands the backing on
    assert z._backing is backing
    z.copy_from_host(NEW)


WRITES = {"copy_from_host": (None, _copy_from_host),
          "async_h2d": (None, _async_h2d),
          "fill": (None, _fill),
          "other_launch_writeback": (None, _other_launch_writeback),
          "restore_rebind": (_restore_rebind_prep, _restore_rebind),
          "free_then_alloc": (None, _free_then_alloc)}


@pytest.mark.parametrize("path", sorted(WRITES))
def test_host_write_while_bound_copies_once(backend, path):
    prep_fn, write = WRITES[path]
    s, fn, x, y = _setup(backend)
    prep = prep_fn(s, fn, x) if prep_fn else None
    at_bind = x.copy_to_host()
    st = s.stream()
    rec = fn.launch_async(GRID, BLOCK, _args(x, y), stream=st)
    eng = rec.engine
    assert np.shares_memory(eng.state.globals_["X"], x.data)
    st.pause()                        # the launch holds at its bind
    write(s, fn, x, prep)
    assert s.stats["bind_cow_copies"] == 1
    assert not x._readers
    assert not np.shares_memory(eng.state.globals_["X"], x.data)
    written = None if x.freed else x.copy_to_host()
    st.resume()
    assert rec.wait()
    np.testing.assert_array_equal(y.data, _expected(at_bind))
    if written is not None:           # no write-back of the read buffer
        np.testing.assert_array_equal(x.data, written)
    assert s.synchronize()
    assert s.stats["bind_cow_copies"] == 1


@pytest.mark.parametrize("trial", range(3))
def test_host_write_between_segments_is_not_seen(backend, trial):
    """After the first segment interp holds its own copy of the buffer;
    vectorized and pallas hold arrays on the CPU device, which may still
    read the host array (a zero-copy upload, or a segment dispatched but
    not yet run): the write waits for them and copies.  Three trials, as
    a lost race shows only some of the time."""
    s, fn, x, y = _setup(backend)
    x.copy_from_host(np.full(N, trial, np.float32) + OLD)
    at_bind = x.copy_to_host()
    rec = fn.launch_async(GRID, BLOCK, _args(x, y))
    assert not rec.advance(max_segments=1)
    x.copy_from_host(NEW)
    assert rec.wait()
    np.testing.assert_array_equal(y.data, _expected(at_bind))
    np.testing.assert_array_equal(x.data, NEW)
    assert s.stats["bind_cow_copies"] == (backend != "interp")


@pytest.mark.parametrize("end", ["finish", "cancel", "migrate"])
def test_no_copy_once_the_launch_lets_go(backend, end):
    s, fn, x, y = _setup(backend)
    rec = fn.launch_async(GRID, BLOCK, _args(x, y))
    assert not rec.advance(max_segments=1)
    assert x._readers
    if end == "finish":
        assert rec.wait()
        np.testing.assert_array_equal(y.data, _expected(OLD))
    elif end == "cancel":
        rec.cancel()
    else:
        dst = _session(backend)
        new = migrate(rec, s, dst, dst.load(PROG).function())
        assert new.wait()
        np.testing.assert_array_equal(new.buffer("Y").copy_to_host(),
                                      _expected(OLD))
    assert not x._readers
    x.copy_from_host(NEW)
    x.fill(1.0)
    x.free()
    assert s.stats["bind_cow_copies"] == 0


def test_checkpoint_mid_launch_is_bit_identical(backend):
    s, fn, x, y = _setup(backend)
    assert fn.launch(GRID, BLOCK, _args(x, y)).finished
    unmigrated = y.copy_to_host()
    y.fill(0.0)
    rec = fn.launch_async(GRID, BLOCK, _args(x, y))
    assert not rec.advance(max_segments=1)
    blob = s.checkpoint(rec)
    rec.cancel()
    restored = s.restore(fn, blob)
    assert restored.bindings["X"] is x and restored.bindings["Y"] is y
    assert restored.wait()
    assert np.array_equal(y.data, unmigrated)
    np.testing.assert_array_equal(x.data, OLD)
    assert s.stats["bind_cow_copies"] == 0
