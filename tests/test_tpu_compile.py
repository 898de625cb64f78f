"""Compile the pallas backend's segment kernels for a TPU v5e chip that is
described, not attached: Mosaic refuses here what the chip would refuse.

Each kernel is built from its segments' shape signatures through the
backend's emit step (``pallas_backend.emit_segment`` with Mosaic
lowering), never by running it: a recording backend walks the launch
and only collects ``(segment, registers, buffers, shared)`` signatures.
The topology is described inside a module fixture, which skips where it
cannot be described; nothing here touches the TPU library at import.
"""
import numpy as np
import pytest

from repro.core import hetir as ir
from repro.core import kernels_suite as suite
from repro.core.backends import pallas_backend as pb
from repro.core.backends.base import Backend, state_signature
from repro.core.backends.pallas_mem import MOSAIC_REFUSALS, PallasRefusal
from repro.core.engine import Engine
from repro.core.passes import choose_block
from repro.zoo import kernels as zoo


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    # a compile for a described chip cannot be read back from the
    # persistent cache; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


class _Signatures(Backend):
    """Walks a launch without computing: records each distinct segment
    signature and gives the next segment the registers it would see."""

    name = "signatures"

    def __init__(self):
        super().__init__(None)
        self.seen = {}

    def run_segment(self, seg, state, launch):
        sig = state_signature(state)
        self.seen.setdefault((seg.index,) + sig, (seg, launch) + sig)
        B, T = launch.num_blocks, launch.block_size
        for r in seg.defs:
            state.regs.setdefault(r.name, np.zeros((B, T),
                                                   ir.np_dtype(r.dtype)))


def _signatures(prog, grid, block, args):
    rec = _Signatures()
    assert Engine(prog, rec, grid, block, dict(args)).run()
    return list(rec.seen.values())


def _compile_all(sigs, grid, block, sharding):
    import jax

    windows = {}
    for seg, launch, reg_sig, glb_sig, shared_sig in sigs:
        fn, meta, example = pb.emit_segment(
            seg, launch, reg_sig, glb_sig, shared_sig,
            choose_block(grid * block), interpret=False)
        structs = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding)
                   for s in example]
        text = jax.jit(fn).lower(*structs).compile().as_text()
        assert "tpu_custom_call" in text, f"segment {seg.index}: no kernel"
        windows.update(meta["windows"])
    return windows


def _attn_width_args():
    H, D, T, nt = 24, 128, 128, 16
    return H, T, {
        "Q": np.zeros(H * D, np.float32),
        "K": np.zeros(H * nt * T * D, np.float32),
        "V": np.zeros(H * nt * T * D, np.float32),
        "O": np.zeros(H * D, np.float32),
        "ntiles": nt, "scale": np.float32(1 / np.sqrt(D))}


def test_attn_decode_compiles_at_llama_decode_width(one_chip):
    """Every segment of attn_decode at head dim 128, 24 heads, 2,048 keys:
    K and V (25 MB each) must move in by (head, tile) windows."""
    prog, _ = zoo.attn_decode(D=128, T=128)
    grid, block, args = _attn_width_args()
    sigs = _signatures(prog, grid, block, args)
    assert len(sigs) >= 5
    windows = _compile_all(sigs, grid, block, one_chip)
    assert windows == {"K": 128 * 128, "V": 128 * 128}


def test_segment_kernel_is_named_for_its_segment(one_chip):
    """A segment kernel compiles to a custom call named
    ``<program>.s<index>``, which is how a device trace names the
    segment that ran."""
    import re

    import jax

    prog, _ = zoo.attn_decode(D=128, T=128)
    grid, block, args = _attn_width_args()
    seg, launch, reg_sig, glb_sig, shared_sig = _signatures(
        prog, grid, block, args)[2]
    fn, _, example = pb.emit_segment(seg, launch, reg_sig, glb_sig,
                                     shared_sig, choose_block(grid * block),
                                     interpret=False)
    structs = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
               for s in example]
    text = jax.jit(fn).lower(*structs).compile().as_text()
    calls = re.findall(r"^\s*%([\w.\-]+) = .*? custom-call\(", text,
                       re.MULTILINE)
    assert [re.sub(r"\.\d+$", "", c) for c in calls] == \
        [f"attn_decode.s{seg.index}"]


def test_vadd_block_tiled_compiles_at_16k(one_chip):
    prog, _ = suite.vadd()
    n = 16384
    args = {"A": np.zeros(n, np.float32), "B": np.zeros(n, np.float32),
            "C": np.zeros(n, np.float32), "n": n}
    sigs = _signatures(prog, 128, 128, args)
    _compile_all(sigs, 128, 128, one_chip)
    fn, meta, _ = pb.emit_segment(*sigs[0][:5], choose_block(n),
                                  interpret=False)
    assert meta["block"] is not None and meta["block"]["tiled"]


@pytest.mark.parametrize("name", ["decode_gemv", "moe_route_gmm",
                                  "mlstm_cell", "rglru_step"])
def test_example_launch_compiles(name, one_chip):
    """Scalar-path kernels with shared memory, barriers, collectives and
    data-dependent gathers, at their example launches."""
    prog, _oracle, grid, block, args, _outs = suite.example_launch(name)
    _compile_all(_signatures(prog, grid, block, args), grid, block,
                 one_chip)


def test_unwindowed_buffer_over_vmem_is_refused_by_name():
    """A gather through a data-dependent index proves no window, so a
    buffer larger than the VMEM budget cannot be staged: the emitter
    raises the named refusal for the chip (and still runs interpreted)."""
    b = ir.Builder("big_gather", [ir.Ptr("Idx", ir.I32), ir.Ptr("Src"),
                                  ir.Ptr("Out")])
    i = b.global_id(0)
    b.store("Out", i, b.load("Src", b.load("Idx", i)))
    prog = b.done()
    args = {"Idx": np.zeros(256, np.int32),
            "Src": np.zeros(8 << 20, np.float32),
            "Out": np.zeros(256, np.float32)}
    sigs = _signatures(prog, 2, 128, args)
    with pytest.raises(PallasRefusal) as err:
        pb.emit_segment(*sigs[0][:5], None, interpret=False)
    assert err.value.reason.split(":")[0] in MOSAIC_REFUSALS
    pb.emit_segment(*sigs[0][:5], None, interpret=True)
