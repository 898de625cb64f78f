"""Compile every segment kernel each cell runs, at the cell's real
shapes, for a TPU v5e chip that is described and not attached: Mosaic
and XLA refuse here what the chip would refuse.  Slow (the unrolled
segment bodies take up to a minute each to trace); run with
``pytest bench/tests -m chip_compile``."""
import json

import numpy as np
import pytest

from conftest import BENCH

pytestmark = pytest.mark.chip_compile


@pytest.fixture(scope="module")
def one_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _signatures(config):
    from repro.core import hetir as ir
    from repro.core.backends.base import Backend, state_signature
    from repro.core.engine import Engine
    import harness

    kernel = harness.load_module(BENCH / "kernels" / "attn_decode.py",
                                 "k_compile")
    attn = config["attn"]
    H, D, S = attn["heads"], attn["head_dim"], attn["keys"]

    class Signatures(Backend):
        name = "signatures"

        def __init__(self):
            super().__init__(None)
            self.seen = {}

        def run_segment(self, seg, state, launch):
            sig = state_signature(state)
            self.seen.setdefault((seg.index,) + sig, (seg, launch) + sig)
            B, T = launch.num_blocks, launch.block_size
            for r in seg.defs:
                state.regs.setdefault(r.name, np.zeros(
                    (B, T), ir.np_dtype(r.dtype)))

    z = lambda n: np.zeros(n, np.float32)
    args = kernel.args(attn, {"Q": z(H * D), "K": z(H * S * D),
                              "V": z(H * S * D), "O": z(H * D)})
    rec = Signatures()
    grid, block = kernel.geometry(attn)
    assert Engine(kernel.program(attn), rec, grid, block, args).run()
    return list(rec.seen.values())


@pytest.mark.parametrize("name", ["glm4-9b-attn", "granite-3b-a800m-attn"])
def test_pallas_segments_compile(name, one_chip):
    import jax
    from repro.core.backends import pallas_backend as pb
    from repro.core.passes import choose_block

    config = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    for seg, launch, reg_sig, glb_sig, shared_sig in _signatures(config):
        fn, meta, example = pb.emit_segment(
            seg, launch, reg_sig, glb_sig, shared_sig,
            choose_block(launch.num_blocks * launch.block_size),
            interpret=False)
        structs = [jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
                   for s in example]
        compiled = jax.jit(fn).lower(*structs).compile()
        assert "tpu_custom_call" in compiled.as_text()
        print(name, seg.label, meta["windows"], compiled.memory_analysis())


def test_vectorized_segments_compile_at_glm_width(one_chip):
    """The migrate cell's source backend (XLA) at GLM-4-9B width."""
    import jax
    from repro.core.backends.semantics import Env, eval_stmts

    config = json.loads((BENCH / "configs" / "glm4-9b-attn.json")
                        .read_text())
    for seg, launch, reg_sig, glb_sig, shared_sig in _signatures(config):
        B, T, scalars = launch.num_blocks, launch.block_size, \
            dict(launch.scalars)

        def run(regs, shared, glbs, seg=seg):
            env = Env(dict(regs), shared, dict(glbs), scalars, B, T)
            env.lane_shape = (B, T)
            eval_stmts(seg.stmts, env, mask=None)
            return env.regs, env.shared, env.globals

        st = lambda shape, dt: jax.ShapeDtypeStruct(shape, np.dtype(dt),
                                                    sharding=one_chip)
        regs = {n: st(s, d) for n, s, d in reg_sig}
        glbs = {n: st(s, d) for n, s, d in glb_sig}
        shared = None if shared_sig is None else st(*shared_sig)
        jax.jit(run).lower(regs, shared, glbs).compile()
