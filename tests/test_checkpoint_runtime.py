"""Checkpoint + fault-tolerance + elastic-restore tests.

Single-device tests run in-process; cross-mesh tests spawn a subprocess
with XLA_FLAGS=--xla_force_host_platform_device_count=8 (jax device count
is locked at first init in this process).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro import configs
from repro.launch.mesh import auto_mesh
from repro.checkpoint import AsyncCheckpointer, latest_step, restore, save
from repro.configs.base import ParallelCfg, ShapeCfg
from repro.runtime.train_loop import SimulatedFailure, Trainer

SMOKE_SHAPE = ShapeCfg("tiny", 32, 4, "train")


def _mesh1():
    return auto_mesh((1, 1), ("data", "model"))


def test_checkpoint_roundtrip(tmp_path):
    state = {"a": jax.numpy.arange(12.0).reshape(3, 4),
             "b": [jax.numpy.ones(5), jax.numpy.zeros(2)]}
    save(tmp_path, 7, state, extra={"next_step": 7})
    assert latest_step(tmp_path) == 7
    got, extra = restore(tmp_path, 7, state)
    assert extra["next_step"] == 7
    np.testing.assert_array_equal(np.asarray(got["a"]),
                                  np.asarray(state["a"]))
    np.testing.assert_array_equal(np.asarray(got["b"][0]), np.ones(5))


def test_async_checkpointer(tmp_path):
    ck = AsyncCheckpointer(tmp_path)
    state = {"x": jax.numpy.ones((4, 4))}
    ck.save(3, state, extra={"next_step": 3})
    ck.wait()
    assert latest_step(tmp_path) == 3
    got, _ = restore(tmp_path, 3, state)
    np.testing.assert_array_equal(np.asarray(got["x"]), np.ones((4, 4)))


def test_train_restart_after_failure_is_bit_exact(tmp_path):
    """Crash mid-run, restart from checkpoint: the loss trajectory must
    match an uninterrupted run exactly (deterministic data + optimizer)."""
    cfg = configs.get_smoke_config("llama3.2-3b")
    mesh = _mesh1()

    # uninterrupted reference
    t_ref = Trainer(cfg, SMOKE_SHAPE, mesh, seed=3)
    ref = t_ref.run(6)

    # crash at step 4, restart from the step-2 checkpoint
    t1 = Trainer(cfg, SMOKE_SHAPE, mesh, ckpt_dir=tmp_path / "ck", seed=3)
    with pytest.raises(SimulatedFailure):
        t1.run(6, checkpoint_every=2, failure_at=4)
    t2 = Trainer(cfg, SMOKE_SHAPE, mesh, ckpt_dir=tmp_path / "ck", seed=3)
    assert t2.maybe_restore()
    assert t2.step == 4
    rep2 = t2.run(2)

    np.testing.assert_allclose(rep2.losses, ref.losses[4:6],
                               rtol=1e-5, atol=1e-6)


def test_preempt_flag_checkpoints_and_stops(tmp_path):
    cfg = configs.get_smoke_config("xlstm-125m")
    t = Trainer(cfg, SMOKE_SHAPE, _mesh1(), ckpt_dir=tmp_path / "ck",
                seed=1)
    calls = {"n": 0}

    def flag():
        calls["n"] += 1
        return calls["n"] >= 2

    rep = t.run(10, preempt_flag=flag)
    assert rep.preempted
    assert rep.steps_run < 10
    assert latest_step(tmp_path / "ck") == t.step


MULTIDEV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json, sys
import jax
import numpy as np
from repro import configs
from repro.launch.mesh import auto_mesh
from repro.configs.base import ParallelCfg, ShapeCfg
from repro.runtime.train_loop import Trainer

cfg = configs.get_smoke_config("llama3.2-3b")
shape = ShapeCfg("tiny", 32, 8, "train")
ckpt = sys.argv[1]

mesh_a = auto_mesh((2, 4), ("data", "model"))
mesh_b = auto_mesh((4, 2), ("data", "model"))

# reference: 4 steps on mesh A only
t_ref = Trainer(cfg, shape, mesh_a, seed=11)
ref = t_ref.run(4)

# elastic: 2 steps on mesh A -> checkpoint -> restore on mesh B (different
# DP/TP split) -> 2 more steps
t1 = Trainer(cfg, shape, mesh_a, ckpt_dir=ckpt, seed=11)
t1.run(2)
t1.save_checkpoint()

t2 = Trainer(cfg, shape, mesh_b, ckpt_dir=ckpt, seed=11)
assert t2.maybe_restore(), "restore failed"
assert t2.step == 2
rep = t2.run(2)

# in-process live resize as well: mesh B -> mesh A
t2.resize(mesh_a)
rep2 = t2.run(1)
ok = bool(np.allclose(rep.losses, ref.losses[2:4], rtol=5e-4, atol=5e-5))
print(json.dumps({
    "elastic_losses": rep.losses, "ref_losses": ref.losses[2:4],
    "resize_loss_finite": bool(np.isfinite(rep2.losses[0])),
    "match": ok,
}))
"""


def test_cross_mesh_elastic_restore(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath("src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", MULTIDEV_SCRIPT,
                          str(tmp_path / "ck")],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["match"], res
    assert res["resize_loss_finite"]
