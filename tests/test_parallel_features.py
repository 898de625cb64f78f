"""Parallelism-feature tests: sharding-rule structure, divisibility
fallbacks, and true expert-parallelism on a divisible mesh (subprocess)."""
import json
import os
import subprocess
import sys

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.launch.mesh import auto_mesh
from repro.configs.base import SHAPES, ParallelCfg, default_parallel
from repro.models import registry as R
from repro.parallel import MeshRules


def test_param_specs_match_param_tree_structure():
    for arch in configs.list_archs():
        cfg = configs.get_smoke_config(arch)
        mesh = auto_mesh((1, 1), ("data", "model"))
        rules = MeshRules(cfg, ParallelCfg(), mesh)
        params = R.abstract_params(cfg)
        specs = rules.param_specs()
        ps = jax.tree.structure(params)
        ss = jax.tree.structure(specs,
                                is_leaf=lambda x: isinstance(x, P))
        assert ps == ss, f"{arch}: spec tree != param tree"


def test_divisibility_fallback_never_invalid():
    """Every spec entry must divide its dim by the mesh axis product."""
    mesh = auto_mesh((1, 1), ("data", "model"))
    # simulate production sizes through the axis-size table
    for arch in configs.list_archs():
        cfg = configs.get_config(arch)
        rules = MeshRules(cfg, default_parallel(cfg, SHAPES["train_4k"]),
                          mesh)
        rules.axis_size = {"data": 16, "model": 16}
        rules.fsdp = ("data",)
        rules.tp = "model"
        params = R.abstract_params(cfg)
        specs = rules.param_specs()
        flat_p = jax.tree.leaves(params)
        flat_s = jax.tree.leaves(specs,
                                 is_leaf=lambda x: isinstance(x, P))
        for leaf, spec in zip(flat_p, flat_s):
            for dim, entry in zip(leaf.shape, spec):
                if entry is None:
                    continue
                n = 1
                for a in (entry if isinstance(entry, tuple)
                          else (entry,)):
                    n *= rules.axis_size[a]
                assert dim % n == 0, \
                    f"{arch}: dim {dim} not divisible by {entry} ({n})"


EP_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import configs
from repro.launch.mesh import auto_mesh
from repro.configs.base import ParallelCfg
from repro.models import layers as L
from repro.parallel import MeshRules

mesh = auto_mesh((2, 4), ("data", "model"))
cfg = configs.get_smoke_config("mixtral-8x22b")
# 4 experts over the 4-wide model axis -> TRUE expert parallelism
cfg = dataclasses.replace(cfg, moe_impl="grouped")
assert cfg.moe.n_experts == 4
rules = MeshRules(cfg, ParallelCfg(fsdp_axes=("data",)), mesh)
assert rules._ep_axis(cfg.moe.n_experts) == "model", "EP axis not chosen"

rng = np.random.default_rng(0)
B, S, D = 4, 16, cfg.d_model
x = jnp.asarray(rng.normal(size=(B, S, D)) * 0.3, jnp.float32)
p = L.init_moe(jax.random.key(0), cfg, jnp.float32)

# unsharded reference
ref = L.moe_ffn_grouped(x, p, cfg)

# sharded: params EP-sharded on the expert dim, batch over data
pspecs = {k: P("model", None, None) if v.ndim == 3 else P(None)
          for k, v in p.items()}
ns = lambda s: NamedSharding(mesh, s)
p_sh = {k: jax.device_put(v, ns(pspecs[k])) for k, v in p.items()}
x_sh = jax.device_put(x, ns(P("data", None, None)))

fn = jax.jit(lambda xx, pp: L.moe_ffn_grouped(xx, pp, cfg, ac=rules.ac))
with mesh:
    out = fn(x_sh, p_sh)
ok = bool(np.allclose(np.asarray(out), np.asarray(ref), atol=1e-4,
                      rtol=1e-4))
# check an all-to-all or expert-routing collective exists in the HLO
with mesh:
    hlo = jax.jit(lambda xx, pp: L.moe_ffn_grouped(xx, pp, cfg,
                                                   ac=rules.ac)) \
        .lower(x_sh, p_sh).compile().as_text()
has_coll = ("all-to-all" in hlo) or ("all-gather" in hlo) or \
    ("collective-permute" in hlo) or ("all-reduce" in hlo)
print(json.dumps({"match": ok, "has_collective": has_coll}))
"""


def test_true_expert_parallelism_on_divisible_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath("src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", EP_SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["match"], res
