"""Expert-parallel MoE (shard_map + all-to-all) vs the dense dispatch path:
numerical equivalence on an 8-device mesh (EXPERIMENTS §Perf A.3)."""

import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def test_ep_matches_dense_dispatch():
    code = '''
import jax, jax.numpy as jnp
from jax.sharding import AxisType
from repro.configs.base import ArchConfig, MoEConfig
from repro.models.moe import apply_moe, moe_specs
from repro.models import layers as L
from repro.parallel import sharding as sh

cfg = ArchConfig(name="ep-test", d_model=64, n_heads=4, n_kv_heads=4, d_ff=64,
                 vocab=256, moe=MoEConfig(n_experts=8, top_k=2, d_ff=32,
                                          capacity_factor=8.0, n_shared=1))
params = L.materialize(moe_specs(cfg), jax.random.PRNGKey(0))
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, 64), jnp.float32) * 0.5
sh.set_mesh(None)
ref, _ = apply_moe(params, x, cfg=cfg)
mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
sh.set_mesh(mesh)
out, aux = jax.jit(lambda p, x: apply_moe(p, x, cfg=cfg))(params, x)
err = float(jnp.abs(out - ref).max())
assert err < 2e-2, err
# gradients flow through the a2a exchange
g = jax.grad(lambda p: apply_moe(p, x, cfg=cfg)[0].sum())(params)
assert all(bool(jnp.isfinite(l).all()) for l in jax.tree.leaves(g))
print("EP OK", err)
'''
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin",
           "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900, env=env)
    assert r.returncode == 0, r.stdout + r.stderr[-3000:]
    assert "EP OK" in r.stdout
