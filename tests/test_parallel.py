"""Distribution-layer tests that need >1 device: run in a subprocess with 8
host platform devices (the dry-run owns the 512-device configuration)."""

import subprocess
import sys
import textwrap
from pathlib import Path


ROOT = Path(__file__).resolve().parents[1]


def _run(code: str) -> str:
    env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=8",
           "PYTHONPATH": str(ROOT / "src"), "PATH": "/usr/bin:/bin",
           "JAX_PLATFORMS": "cpu"}
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=900, env=env)
    assert r.returncode == 0, f"stdout={r.stdout}\nstderr={r.stderr[-3000:]}"
    return r.stdout


def test_pipeline_parallel_matches_sequential():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType
    from repro.parallel.pipeline import pipeline_forward, make_mlp_stage
    mesh = jax.make_mesh((4,), ("stage",),
                     axis_types=(AxisType.Auto,))
    d, n_micro, mb = 32, 8, 4
    stage_fn, init = make_mlp_stage(d)
    params = init(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, d))
    y = pipeline_forward(stage_fn, params, x, mesh=mesh)
    # sequential reference
    ref = x
    for s in range(4):
        p = jax.tree.map(lambda a: a[s], params)
        ref = jax.vmap(lambda m: stage_fn(p, m))(ref)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-4)
    print("PP OK")
    """)


def test_int8_compressed_allreduce_close_to_exact():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType
    from repro.parallel.compression import compressed_psum, compress_with_feedback
    mesh = jax.make_mesh((8,), ("data",),
                     axis_types=(AxisType.Auto,))
    x = jax.random.normal(jax.random.PRNGKey(0), (4096,)) * 0.01
    out = compressed_psum(x, mesh, "data")
    exact = x * 8.0                       # replicated input: psum = 8x
    rel = float(jnp.linalg.norm(out - exact) / jnp.linalg.norm(exact))
    assert rel < 0.02, rel
    # error feedback: averaged repeated reductions converge to the mean
    grads = {"w": x}
    residual = {"w": jnp.zeros_like(x)}
    total_err = []
    for _ in range(4):
        mean, residual = compress_with_feedback(grads, residual, mesh, "data")
        total_err.append(float(jnp.abs(mean["w"] - x).max()))
    assert total_err[-1] < 0.005
    print("compression OK", rel, total_err)
    """)


def test_sharded_train_step_runs_on_8_devices():
    """End-to-end pjit train step on a small mesh: the same code path the
    512-device dry-run lowers, but actually executed."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType
    from repro.configs.base import ArchConfig, dense_stack
    from repro.models.model import init_params, params_logical_axes
    from repro.optim.adamw import adamw_init
    from repro.parallel import sharding as sh
    from repro.train.train_step import make_train_step
    cfg = ArchConfig(name="t8", d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                     vocab=256, groups=dense_stack(2), remat="none")
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
    sh.set_mesh(mesh)
    params = init_params(cfg, jax.random.PRNGKey(0))
    p_sh = sh.tree_shardings(mesh, params_logical_axes(cfg),
                             jax.tree.map(lambda a: a, params))
    params = jax.device_put(params, p_sh)
    opt = adamw_init(params)
    step = jax.jit(make_train_step(cfg), donate_argnums=(0, 1))
    tokens = jnp.zeros((8, 32), jnp.int32)
    batch = {"tokens": tokens, "targets": tokens}
    params, opt, metrics = step(params, opt, batch)
    loss = float(metrics["loss"])
    assert np.isfinite(loss)
    print("8-dev train OK", loss)
    """)


def test_long500k_sequence_parallel_spec():
    _run("""
    import jax
    from jax.sharding import AxisType
    from repro.launch.mesh import make_production_mesh
    from repro.parallel.sharding import spec_for
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                     axis_types=(AxisType.Auto,) * 3)
    # batch=1 -> kv_seq claims ("pod","data")
    spec = spec_for(mesh, ("batch", "kv_seq", "kv_heads", None), (1, 1024, 8, 64))
    assert spec == jax.sharding.PartitionSpec(None, ("pod", "data"), "model"), spec
    print("SP spec OK", spec)
    """)
