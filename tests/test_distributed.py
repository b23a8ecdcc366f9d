"""Multi-device tests (subprocess-isolated: device count is process-global,
and the main pytest process must stay single-device)."""
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _run(code: str, devices: int = 4, timeout: int = 420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(ROOT / "src")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


def test_pipeline_parallel_forward_backward():
    _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.core.pipeline import pipeline_apply
mesh = make_mesh((4,), ("stage",), devices=jax.devices()[:4])
S, M, mb, d = 4, 8, 2, 16
key = jax.random.PRNGKey(0)
Ws = jax.random.normal(key, (S, d, d)) * 0.3
xs = jax.random.normal(jax.random.fold_in(key, 2), (M, mb, d))
stage_fn = lambda W, x: jnp.tanh(x @ W)
out = pipeline_apply(stage_fn, Ws, xs, mesh)
ref = xs
for s in range(S): ref = jnp.tanh(ref @ Ws[s])
np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)
g = jax.grad(lambda W: (pipeline_apply(stage_fn, W, xs, mesh) ** 2).sum())(Ws)
def lref(W):
    r = xs
    for s in range(S): r = jnp.tanh(r @ W[s])
    return (r ** 2).sum()
np.testing.assert_allclose(g, jax.grad(lref)(Ws), atol=1e-4, rtol=1e-4)
print("OK")
""")


def test_moe_shard_map_equals_gspmd():
    _run("""
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.configs import get_config, reduced
from repro.models import moe as MoE
from repro.sharding import partition as P_
from jax.sharding import NamedSharding, PartitionSpec as P
mesh = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
cfg = reduced(get_config("qwen3-moe-30b-a3b"))
key = jax.random.PRNGKey(0)
p = MoE.moe_init(key, cfg)
x = jax.random.normal(jax.random.fold_in(key, 1), (4, 8, cfg.d_model), jnp.float32)
out_ref, _ = MoE.moe_apply_gspmd(p, cfg, x)
cfg_sm = dataclasses.replace(cfg, moe_impl="shard_map")
with P_.use_mesh(mesh):
    p_d = jax.device_put(p, P_.param_shardings(p, mesh))
    x_d = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
    out_sm, _ = jax.jit(lambda p_, x_: MoE.moe_apply(p_, cfg_sm, x_))(p_d, x_d)
np.testing.assert_allclose(np.asarray(out_sm), np.asarray(out_ref),
                           atol=2e-4, rtol=2e-3)
print("OK")
""")


def test_train_step_on_2x2_mesh():
    _run("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import make_mesh
from repro.configs import get_config, reduced
from repro.models import init_params
from repro.optim import adamw
from repro.sharding import partition as P_
from repro.training import make_train_step
from jax.sharding import NamedSharding, PartitionSpec as P
mesh = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
cfg = reduced(get_config("glm4-9b"), d_model=64, num_heads=4, head_dim=16)
opt = adamw(1e-3)
with P_.use_mesh(mesh):
    params = init_params(cfg, jax.random.PRNGKey(0))
    params = jax.device_put(params, P_.param_shardings(params, mesh))
    state = opt.init(params)
    step = jax.jit(make_train_step(cfg, opt), donate_argnums=(0, 1))
    toks = jax.device_put(jnp.full((4, 16), 3, jnp.int32),
                          NamedSharding(mesh, P("data", None)))
    p2, s2, m = step(params, state, {"tokens": toks})
    assert np.isfinite(float(m["loss"]))
print("OK")
""")


@pytest.mark.slow
def test_dryrun_single_cell_small_mesh():
    """The dry-run machinery end-to-end on a tiny in-process mesh.

    Heaviest single test in the suite (~35s: two full model lowerings in a
    subprocess) — behind the ``slow`` marker; run with ``-m slow``."""
    _run("""
import jax
from repro.launch.dryrun import lower_cell
from repro.launch.mesh import make_mesh
mesh = make_mesh((2, 2), ("data", "model"), devices=jax.devices()[:4])
r = lower_cell("qwen2.5-3b", "train_4k", mesh=mesh, save=False)
assert r["roofline"]["hlo_flops_per_device"] > 0
assert r["cost_mode"] == "extrapolated_exact"
r2 = lower_cell("mamba2-370m", "long_500k", mesh=mesh, save=False)
assert r2["kind"] == "decode"
r3 = lower_cell("qwen2.5-3b", "long_500k", mesh=mesh, save=False)
assert "skipped" in r3
print("OK")
""", timeout=420)
