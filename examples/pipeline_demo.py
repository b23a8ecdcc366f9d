"""Pipeline parallelism from the pipeline TDG.

Shows the static 1F1B schedule that the Taskgraph scheduler emits (the
pipeline schedule IS a TDG), then executes a 4-stage GPipe forward+backward
on a 4-device CPU mesh via shard_map+ppermute and verifies against the
sequential model.

Run: XLA_FLAGS=--xla_force_host_platform_device_count=4 \
     PYTHONPATH=src python examples/pipeline_demo.py
"""
import os

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (list_schedule, one_f_one_b_order, pipeline_tdg,
                        topo_waves)
from repro.core.pipeline import bubble_fraction, pipeline_apply
from repro.launch.mesh import make_mesh


def main():
    S, M = 4, 8
    tdg = pipeline_tdg(S, M)
    print(tdg.summary())
    waves = topo_waves(tdg)
    print(f"waves: {len(waves)} (fwd+bwd), "
          f"GPipe bubble fraction: {bubble_fraction(S, M):.2f}")
    print("1F1B stage streams:")
    for s, stream in enumerate(one_f_one_b_order(S, M)):
        print(f"  stage{s}: " + " ".join(f"{p}{m}" for p, m in stream))
    sched = list_schedule(tdg, n_workers=S)
    print(f"list-schedule makespan {sched.makespan:.0f} "
          f"(critical path bound: {len(waves)})")

    mesh = make_mesh((S,), ("stage",), devices=jax.devices()[:S])
    d, mb = 32, 4
    key = jax.random.PRNGKey(0)
    Ws = jax.random.normal(key, (S, d, d)) * 0.3
    xs = jax.random.normal(jax.random.fold_in(key, 1), (M, mb, d))

    def stage_fn(W, x):
        return jnp.tanh(x @ W)

    out = pipeline_apply(stage_fn, Ws, xs, mesh)
    ref = xs
    for s in range(S):
        ref = jnp.tanh(ref @ Ws[s])
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    g = jax.grad(lambda W: (pipeline_apply(stage_fn, W, xs, mesh) ** 2).sum())(Ws)
    g_ref = jax.grad(lambda W: (jnp.tanh(jnp.tanh(jnp.tanh(jnp.tanh(
        xs @ W[0]) @ W[1]) @ W[2]) @ W[3]) ** 2).sum())(Ws)
    np.testing.assert_allclose(g, g_ref, atol=1e-4, rtol=1e-4)
    print("pipeline forward+backward == sequential: OK")


if __name__ == "__main__":
    main()
