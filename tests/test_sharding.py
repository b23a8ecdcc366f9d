"""Distribution: partition rules, small-mesh pjit/shard_map, pipeline."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.launch.mesh import make_mesh
from repro.sharding import partition as P_

pytestmark = pytest.mark.skipif(
    jax.device_count() < 1, reason="needs devices")


def _mesh11():
    return make_mesh((1, 1), ("data", "model"), devices=jax.devices()[:1])


class TestPartitionRules:
    def test_param_specs_by_path(self):
        mesh = _mesh11()
        params = {
            "embed": {"table": jnp.zeros((256, 64))},
            "layers": {"attn": {"wq": {"w": jnp.zeros((2, 64, 64))},
                                "wo": {"w": jnp.zeros((2, 64, 64))}},
                       "mlp": {"up": {"w": jnp.zeros((2, 64, 128))},
                               "down": {"w": jnp.zeros((2, 128, 64))}},
                       "norm1": {"scale": jnp.zeros((2, 64))}},
        }
        specs = P_.param_pspecs(params, mesh)
        assert specs["embed"]["table"] == P("model", "data")
        assert specs["layers"]["attn"]["wq"]["w"] == P(None, "data", "model")
        assert specs["layers"]["attn"]["wo"]["w"] == P(None, "model", "data")
        assert specs["layers"]["mlp"]["down"]["w"] == P(None, "model", "data")
        assert specs["layers"]["norm1"]["scale"] == P(None, None)

    def test_expert_specs_no_axis_reuse(self):
        mesh = _mesh11()
        params = {"moe": {"experts": {"up": {"w": jnp.zeros((2, 4, 8, 16))}}}}
        spec = P_.param_pspecs(params, mesh)["moe"]["experts"]["up"]["w"]
        flat = [a for e in spec if e for a in
                (e if isinstance(e, tuple) else (e,))]
        assert len(flat) == len(set(flat))   # each mesh axis used once

    def test_sanitize_drops_nondivisible(self):
        mesh = _mesh11()
        spec = P_.sanitize_spec((7, 64), P("model", "data"), mesh)
        assert spec == P("model", "data")   # axis size 1 divides everything

    def test_constrain_noop_without_mesh(self):
        x = jnp.ones((4, 4))
        out = P_.constrain(x, ("batch", None))
        np.testing.assert_array_equal(out, x)


class TestSmallMeshLowering:
    """End-to-end pjit of the real train/serve steps on a 1x1 CPU mesh —
    the same code path the 512-device dry-run exercises."""

    def test_train_step_lowers_and_runs(self):
        import dataclasses
        from repro.configs import get_config, reduced
        from repro.launch import specs as SP
        from repro.models import init_params
        from repro.optim import adamw
        from repro.training import make_train_step

        mesh = _mesh11()
        cfg = reduced(get_config("qwen2.5-3b"))
        opt = adamw(1e-3)
        with P_.use_mesh(mesh):
            params = init_params(cfg, jax.random.PRNGKey(0))
            sh = P_.param_shardings(params, mesh)
            params = jax.device_put(params, sh)
            state = opt.init(params)
            step = jax.jit(make_train_step(cfg, opt), donate_argnums=(0, 1))
            tokens = jnp.zeros((2, 16), jnp.int32) + 3
            p2, s2, m = step(params, state, {"tokens": tokens})
            assert np.isfinite(float(m["loss"]))

    def test_input_specs_cover_all_kinds(self):
        from repro.configs import SHAPES, get_config
        from repro.launch import specs as SP
        from repro.optim import adamw
        mesh = _mesh11()
        cfg = get_config("qwen2.5-3b")
        for name in ("train_4k", "prefill_32k", "decode_32k"):
            out = SP.input_specs(cfg, SHAPES[name], mesh,
                                 adamw(1e-4) if name == "train_4k" else None)
            assert "params" in out
            leaves = jax.tree_util.tree_leaves(out["params"])
            assert all(isinstance(l, jax.ShapeDtypeStruct) for l in leaves)

    def test_cache_specs_sharded_sanely(self):
        from repro.configs import SHAPES, get_config
        from repro.launch import specs as SP
        mesh = _mesh11()
        caches = SP.cache_specs(get_config("hymba-1.5b"),
                                SHAPES["decode_32k"], mesh)
        k = caches[0]["attn"]["k"]
        assert k.shape[1] == 1024      # ring buffer == window, not 32768
