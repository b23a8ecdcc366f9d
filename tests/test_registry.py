"""Kernel substrate registry + compat shim tests.

Fast section: registry semantics (registration, resolution, mode state,
eager env validation), compat feature detection, ReplayExecutor substrate
pinning, and one small ref-vs-interpret parity case per op — these run in
the default tier-1 sweep and are the acceptance check that all four Pallas
kernels run green in interpret mode through the registry.

Slow section (``-m slow``): broader interpret-mode parity sweeps over
shapes/dtypes, excluded from the default run to keep tier-1 fast.
"""
import pathlib
import subprocess
import sys
import threading

ROOT = pathlib.Path(__file__).resolve().parents[1]

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import TDG, ReplayExecutor
from repro.kernels import compat, ops, ref, registry


def _arr(rng, *shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(rng.standard_normal(shape) * scale, dtype)


# ------------------------------------------------------------------ registry

class TestRegistrySemantics:
    def test_all_ops_registered(self):
        assert {"attention", "rmsnorm", "grouped_matmul", "ssd"} <= set(
            registry.ops())

    def test_every_op_has_all_substrates(self):
        for op in ("attention", "rmsnorm", "grouped_matmul", "ssd"):
            modes = {m for _, m in registry.substrates(op)}
            assert modes == {"pallas", "ref", "interpret"}, (op, modes)

    def test_set_kernel_mode_rejects_bogus(self):
        with pytest.raises(ValueError, match="invalid kernel mode"):
            registry.set_kernel_mode("fastplz")

    def test_env_mode_validated_eagerly(self, monkeypatch):
        monkeypatch.setenv("REPRO_KERNELS", "bogus")
        with pytest.raises(ValueError, match="REPRO_KERNELS"):
            registry._env_mode()

    def test_mode_scope_restores_on_exit_and_error(self):
        before = registry.kernel_mode()
        with registry.kernel_mode_scope("interpret"):
            assert registry.kernel_mode() == "interpret"
        assert registry.kernel_mode() == before
        with pytest.raises(RuntimeError):
            with registry.kernel_mode_scope("ref"):
                raise RuntimeError("boom")
        assert registry.kernel_mode() == before

    def test_mode_scope_is_thread_local(self):
        """A scope on one thread must not leak into another (concurrent
        executors pin different substrates)."""
        seen = {}

        def worker():
            seen["mode"] = registry.kernel_mode()

        with registry.kernel_mode_scope("interpret"):
            t = threading.Thread(target=worker)
            t.start()
            t.join()
            assert registry.kernel_mode() == "interpret"
        assert seen["mode"] == registry.kernel_mode()  # base, not the scope

    def test_auto_resolves_per_platform(self):
        concrete = registry.resolved_mode("auto")
        assert concrete in ("pallas", "ref")
        assert concrete == ("pallas" if compat.tpu_available() else "ref")

    def test_unknown_op_raises_with_known_ops(self):
        with pytest.raises(KeyError, match="registered ops"):
            registry.resolve("transmogrify")

    def test_missing_substrate_lists_alternatives(self):
        registry.register("_probe_partial", "ref", fn=lambda: "ref")
        try:
            with pytest.raises(KeyError, match="available"):
                registry.resolve("_probe_partial", mode="interpret")
        finally:
            registry._impls.pop(("_probe_partial", "*", "ref"), None)

    def test_register_decorator_and_override(self):
        key = ("_probe_override", "*", "ref")
        try:
            @registry.register("_probe_override", "ref")
            def first():
                return 1

            assert registry.dispatch("_probe_override", mode="ref") == 1
            registry.register("_probe_override", "ref", fn=lambda: 2)
            assert registry.dispatch("_probe_override", mode="ref") == 2
        finally:
            registry._impls.pop(key, None)

    def test_cannot_register_auto(self):
        with pytest.raises(ValueError, match="resolution rule"):
            registry.register("x", "auto", fn=lambda: None)

    def test_dispatch_explicit_mode_overrides_global(self, rng):
        x, w = _arr(rng, 8, 64), _arr(rng, 64)
        with registry.kernel_mode_scope("interpret"):
            got = registry.dispatch("rmsnorm", x, w, mode="ref")
        np.testing.assert_allclose(got, ref.rmsnorm_ref(x, w),
                                   atol=1e-6, rtol=1e-6)

    @pytest.mark.slow
    def test_bogus_env_fails_at_import(self):
        proc = subprocess.run(
            [sys.executable, "-c", "import repro.kernels.ops"],
            capture_output=True, text=True,
            env={"PYTHONPATH": str(ROOT / "src"), "REPRO_KERNELS": "bogus",
                 "PATH": "/usr/bin:/bin:/usr/local/bin"},
            cwd=str(ROOT))
        assert proc.returncode != 0
        assert "REPRO_KERNELS" in proc.stderr


# -------------------------------------------------------------------- compat

class TestCompat:
    def test_compiler_params(self):
        from jax.experimental.pallas import tpu as pltpu

        params = compat.tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=1 << 20)
        assert isinstance(params, pltpu.CompilerParams)
        assert tuple(params.dimension_semantics) == ("parallel", "arbitrary")
        assert params.vmem_limit_bytes == 1 << 20

    def test_unknown_hint_field_raises(self):
        # Nothing is dropped silently any more: a hint field that
        # pltpu.CompilerParams does not have fails where it is passed.
        with pytest.raises(TypeError):
            compat.tpu_compiler_params(
                dimension_semantics=("parallel",),
                definitely_not_a_real_hint_field_xyz=1)

    def test_interpret_supported_here(self):
        # this repo's CPU CI runs every kernel through pallas_call's
        # interpret= keyword
        import inspect

        from jax.experimental import pallas as pl

        assert "interpret" in inspect.signature(pl.pallas_call).parameters

    def test_pallas_call_interpret_smoke(self):
        def kernel(x_ref, o_ref):
            o_ref[...] = x_ref[...] * 2.0

        x = jnp.ones((8, 128), jnp.float32)
        out = compat.pallas_call(
            kernel, out_shape=jnp.zeros_like(x),
            compiler_params=compat.tpu_compiler_params(),
            name="double", interpret=True)(x)
        np.testing.assert_allclose(out, 2.0)


# -------------------------------------------------- executor substrate pinning

class TestReplayExecutorPinning:
    @pytest.fixture()
    def probe_op(self):
        registry.register("_probe_sub", "ref",
                          fn=lambda x: x + jnp.float32(1.0))
        registry.register("_probe_sub", "interpret",
                          fn=lambda x: x + jnp.float32(2.0))
        registry.register("_probe_sub", "pallas",
                          fn=lambda x: x + jnp.float32(3.0))
        yield "_probe_sub"
        for mode in ("ref", "interpret", "pallas"):
            registry._impls.pop(("_probe_sub", "*", mode), None)

    def _tdg(self, probe_op):
        tdg = TDG("probe")
        tdg.add_task(lambda x: registry.dispatch(probe_op, x),
                     ins=["x"], outs=["y"])
        return tdg, {"x": jnp.zeros((4,), jnp.float32)}

    def test_substrate_resolved_once_at_construction(self, probe_op):
        tdg, bufs = self._tdg(probe_op)
        ex = ReplayExecutor(tdg, kernel_mode="interpret")
        registry.set_kernel_mode("ref")
        try:
            out = ex.run(dict(bufs))
        finally:
            registry.set_kernel_mode("auto")
        # global says ref (+1) but the executor pinned interpret (+2)
        np.testing.assert_allclose(out["y"], 2.0)

    def test_default_mode_captured_from_global(self, probe_op):
        tdg, bufs = self._tdg(probe_op)
        with registry.kernel_mode_scope("interpret"):
            ex = ReplayExecutor(tdg)
        assert ex.kernel_mode == "interpret"
        np.testing.assert_allclose(ex.run(dict(bufs))["y"], 2.0)

    def test_cache_keyed_by_mode(self, probe_op):
        tdg, bufs = self._tdg(probe_op)
        a = ReplayExecutor(tdg, kernel_mode="ref")
        b = ReplayExecutor(tdg, kernel_mode="interpret")
        np.testing.assert_allclose(a.run(dict(bufs))["y"], 1.0)
        np.testing.assert_allclose(b.run(dict(bufs))["y"], 2.0)

    def test_auto_is_pinned_to_concrete(self, probe_op):
        tdg, _ = self._tdg(probe_op)
        ex = ReplayExecutor(tdg, kernel_mode="auto")
        assert ex.kernel_mode in ("pallas", "ref")


# ------------------------------------------- ref vs interpret parity (fast)

class TestParityFast:
    """One small case per op: the registry's interpret substrate (real
    Pallas kernel bodies) must match the jnp references on CPU."""

    def _pair(self, op, *args, **kwargs):
        with registry.kernel_mode_scope("ref"):
            want = registry.dispatch(op, *args, **kwargs)
        with registry.kernel_mode_scope("interpret"):
            got = registry.dispatch(op, *args, **kwargs)
        return got, want

    def test_rmsnorm(self, rng):
        got, want = self._pair("rmsnorm", _arr(rng, 16, 64), _arr(rng, 64))
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)

    def test_grouped_matmul(self, rng):
        got, want = self._pair("grouped_matmul",
                               _arr(rng, 2, 16, 128, scale=0.3),
                               _arr(rng, 2, 128, 128, scale=0.3))
        np.testing.assert_allclose(got, want, atol=3e-3, rtol=1e-4)

    def test_attention(self, rng):
        q, k, v = (_arr(rng, 1, 64, 2, 32), _arr(rng, 1, 64, 1, 32),
                   _arr(rng, 1, 64, 1, 32))
        got, want = self._pair("attention", q, k, v, causal=True)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)

    def test_ssd(self, rng):
        x = _arr(rng, 1, 64, 2, 16)
        dt = jnp.abs(_arr(rng, 1, 64, 2)) * 0.1 + 0.01
        A = -jnp.abs(_arr(rng, 2)) - 0.1
        Bm = _arr(rng, 1, 64, 1, 16, scale=0.5)
        Cm = _arr(rng, 1, 64, 1, 16, scale=0.5)
        (y_got, h_got), (y_want, h_want) = self._pair(
            "ssd", x, dt, A, Bm, Cm, chunk=32)
        np.testing.assert_allclose(y_got, y_want, atol=1e-3, rtol=1e-3)
        np.testing.assert_allclose(h_got, h_want, atol=1e-3, rtol=1e-3)


# ------------------------------------------- ref vs interpret parity (slow)

@pytest.mark.slow
class TestParitySweep:
    """Broader interpret sweeps (shapes, dtypes, op variants) — `-m slow`."""

    def _pair(self, op, *args, **kwargs):
        with registry.kernel_mode_scope("ref"):
            want = registry.dispatch(op, *args, **kwargs)
        with registry.kernel_mode_scope("interpret"):
            got = registry.dispatch(op, *args, **kwargs)
        return got, want

    @pytest.mark.parametrize("shape", [(4, 17, 64), (2, 128, 256)])
    @pytest.mark.parametrize("residual", [False, True])
    def test_rmsnorm(self, rng, shape, residual):
        x, w = _arr(rng, *shape), _arr(rng, shape[-1])
        r = _arr(rng, *shape) if residual else None
        got, want = self._pair("rmsnorm", x, w, residual=r)
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("E,C,d,f", [(4, 64, 128, 128), (2, 100, 256, 128)])
    def test_grouped_matmul(self, rng, E, C, d, f, dtype):
        got, want = self._pair("grouped_matmul",
                               _arr(rng, E, C, d, dtype=dtype, scale=0.3),
                               _arr(rng, E, d, f, dtype=dtype, scale=0.3))
        atol = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}[dtype] * d
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=atol, rtol=1e-2)

    @pytest.mark.parametrize("kw", [
        dict(causal=True), dict(causal=False), dict(causal=True, window=64),
        dict(causal=True, chunk=64), dict(causal=True, q_offset=128),
    ])
    def test_attention_variants(self, rng, kw):
        sq = 1 if kw.get("q_offset") else 128
        q = _arr(rng, 2, sq, 4, 64)
        k, v = _arr(rng, 2, 128, 2, 64), _arr(rng, 2, 128, 2, 64)
        got, want = self._pair("attention", q, k, v, **kw)
        np.testing.assert_allclose(got, want, atol=3e-5, rtol=3e-5)

    @pytest.mark.parametrize("S,H,P,G,N,chunk", [
        (128, 2, 32, 1, 16, 32), (256, 4, 64, 2, 32, 64),
    ])
    def test_ssd(self, rng, S, H, P, G, N, chunk):
        x = _arr(rng, 2, S, H, P)
        dt = jnp.abs(_arr(rng, 2, S, H)) * 0.1 + 0.01
        A = -jnp.abs(_arr(rng, H)) - 0.1
        Bm = _arr(rng, 2, S, G, N, scale=0.5)
        Cm = _arr(rng, 2, S, G, N, scale=0.5)
        D = _arr(rng, H)
        (y_got, h_got), (y_want, h_want) = self._pair(
            "ssd", x, dt, A, Bm, Cm, D=D, chunk=chunk)
        np.testing.assert_allclose(y_got, y_want, atol=1e-3, rtol=1e-3)
        np.testing.assert_allclose(h_got, h_want, atol=1e-3, rtol=1e-3)
