"""AOT compile path: warmup, cost capture, executable serialization.

The paper's compiler emits a TDG artifact the runtime just *loads*; the
JAX analogue is ``lower.aot_compile_tdg`` (+ ``serialize.save_executable``)
— trace and XLA-compile ahead of time, replay anywhere without retracing.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (TDG, ReplayExecutor, aot_compile_tdg,
                        executable_serialization_available, load_warm,
                        taskgraph, warmup_and_save)
from repro.core.serialize import TaskFnRegistry, load_executable

REG = TaskFnRegistry()


@REG.register()
def _aot_scale(x):
    return x * 2.0 + 1.0


def _graph(n=6):
    tdg = TDG("aot")
    for t in range(n):
        tdg.add_task(_aot_scale, inouts=[f"x{t}"])
    return tdg, {f"x{t}": jnp.arange(4.0) + t for t in range(n)}


class TestAotCompile:
    def test_matches_lazy_replay(self):
        tdg, bufs = _graph()
        aot = aot_compile_tdg(tdg, bufs)
        lazy = ReplayExecutor(tdg).run(dict(bufs))
        got = aot(bufs)
        for k in lazy:
            np.testing.assert_allclose(np.asarray(got[k]),
                                       np.asarray(lazy[k]), rtol=1e-6)

    def test_accepts_abstract_specs(self):
        tdg, bufs = _graph()
        specs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                 for k, v in bufs.items()}
        aot = aot_compile_tdg(tdg, specs)     # no data touched
        got = aot(bufs)
        np.testing.assert_allclose(got["x0"], bufs["x0"] * 2.0 + 1.0)

    def test_cost_analysis_and_timings_captured(self):
        tdg, bufs = _graph()
        aot = aot_compile_tdg(tdg, bufs)
        assert aot.trace_seconds > 0 and aot.compile_seconds > 0
        if aot.cost_analysis is not None:     # backend-dependent
            assert aot.flops is not None and aot.flops > 0

    def test_donation_preserved_in_aot_path(self):
        # regression: aot_compile dropped donate_slots, silently changing
        # memory semantics vs the lazy jit path
        tdg = TDG("don")
        tdg.add_task(_aot_scale, inouts=["state"])
        aot = aot_compile_tdg(tdg, {"state": jnp.ones((4,))},
                              donate_slots=("state",))
        assert aot.donate_slots == ("state",)
        np.testing.assert_allclose(aot({"state": jnp.ones((4,))})["state"],
                                   3.0)

        ex = ReplayExecutor(TDG("don2"), donate_slots=("state",))
        ex.tdg.add_task(_aot_scale, inouts=["state"])
        aot2 = ex.aot_compile({"state": jnp.ones((4,))})
        assert aot2.donate_slots == ("state",)
        np.testing.assert_allclose(ex.run({"state": jnp.ones((4,))})["state"],
                                   3.0)

    def test_extra_buffer_keys_dropped(self):
        tdg, bufs = _graph()
        aot = aot_compile_tdg(tdg, bufs)
        got = aot({**bufs, "unrelated": jnp.zeros(9)})
        np.testing.assert_allclose(got["x1"], bufs["x1"] * 2.0 + 1.0)


class TestExecutorWarmup:
    def test_replay_executor_aot_populates_cache(self):
        tdg, bufs = _graph()
        ex = ReplayExecutor(tdg)
        aot = ex.aot_compile(bufs)
        assert len(ex._cache) == 1
        out = ex.run(dict(bufs))
        assert ex._cache[(list(ex._cache)[0])] is aot
        np.testing.assert_allclose(out["x0"], bufs["x0"] * 2.0 + 1.0)

    def test_region_warmup_skips_retrace(self):
        traces = []

        def payload(x):
            traces.append(1)        # runs once per *trace*, not per call
            return x + 1.0

        @taskgraph
        def region(g, a, b):
            g.task(payload, inouts=["a"])
            g.task(payload, inouts=["b"])

        specs = dict(a=jax.ShapeDtypeStruct((3,), jnp.float32),
                     b=jax.ShapeDtypeStruct((3,), jnp.float32))
        region.build_static(**specs)
        region.warmup(**specs)
        n_after_warmup = len(traces)
        assert n_after_warmup >= 1
        out = region(a=jnp.zeros(3), b=jnp.ones(3))
        out2 = region(a=jnp.ones(3), b=jnp.zeros(3))
        assert len(traces) == n_after_warmup   # zero retraces at call time
        assert region.replays == 2
        np.testing.assert_allclose(out["a"], 1.0)
        np.testing.assert_allclose(out2["b"], 1.0)

    def test_warmup_requires_tdg(self):
        @taskgraph
        def region(g, x):
            g.task(lambda x: x, inouts=["x"])

        with pytest.raises(RuntimeError, match="no TDG yet"):
            region.warmup(x=jnp.zeros(2))


@pytest.mark.skipif(not executable_serialization_available(),
                    reason="jax build lacks serialize_executable")
class TestExecutableSerialization:
    def test_warmup_and_save_round_trip(self, tmp_path):
        tdg, bufs = _graph()
        path = tmp_path / "region.tdg.json"
        info = warmup_and_save(tdg, bufs, path, REG)
        assert info["aot_path"].endswith(".aot")
        assert info["trace_seconds"] > 0

        tdg2, aot = load_warm(path, REG)
        assert aot is not None
        assert tdg2.num_tasks == tdg.num_tasks
        want = ReplayExecutor(tdg).run(dict(bufs))
        got = aot(bufs)                        # deserialized binary: no trace
        for k in want:
            np.testing.assert_allclose(np.asarray(got[k]),
                                       np.asarray(want[k]), rtol=1e-6)

    def test_load_executable_direct(self, tmp_path):
        tdg, bufs = _graph(3)
        aot = aot_compile_tdg(tdg, bufs)
        p = tmp_path / "exec.aot"
        from repro.core import save_executable
        save_executable(aot, p)
        aot2 = load_executable(p)
        assert aot2.fused == aot.fused
        got = aot2(bufs)
        np.testing.assert_allclose(got["x2"], bufs["x2"] * 2.0 + 1.0)

    def test_load_warm_without_sidecar(self, tmp_path):
        tdg, bufs = _graph(2)
        path = tmp_path / "plain.tdg.json"
        from repro.core import save_tdg
        save_tdg(tdg, path, REG)
        tdg2, aot = load_warm(path, REG)
        assert aot is None and tdg2.num_tasks == 2

    def test_load_warm_corrupt_sidecar_falls_back(self, tmp_path):
        # A damaged .aot sidecar must degrade to (tdg, None) — the caller
        # retraces — never crash the load.
        tdg, bufs = _graph(2)
        path = tmp_path / "corrupt.tdg.json"
        warmup_and_save(tdg, bufs, path, REG)
        with open(str(path) + ".aot", "wb") as f:
            f.write(b"\x00this is not a pickled executable\xff")
        tdg2, aot = load_warm(path, REG)
        assert aot is None and tdg2.num_tasks == tdg.num_tasks
        got = ReplayExecutor(tdg2).run(dict(bufs))   # retrace path still works
        np.testing.assert_allclose(got["x0"], bufs["x0"] * 2.0 + 1.0)

    def test_load_warm_truncated_sidecar_falls_back(self, tmp_path):
        tdg, bufs = _graph(2)
        path = tmp_path / "trunc.tdg.json"
        warmup_and_save(tdg, bufs, path, REG)
        aot_path = str(path) + ".aot"
        blob = open(aot_path, "rb").read()
        with open(aot_path, "wb") as f:
            f.write(blob[: max(1, len(blob) // 3)])
        tdg2, aot = load_warm(path, REG)
        assert aot is None and tdg2.num_tasks == tdg.num_tasks

    def test_load_warm_unknown_version_sidecar_falls_back(self, tmp_path):
        import pickle
        tdg, bufs = _graph(2)
        path = tmp_path / "vers.tdg.json"
        warmup_and_save(tdg, bufs, path, REG)
        aot_path = str(path) + ".aot"
        with open(aot_path, "rb") as f:
            blob = pickle.load(f)
        blob["version"] = 99
        with open(aot_path, "wb") as f:
            pickle.dump(blob, f)
        with pytest.raises(ValueError, match="version"):
            load_executable(aot_path)                # direct load: loud
        tdg2, aot = load_warm(path, REG)             # warm load: soft-fail
        assert aot is None and tdg2.num_tasks == tdg.num_tasks

    def test_load_warm_corrupt_graph_is_loud(self, tmp_path):
        # The graph JSON is authoritative — unlike the sidecar, damage
        # there must NOT be silently absorbed.
        tdg, bufs = _graph(2)
        path = tmp_path / "badgraph.tdg.json"
        warmup_and_save(tdg, bufs, path, REG)
        with open(path, "w") as f:
            f.write("{not json")
        with pytest.raises(Exception):
            load_warm(path, REG)


class TestCompileCacheDir:
    """Entry points keep JAX's persistent cache at one fixed path."""

    @pytest.fixture()
    def cache_dir_config(self):
        prev = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", prev)

    def test_env_var_wins_and_nothing_is_set(self, monkeypatch,
                                             cache_dir_config):
        from repro.launch import compile_cache

        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        assert compile_cache.enable() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before

    def test_default_is_fixed_path_in_checkout(self, monkeypatch,
                                               cache_dir_config):
        import pathlib

        from repro.launch import compile_cache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        root = pathlib.Path(__file__).resolve().parents[1]
        want = str(root / ".jax_cache")
        assert compile_cache.enable() == want
        assert jax.config.jax_compilation_cache_dir == want
        assert compile_cache.enable() == want          # same path every call
