"""Wave-fused lowering: parity, jaxpr shrink, interning, fallbacks.

The tentpole invariants:

* fused replay == unfused replay == EagerExecutor, on every graph shape
  (chain / diamond / pipeline grid / MoE-style heterogeneous fan-out);
* an isomorphic-wave graph lowers to O(waves) task-body instances, not
  O(tasks) — asserted on the traced jaxpr;
* structurally identical TDGs intern to ONE shared compiled executable.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (TDG, EagerExecutor, ReplayExecutor, classify_wave,
                        clear_intern_cache, fused_tdg_as_function,
                        fusion_plan, intern_stats, lower_tdg, taskgraph,
                        tdg_as_function, topo_waves)


def _mm(x):
    return jnp.tanh(x @ x.T) @ x * 0.5 + x


def _grid_tdg(n_waves=4, n_tasks=8, dim=8):
    """`n_waves` waves of `n_tasks` isomorphic chains (paper Listing 1)."""
    tdg = TDG(f"grid{n_waves}x{n_tasks}")
    for w in range(n_waves):
        for t in range(n_tasks):
            tdg.add_task(_mm, inouts=[f"x{t}"], name=f"t{w}.{t}")
    rng = np.random.default_rng(0)
    bufs = {f"x{t}": jnp.asarray(rng.standard_normal((dim, dim)), jnp.float32)
            for t in range(n_tasks)}
    return tdg, bufs


def _chain_tdg(n=12):
    tdg = TDG("chain")
    for i in range(n):
        tdg.add_task(lambda x: x * 1.001 + 0.5, inouts=["x"], name=f"c{i}")
    return tdg, {"x": jnp.arange(6.0)}


def _diamond_tdg():
    tdg = TDG("diamond")
    tdg.add_task(lambda x: x + 1.0, ins=["x"], outs=["a"])
    tdg.add_task(lambda a: a * 2.0, ins=["a"], outs=["b"])
    tdg.add_task(lambda a: a * 3.0, ins=["a"], outs=["c"])
    tdg.add_task(lambda b, c: b + c, ins=["b", "c"], outs=["y"])
    return tdg, {"x": jnp.arange(5.0)}


def _pipeline_grid_tdg(stages=4, micro=6, dim=8):
    """Forward pipeline over real matmul payloads (isomorphic diagonals)."""
    tdg = TDG("pipe")
    for m in range(micro):
        for s in range(stages):
            ins = [f"act[{m},{s-1}]"] if s > 0 else [f"in{m}"]
            tdg.add_task(_mm, ins=ins, outs=[f"act[{m},{s}]"],
                         name=f"F[{m},{s}]")
    rng = np.random.default_rng(1)
    bufs = {f"in{m}": jnp.asarray(rng.standard_normal((dim, dim)), jnp.float32)
            for m in range(micro)}
    return tdg, bufs


def _moe_tdg(n_tokens_blocks=6, dim=16):
    """MoE-style: shared router weight + heterogeneous expert payloads."""
    tdg = TDG("moe")
    rng = np.random.default_rng(2)

    def route(x, w):
        return x @ w

    def expert_a(x):
        return jax.nn.gelu(x) * 1.5

    def expert_b(x):
        return jnp.tanh(x) - 0.1 * x

    for b in range(n_tokens_blocks):
        tdg.add_task(route, ins=[f"x{b}", "w"], outs=[f"r{b}"])
        fn = expert_a if b % 2 == 0 else expert_b
        tdg.add_task(fn, ins=[f"r{b}"], outs=[f"e{b}"])
    tdg.add_task(lambda *es: sum(es),
                 ins=[f"e{b}" for b in range(n_tokens_blocks)], outs=["y"])
    bufs = {f"x{b}": jnp.asarray(rng.standard_normal((4, dim)), jnp.float32)
            for b in range(n_tokens_blocks)}
    bufs["w"] = jnp.asarray(rng.standard_normal((dim, dim)), jnp.float32)
    return tdg, bufs


def _cholesky_tdg(nb, bs=128):
    """Tiled Cholesky (potrf/trsm/syrk/gemm over the lower tiles): each
    step's gemm reads ``L[j,k]`` once per ``i > j``, a repeated operand."""
    def potrf(a):
        return jnp.linalg.cholesky(a)

    def trsm(l_kk, a):
        return jax.scipy.linalg.solve_triangular(l_kk, a.T, lower=True).T

    def syrk(a, l):
        return a - l @ l.T

    def gemm(a, l1, l2):
        return a - l1 @ l2.T

    tdg = TDG(f"cholesky{nb}x{bs}")
    for k in range(nb):
        tdg.add_task(potrf, ins=[f"A{k}_{k}"], outs=[f"L{k}_{k}"])
        for i in range(k + 1, nb):
            tdg.add_task(trsm, ins=[f"L{k}_{k}", f"A{i}_{k}"],
                         outs=[f"L{i}_{k}"])
        for i in range(k + 1, nb):
            tdg.add_task(syrk, ins=[f"A{i}_{i}", f"L{i}_{k}"],
                         outs=[f"A{i}_{i}"])
            for j in range(k + 1, i):
                tdg.add_task(gemm, ins=[f"A{i}_{j}", f"L{i}_{k}", f"L{j}_{k}"],
                             outs=[f"A{i}_{j}"])
    n = nb * bs
    m = np.random.default_rng(nb).standard_normal((n, n))
    a = (m @ m.T + n * np.eye(n)).astype(np.float32)
    bufs = {f"A{i}_{j}": jnp.asarray(a[i * bs:(i + 1) * bs, j * bs:(j + 1) * bs])
            for i in range(nb) for j in range(i + 1)}
    return tdg, bufs


GRAPHS = {
    "grid": _grid_tdg,
    "chain": _chain_tdg,
    "diamond": _diamond_tdg,
    "pipeline": _pipeline_grid_tdg,
    "moe": _moe_tdg,
}


class TestParity:
    @pytest.mark.parametrize("graph", sorted(GRAPHS))
    def test_fused_vs_unfused_vs_eager(self, graph):
        tdg, bufs = GRAPHS[graph]()
        eager = EagerExecutor(tdg, n_workers=3).run(dict(bufs))
        unfused = lower_tdg(tdg, fuse=False, intern=False)(dict(bufs))
        fused = lower_tdg(tdg, fuse=True, intern=False)(dict(bufs))
        assert set(eager) == set(unfused) == set(fused)
        for k in fused:
            np.testing.assert_allclose(np.asarray(fused[k]),
                                       np.asarray(unfused[k]),
                                       rtol=2e-5, atol=2e-5)
            np.testing.assert_allclose(np.asarray(fused[k]),
                                       np.asarray(eager[k]),
                                       rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("graph", ["grid", "pipeline", "moe"])
    def test_map_batcher_parity(self, graph):
        tdg, bufs = GRAPHS[graph]()
        vmapped = lower_tdg(tdg, fuse=True, intern=False)(dict(bufs))
        mapped = lower_tdg(tdg, fuse=True, intern=False,
                           batcher="map")(dict(bufs))
        for k in vmapped:
            np.testing.assert_allclose(np.asarray(mapped[k]),
                                       np.asarray(vmapped[k]),
                                       rtol=2e-5, atol=2e-5)

    def test_grad_through_fused(self):
        tdg = TDG("g")
        for t in range(4):
            tdg.add_task(lambda x: x * 2.0, ins=[f"x{t}"], outs=[f"y{t}"])
        tdg.add_task(lambda *ys: sum((y ** 2).sum() for y in ys),
                     ins=[f"y{t}" for t in range(4)], outs=["l"])
        f = lower_tdg(tdg, jit=False, fuse=True)
        x = jnp.arange(3.0)
        g = jax.grad(lambda x: f({f"x{t}": x for t in range(4)})["l"])(x)
        np.testing.assert_allclose(g, 4 * 8.0 * x)


class TestWaveAnalysis:
    def test_plan_groups_isomorphic_waves(self):
        tdg, bufs = _grid_tdg(n_waves=5, n_tasks=7)
        plan = fusion_plan(tdg, bufs)
        assert plan.num_tasks == 35
        assert plan.num_waves == 5
        assert plan.num_classes == 5          # one class per wave
        assert plan.fused_tasks == 35 and plan.fused_fraction == 1.0

    def test_plan_respects_shapes(self):
        # same payload, two shapes in one wave -> two classes
        tdg = TDG("shapes")
        fn = lambda x: x + 1.0  # noqa: E731
        for t in range(4):
            tdg.add_task(fn, ins=[f"a{t}"], outs=[f"b{t}"])
        bufs = {f"a{t}": jnp.zeros((4,) if t < 2 else (8,)) for t in range(4)}
        plan = fusion_plan(tdg, bufs)
        assert plan.num_classes == 2
        assert sorted(c.size for c in plan.classes) == [2, 2]

    def test_structural_plan_without_shapes(self):
        tdg, _ = _grid_tdg(n_waves=2, n_tasks=4)
        plan = fusion_plan(tdg)     # structural upper bound, no buffers
        assert plan.num_classes == 2 and plan.fused_tasks == 8

    def test_classify_shared_arg_positions(self):
        tdg = TDG("sh")
        fn = lambda x, w: x * w  # noqa: E731
        for t in range(3):
            tdg.add_task(fn, ins=[f"x{t}", "w"], outs=[f"y{t}"])
        waves = topo_waves(tdg)
        env = {f"x{t}": jnp.zeros(3) for t in range(3)}
        env["w"] = jnp.zeros(3)
        from repro.core.fuse import value_signature
        [cls] = classify_wave(tdg, 0, waves[0],
                              lambda s: value_signature(env[s]))
        assert cls.shared == (False, True)    # w broadcasts, x stacks

    def test_heterogeneous_wave_falls_back(self):
        tdg, bufs = _moe_tdg()
        f = fused_tdg_as_function(tdg)
        f(dict(bufs))
        plan = f.last_plan
        # router wave fuses, expert wave splits into the two payload classes
        assert plan.fused_classes >= 1
        assert plan.fused_tasks < plan.num_tasks  # reduce task is unrolled
        assert sum(c.size for c in plan.classes) == plan.num_tasks

    def test_identical_input_class_evaluates_once(self):
        # N tasks, same fn, same input slot -> single evaluation fans out
        calls = []

        def fn(x):
            calls.append(1)
            return x + 1.0

        tdg = TDG("allshared")
        for t in range(5):
            tdg.add_task(fn, ins=["x"], outs=[f"y{t}"])
        out = fused_tdg_as_function(tdg)({"x": jnp.arange(3.0)})
        assert len(calls) == 1
        for t in range(5):
            np.testing.assert_allclose(out[f"y{t}"], jnp.arange(3.0) + 1)


class TestStackedHandOff:
    """A fused class's stacked result is read back as a view (whole or a
    slice) by later classes, and a repeated operand is broadcast in
    sub-classes instead of stacked once per member."""

    @pytest.mark.parametrize("nb", [4, 6, 8])
    def test_tiled_cholesky_fused_vs_unfused_vs_eager(self, nb):
        tdg, bufs = _cholesky_tdg(nb)
        with jax.default_matmul_precision("highest"):
            eager = EagerExecutor(tdg, n_workers=3).run(dict(bufs))
            unfused = lower_tdg(tdg, fuse=False, intern=False)(dict(bufs))
            fused = lower_tdg(tdg, fuse=True, intern=False)(dict(bufs))
        assert set(eager) == set(unfused) == set(fused)
        for k in fused:
            for ref in (unfused, eager):
                np.testing.assert_allclose(np.asarray(fused[k]),
                                           np.asarray(ref[k]),
                                           rtol=1e-5, atol=1e-4, err_msg=k)

    def test_cholesky_gemm_cut_on_repeated_operand(self):
        nb = 8
        tdg, bufs = _cholesky_tdg(nb)
        plan = fusion_plan(tdg, bufs)
        gemm = [c for c in plan.classes
                if tdg.tasks[c.tids[0]].fn.__name__ == "gemm"]
        # steps 0..nb-4 have >= 2 distinct L[j,k]: each is cut by j, the
        # third argument, which every sub-class broadcasts
        assert plan.split_classes == nb - 3
        cut = [c for c in gemm if c.wave < 3 * (nb - 3)]
        assert [c.wave for c in gemm if c not in cut] == [3 * (nb - 3) + 2]
        for c in cut:
            assert c.split is not None and c.split[0] == 2
            assert len({tdg.tasks[t].ins[2] for t in c.tids}) == 1
            if c.size > 1:
                assert c.shared == (False, False, True)
        assert plan.split_off == sum(len(range(k + 1, nb - 1)) - 1
                                     for k in range(nb - 3))
        # past step 0 every fused gemm reads A whole, L[i,k] as a slice
        for c in gemm:
            if c.fused and c.wave > 2:
                assert c.args == ("whole", "slice", "broadcast")
                assert c.stacked_bytes == 0

    def test_cholesky_stacks_only_region_inputs(self):
        """In the program, ``concatenate`` (a stacked copy) appears only
        where step 0 reads the region's input tiles: no hand-off between
        waves copies."""
        import re

        tdg, bufs = _cholesky_tdg(8)
        f = lower_tdg(tdg, jit=False, intern=False)
        text = jax.jit(f).lower(bufs).as_text()
        plan = f.last_plan
        stacked = [c for c in plan.classes if "stacked" in c.args]
        assert stacked and all(c.wave <= 2 for c in stacked)   # step 0
        inputs = set(tdg.input_slots)
        for c in stacked:
            for i, kind in enumerate(c.args):
                if kind == "stacked":
                    assert {tdg.tasks[t].ins[i] for t in c.tids} <= inputs
        n_stacked = sum(c.args.count("stacked") for c in plan.classes)
        assert len(re.findall(r"stablehlo\.concatenate", text)) == n_stacked

    def test_plan_counters_agree_with_trace(self):
        from repro.core import spans

        tdg, bufs = _cholesky_tdg(6)
        offline = fusion_plan(tdg, bufs).summary()
        before = spans.counters()
        f = lower_tdg(tdg, jit=False, intern=False)
        jax.eval_shape(f, bufs)
        after = spans.counters()
        summary = f.last_plan.summary()

        def delta(name):
            return after.get(name, 0) - before.get(name, 0)

        for k, n in summary["args"].items():
            assert delta(f"taskgraph.fuse.arg_{k}") == n
        assert delta("taskgraph.fuse.split_off") == summary["split_off"] > 0
        assert delta("taskgraph.fuse.stacked_bytes") == \
            summary["stacked_bytes"] > 0
        offline.pop("decisions"), summary.pop("decisions")
        assert offline == summary

    def test_tiled_matmul_cuts_every_step_and_hands_c_on_whole(self):
        """``C[i,j] += A[i,k] B[k,j]``: cutting step k by ``A[i,k]`` pays
        only if step k+1 is cut the same way, which the look ahead at the
        readers' own cuts sees; every step is cut and ``C`` is handed on
        whole."""
        def mac(c, a, b):
            return c + a @ b

        nb, bs = 3, 128
        tdg = TDG("matmul")
        for k in range(nb):
            for i in range(nb):
                for j in range(nb):
                    tdg.add_task(mac, ins=[f"C{i}{j}", f"A{i}{k}", f"B{k}{j}"],
                                 outs=[f"C{i}{j}"])
        rng = np.random.default_rng(4)
        bufs = {f"{p}{i}{j}": jnp.asarray(rng.standard_normal((bs, bs)),
                                          jnp.float32)
                for p in "ABC" for i in range(nb) for j in range(nb)}
        plan = fusion_plan(tdg, bufs)
        assert plan.split_classes == nb and plan.split_off == nb * (nb - 1)
        for c in plan.classes:
            assert c.split[0] == 1 and c.args[1] == "broadcast"
            assert c.args[0] == ("stacked" if c.wave == 0 else "whole")
        with jax.default_matmul_precision("highest"):
            fused = lower_tdg(tdg, intern=False)(dict(bufs))
            unfused = lower_tdg(tdg, fuse=False, intern=False)(dict(bufs))
        for k in fused:
            np.testing.assert_allclose(np.asarray(fused[k]),
                                       np.asarray(unfused[k]),
                                       rtol=1e-5, atol=1e-4, err_msg=k)

    def test_tiny_repeated_operand_stays_whole(self):
        """8x8 tiles: cutting would add calls worth more than the copies
        it saves (the cost model's ``split_call_bytes``), so the plan is as
        it was."""
        tdg, bufs = _cholesky_tdg(6, bs=8)
        plan = fusion_plan(tdg, bufs)
        assert plan.split_off == plan.split_classes == 0
        assert all(c.split is None for c in plan.classes)

    def test_cut_break_even_comes_from_the_cost_model(self, monkeypatch):
        """The cut's per-call charge is the process cost model's, and the
        plan key carries it: with no charge the 8x8 tiles are cut too."""
        from repro.core import costmodel

        tdg, bufs = _cholesky_tdg(6, bs=8)
        key = costmodel.plan_key("vmap")
        monkeypatch.setattr(costmodel.default_model(), "split_call_bytes", 0)
        assert costmodel.plan_key("vmap") != key
        plan = fusion_plan(tdg, bufs)
        assert plan.split_classes == 6 - 3
        with jax.default_matmul_precision("highest"):
            fused = lower_tdg(tdg, intern=False)(dict(bufs))
            unfused = lower_tdg(tdg, fuse=False, intern=False)(dict(bufs))
        for k in fused:
            np.testing.assert_allclose(np.asarray(fused[k]),
                                       np.asarray(unfused[k]),
                                       rtol=1e-5, atol=1e-4, err_msg=k)

    @pytest.mark.parametrize("graph", sorted(GRAPHS))
    def test_graphs_without_repeated_operand_keep_their_classes(self, graph):
        tdg, bufs = GRAPHS[graph]()
        plan = fusion_plan(tdg, bufs)
        structural = [c.tids for wi, wave in enumerate(topo_waves(tdg))
                      for c in classify_wave(tdg, wi, wave, None)]
        assert [c.tids for c in plan.classes] == structural
        assert plan.split_off == 0

    def test_isomorphic_waves_hand_off_whole_stacks(self):
        tdg, bufs = _grid_tdg(n_waves=4, n_tasks=6)
        plan = fusion_plan(tdg, bufs)
        assert [c.args for c in plan.classes] == \
            [("stacked",)] + [("whole",)] * 3
        assert plan.stacked_bytes == 6 * 8 * 8 * 4     # the inputs, once


class TestJaxprSize:
    def test_isomorphic_wave_graph_lowers_to_o_waves_bodies(self):
        n_waves, n_tasks = 4, 16
        tdg, bufs = _grid_tdg(n_waves=n_waves, n_tasks=n_tasks)
        unfused = jax.make_jaxpr(lower_tdg(tdg, jit=False, fuse=False))(bufs)
        fused = jax.make_jaxpr(lower_tdg(tdg, jit=False, fuse=True))(bufs)

        def dots(jaxpr):
            return sum(1 for e in jaxpr.eqns
                       if e.primitive.name == "dot_general")

        # body instances: O(tasks) unrolled (2 dots/body), O(waves) fused
        assert dots(unfused) == 2 * n_waves * n_tasks
        assert dots(fused) == 2 * n_waves
        # total program shrinks even counting stack/unstack bookkeeping
        assert len(fused.eqns) < len(unfused.eqns)

    def test_fallback_when_explicit_order(self):
        tdg, bufs = _grid_tdg(2, 4)
        order = list(range(tdg.num_tasks))
        f = lower_tdg(tdg, order=order, jit=False)
        assert not hasattr(f, "last_plan")     # unrolled form was chosen
        out = f(dict(bufs))
        ref = lower_tdg(tdg, fuse=False, intern=False)(dict(bufs))
        for k in out:
            np.testing.assert_allclose(np.asarray(out[k]), np.asarray(ref[k]),
                                       rtol=1e-6)

    def test_fuse_env_var_kill_switch(self, monkeypatch):
        from repro.core import fuse_enabled
        monkeypatch.setenv("REPRO_FUSE", "0")
        assert not fuse_enabled("auto")
        monkeypatch.setenv("REPRO_FUSE", "1")
        assert fuse_enabled("auto")
        assert fuse_enabled(True) and not fuse_enabled(False)


class TestInterning:
    def setup_method(self):
        clear_intern_cache()

    def test_structurally_identical_tdgs_share_executable(self):
        def fn(x):
            return x * 2.0 + 1.0

        def mk(name):
            tdg = TDG(name)
            for w in range(3):
                for t in range(4):
                    tdg.add_task(fn, inouts=[f"b{t}"])
            return tdg

        bufs = {f"b{t}": jnp.arange(4.0) + t for t in range(4)}
        a, b = ReplayExecutor(mk("A")), ReplayExecutor(mk("B"))
        o1, o2 = a.run(dict(bufs)), b.run(dict(bufs))
        stats = intern_stats()
        assert stats["misses"] == 1 and stats["hits"] == 1
        assert stats["entries"] == 1           # ONE shared compiled executable
        for k in o1:
            np.testing.assert_allclose(o1[k], o2[k])

    def test_regions_with_renamed_slots_intern(self):
        def payload(x):
            return x * 3.0 - 1.0

        @taskgraph
        def region_a(g, u0, u1):
            g.task(payload, inouts=["u0"])
            g.task(payload, inouts=["u1"])

        @taskgraph
        def region_b(g, v0, v1):
            g.task(payload, inouts=["v0"])
            g.task(payload, inouts=["v1"])

        region_a(u0=jnp.ones(3), u1=jnp.zeros(3))   # record
        region_b(v0=jnp.ones(3), v1=jnp.zeros(3))   # record
        clear_intern_cache()
        ra = region_a(u0=jnp.ones(3), u1=jnp.zeros(3))   # replay: miss
        rb = region_b(v0=jnp.ones(3), v1=jnp.zeros(3))   # replay: HIT
        stats = intern_stats()
        assert (stats["misses"], stats["hits"]) == (1, 1)
        np.testing.assert_allclose(ra["u0"], rb["v0"])

    def test_different_payloads_do_not_collide(self):
        def f1(x):
            return x + 1.0

        def f2(x):
            return x - 1.0

        def mk(fn):
            tdg = TDG("p")
            tdg.add_task(fn, inouts=["x"])
            tdg.add_task(fn, inouts=["x"])
            return tdg

        bufs = {"x": jnp.zeros(3)}
        o1 = ReplayExecutor(mk(f1)).run(dict(bufs))
        o2 = ReplayExecutor(mk(f2)).run(dict(bufs))
        assert intern_stats()["entries"] == 2
        np.testing.assert_allclose(o1["x"], 2.0)
        np.testing.assert_allclose(o2["x"], -2.0)

    def test_different_structure_does_not_collide(self):
        def fn(x):
            return x + 1.0

        t1, t2 = TDG("a"), TDG("b")
        t1.add_task(fn, inouts=["x"])
        t2.add_task(fn, inouts=["x"])
        t2.add_task(fn, inouts=["x"])
        ReplayExecutor(t1).run({"x": jnp.zeros(2)})
        ReplayExecutor(t2).run({"x": jnp.zeros(2)})
        assert intern_stats()["entries"] == 2

    def test_explicit_intern_requires_jit_and_default_order(self):
        tdg, _ = _chain_tdg(3)
        with pytest.raises(ValueError, match="intern=True"):
            lower_tdg(tdg, jit=False, intern=True)
        with pytest.raises(ValueError, match="intern=True"):
            lower_tdg(tdg, order=[0, 1, 2], intern=True)

    def test_intern_cache_is_lru_bounded(self, monkeypatch):
        from repro.core import lower as lower_mod
        monkeypatch.setattr(lower_mod, "_INTERN_CAP", 2)
        bufs = {"x": jnp.zeros(2)}
        for i in range(4):
            tdg = TDG(f"lru{i}")
            tdg.add_task(lambda x, i=i: x + float(i), inouts=["x"])
            ReplayExecutor(tdg).run(dict(bufs))   # fresh closure: always miss
        stats = intern_stats()
        assert stats["entries"] <= 2
        assert stats["evictions"] == 2

    def test_kernel_mode_keys_intern_cache(self):
        from repro.kernels import ops

        def fn(x, w):
            return ops.rmsnorm(x, w)

        def mk():
            tdg = TDG("k")
            for t in range(2):
                tdg.add_task(fn, ins=[f"x{t}", "w"], outs=[f"y{t}"])
            return tdg

        bufs = {f"x{t}": jnp.ones((4, 8)) for t in range(2)}
        bufs["w"] = jnp.ones(8)
        ReplayExecutor(mk(), kernel_mode="ref").run(dict(bufs))
        ReplayExecutor(mk(), kernel_mode="interpret").run(dict(bufs))
        assert intern_stats()["entries"] == 2  # substrate is part of the key


class TestRegionFusionIntegration:
    def test_region_replay_fused_matches_record(self):
        @taskgraph
        def region(g, **kw):
            for t in range(6):
                g.task(_mm, inouts=[f"x{t}"], name=f"a{t}")
            for t in range(6):
                g.task(_mm, inouts=[f"x{t}"], name=f"b{t}")

        rng = np.random.default_rng(3)
        bufs = {f"x{t}": jnp.asarray(rng.standard_normal((8, 8)), jnp.float32)
                for t in range(6)}
        rec = region(**bufs)
        rep = region(**bufs)
        assert region.records == 1 and region.replays == 1
        for k in rec:
            np.testing.assert_allclose(np.asarray(rec[k]), np.asarray(rep[k]),
                                       rtol=2e-5, atol=2e-5)
        assert region.schedule_summary()["fusion"]["fused_tasks"] == 12

    def test_fuse_false_region_still_works(self):
        @taskgraph(fuse=False)
        def region(g, x):
            g.task(lambda x: x + 1.0, inouts=["x"])
            g.task(lambda x: x * 2.0, inouts=["x"])

        o1 = region(x=jnp.arange(4.0))
        o2 = region(x=jnp.arange(4.0))
        np.testing.assert_allclose(o1["x"], o2["x"])


class TestListScheduleRegression:
    def test_no_dead_pending_path(self):
        # Before the fix, an (unreachable) branch popped from an
        # always-empty list; the scheduler now raises only on impossible
        # (cyclic) inputs and completes every DAG.
        from repro.core import list_schedule, validate_execution_order
        tdg, _ = _pipeline_grid_tdg(stages=3, micro=4)
        sched = list_schedule(tdg, 3)
        assert validate_execution_order(tdg, sched.order())
        assert len(sched.start_time) == tdg.num_tasks

    def test_forged_cycle_rejected_loudly(self):
        # a cyclic graph dies with a clear error (either topo_order's cycle
        # check or the scheduler's stall guard), never a silent IndexError
        from repro.core import list_schedule
        tdg, _ = _diamond_tdg()
        tdg.preds[0].add(3)     # forge a cycle bypassing add_task
        tdg.succs[3].add(0)
        with pytest.raises((ValueError, RuntimeError)):
            list_schedule(tdg, 2)
