"""Cost-model-driven adaptive fusion: probing, decisions, caches, buckets.

The invariants that make adaptivity safe to ship:

* a backend without a ``cost_analysis`` degrades to None / UNMEASURED,
  never a lie;
* the decision matrix is exactly the documented policy, and an unmeasured
  payload always falls back to the static vmap plan;
* different batcher *plans* never share an interned executable, while the
  ``REPRO_ADAPTIVE=0`` kill switch makes "auto" share the static entry;
* adaptive replay is bit-exact against static replay (the model picks
  where a class computes, never what);
* bucket fitting is the exact pad-minimizing DP, and the tuner respects
  its retrace budget and the kill switch.
"""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (TDG, ReplayExecutor, clear_intern_cache, fusion_plan,
                        intern_stats, lower_tdg)
from repro.core import costmodel as cm
from repro.core import lower as lower_mod
from repro.serving import RegionServer, WarmPool
from repro.serving.pool import PoolEntry

f32 = jnp.float32


# ------------------------------------------------- capture_cost_analysis

class _Compiled:
    """Fake jax.stages.Compiled returning a canned cost_analysis."""

    def __init__(self, result=None, raises=False):
        self._result, self._raises = result, raises

    def cost_analysis(self):
        if self._raises:
            raise self._raises("no analysis on this backend")
        return self._result


class TestCaptureCostAnalysis:
    def test_reexported_into_lower(self):
        # tests/serialize reach it as lower._capture_cost_analysis; the
        # canonical def moved to costmodel but the old name must keep working.
        assert lower_mod._capture_cost_analysis is cm.capture_cost_analysis

    def test_raising_backend_degrades_to_none(self):
        assert cm.capture_cost_analysis(
            _Compiled(raises=NotImplementedError)) is None

    def test_none_and_empty_shapes_degrade_to_none(self):
        assert cm.capture_cost_analysis(_Compiled(None)) is None
        assert cm.capture_cost_analysis(_Compiled([])) is None
        assert cm.capture_cost_analysis(_Compiled(())) is None
        assert cm.capture_cost_analysis(_Compiled({})) is None

    def test_real_compiled_executable(self):
        compiled = jax.jit(lambda a: a @ a).lower(
            jax.ShapeDtypeStruct((32, 32), f32)).compile()
        got = cm.capture_cost_analysis(compiled)
        assert type(got) is dict
        assert got["flops"] > 0 and got["bytes accessed"] > 0

    def test_plain_dict_passes_through(self):
        got = cm.capture_cost_analysis(_Compiled({"bytes accessed": 64.0}))
        assert got == {"bytes accessed": 64.0}

    def test_dict_like_converts(self):
        ca = collections.OrderedDict(flops=2.0)
        assert cm.capture_cost_analysis(_Compiled(ca)) == {"flops": 2.0}

    def test_other_errors_propagate(self):
        with pytest.raises(RuntimeError):
            cm.capture_cost_analysis(_Compiled(raises=RuntimeError))


# --------------------------------------------------------- decision matrix

def _cost(flops, nbytes):
    return cm.ClassCost(flops=flops, bytes_accessed=nbytes)


class TestDecide:
    def setup_method(self):
        self.m = cm.CostModel()   # default thresholds

    def test_unmeasured_falls_back_to_vmap(self):
        d = self.m.decide(cm.UNMEASURED, size=8)
        assert d.batcher == "vmap" and "unmeasured" in d.reason

    def test_below_breakeven_unrolls(self):
        # 8 members x 4 flops = 32 << 256
        d = self.m.decide(_cost(4.0, 16.0), size=8)
        assert d.batcher == "unrolled" and "break-even" in d.reason

    def test_memory_bound_cache_resident_member_maps(self):
        # intensity 0.25, member 256KB <= 512KB, batch 2MB >= 128KB
        d = self.m.decide(_cost(64e3, 256 * 1024), size=8)
        assert d.batcher == "map"

    def test_memory_bound_huge_member_stays_vmap(self):
        # intensity low but member 2MB can never be cache-resident
        d = self.m.decide(_cost(256e3, 2 * 1024 * 1024), size=8)
        assert d.batcher == "vmap" and "too large" in d.reason

    def test_memory_bound_tiny_batch_stays_vmap(self):
        # whole batch (8 x 4KB = 32KB) fits in cache: fused vmap wins
        d = self.m.decide(_cost(1e3, 4 * 1024), size=8)
        assert d.batcher == "vmap" and "cache-resident" in d.reason

    def test_compute_bound_vmaps(self):
        d = self.m.decide(_cost(1e6, 1e4), size=8)   # 100 flops/B
        assert d.batcher == "vmap" and "compute-bound" in d.reason

    def test_describe_carries_the_numbers(self):
        rec = self.m.decide(_cost(64e3, 256 * 1024), size=8).describe()
        assert rec["flops"] == 64e3 and rec["bytes"] == 256 * 1024
        assert rec["intensity"] == pytest.approx(0.2441, abs=1e-3)


class TestProbe:
    def test_real_matmul_measures_positive_cost(self):
        m = cm.CostModel()
        spec = jax.ShapeDtypeStruct((32, 32), f32)
        cost = m.measure(lambda a, b: a @ b, [spec, spec])
        assert cost.source == "measured"
        assert cost.flops and cost.flops > 0
        assert cost.bytes_accessed and cost.bytes_accessed > 0
        assert cost.intensity and cost.intensity > 0

    def test_probe_cached_per_payload_and_signature(self):
        m = cm.CostModel()
        fn = lambda x: x * 2.0                                    # noqa: E731
        spec = jax.ShapeDtypeStruct((8,), f32)
        m.measure(fn, [spec])
        m.measure(fn, [spec])
        assert m.probes == 1
        m.measure(fn, [jax.ShapeDtypeStruct((16,), f32)])
        assert m.probes == 2

    def test_probe_failure_degrades_to_unmeasured(self):
        m = cm.CostModel()

        def boom(x):
            raise ValueError("untraceable")

        cost = m.measure(boom, [jax.ShapeDtypeStruct((4,), f32)])
        assert cost is cm.UNMEASURED
        assert m.probe_failures == 1

    def test_negative_flops_sentinel_normalized_to_unmeasured(self):
        # CPU triangular solve is the real-world producer of XLA's -1
        # "unknown flops" sentinel; the probe must not treat it as "free".
        m = cm.CostModel()
        a = jax.ShapeDtypeStruct((8, 8), f32)
        b = jax.ShapeDtypeStruct((8, 8), f32)

        def trsm(l, x):
            return jax.scipy.linalg.solve_triangular(l, x, lower=True)

        cost = m.measure(trsm, [a, b])
        assert cost.flops is None       # never negative, never a lie
        # whatever bytes say, an unknown-flops payload must not unroll
        assert m.decide(cost, size=8).batcher == "vmap"


# ------------------------------------------------- plan keys + kill switch

class TestPlanKey:
    def test_static_plans_pass_through(self):
        cut = cm.default_model().split_call_bytes
        assert cm.plan_key("vmap") == f"vmap/c{cut}"
        assert cm.plan_key("map") == f"map/c{cut}"

    def test_adaptive_plan_carries_threshold_fingerprint(self):
        key = cm.plan_key("auto")
        assert key == f"auto/{cm.default_model().fingerprint()}"

    def test_kill_switch_collapses_auto_to_vmap(self, monkeypatch):
        monkeypatch.setenv(cm.ADAPTIVE_ENV, "0")
        assert cm.resolve_batcher("auto") == "vmap"
        assert cm.plan_key("auto") == cm.plan_key("vmap")
        monkeypatch.setenv(cm.ADAPTIVE_ENV, "1")
        assert cm.resolve_batcher("auto") == "auto"

    def test_invalid_args_are_loud(self):
        with pytest.raises(ValueError, match="batcher"):
            cm.resolve_batcher("scan")
        with pytest.raises(ValueError, match="adaptive"):
            cm.adaptive_enabled("maybe")


def _grid_tdg(n_tasks=6, dim=16):
    tdg = TDG("cmgrid")

    def body(x):
        return jnp.tanh(x @ x.T) + x

    for t in range(n_tasks):
        tdg.add_task(body, inouts=[f"x{t}"], name=f"t{t}")
    rng = np.random.default_rng(7)
    bufs = {f"x{t}": jnp.asarray(rng.standard_normal((dim, dim)), f32)
            for t in range(n_tasks)}
    return tdg, bufs


class TestInternIsolation:
    def test_each_plan_gets_its_own_entry(self):
        tdg, bufs = _grid_tdg()
        clear_intern_cache()
        outs = {}
        for b in ("vmap", "map", "auto"):
            outs[b] = lower_tdg(tdg, batcher=b)(bufs)
        stats = intern_stats()
        assert stats["misses"] == 3 and stats["entries"] == 3
        # same structure re-lowered under each plan hits its own entry
        for b in ("vmap", "map", "auto"):
            lower_tdg(tdg, batcher=b)
        assert intern_stats()["hits"] == 3
        for b in ("map", "auto"):   # and the plans agree bit-exactly
            for k in outs["vmap"]:
                np.testing.assert_array_equal(np.asarray(outs["vmap"][k]),
                                              np.asarray(outs[b][k]))

    def test_kill_switch_shares_the_static_entry(self, monkeypatch):
        tdg, _ = _grid_tdg()
        clear_intern_cache()
        monkeypatch.setenv(cm.ADAPTIVE_ENV, "0")
        lower_tdg(tdg, batcher="vmap")
        lower_tdg(tdg, batcher="auto")     # resolves to the SAME plan
        stats = intern_stats()
        assert stats["misses"] == 1 and stats["hits"] == 1
        assert stats["entries"] == 1


# ----------------------------------------------- adaptive plan end to end

def _mixed_tdg():
    """One region spanning all three batcher outcomes in a single wave."""
    tdg = TDG("mixed")

    def mm(a, w):
        return a @ w

    def relax(x):
        return 0.25 * (jnp.roll(x, 1, 0) + jnp.roll(x, -1, 0)
                       + jnp.roll(x, 1, 1) + jnp.roll(x, -1, 1))

    def nudge(x):
        return x + 0.5

    for i in range(4):
        tdg.add_task(mm, ins=[f"a{i}", "w"], outs=[f"y{i}"])
        tdg.add_task(relax, ins=[f"h{i}"], outs=[f"g{i}"])
        tdg.add_task(nudge, ins=[f"s{i}"], outs=[f"t{i}"])
    rng = np.random.default_rng(3)
    bufs = {}
    for i in range(4):
        bufs[f"a{i}"] = jnp.asarray(rng.standard_normal((64, 64)), f32)
        # 64x64 stencil: XLA counts ~80 KB per member (cache-resident) and
        # ~320 KB for the class of 4, which is the lax.map band.
        bufs[f"h{i}"] = jnp.asarray(rng.standard_normal((64, 64)), f32)
        bufs[f"s{i}"] = jnp.asarray(rng.standard_normal((2,)), f32)
    bufs["w"] = jnp.asarray(rng.standard_normal((64, 64)), f32)
    return tdg, bufs


class TestAdaptivePlan:
    def test_mixed_region_decisions_and_summary(self):
        tdg, bufs = _mixed_tdg()
        plan = fusion_plan(tdg, buffers=bufs, batcher="auto")
        by_batcher = {d["batcher"]: d for d in plan.summary()["decisions"]}
        assert set(by_batcher) == {"vmap", "map", "unrolled"}
        mm_d = by_batcher["vmap"]
        assert mm_d["flops"] > 0 and mm_d["intensity"] >= cm.DEFAULT_RIDGE
        st_d = by_batcher["map"]
        assert 0 < st_d["intensity"] < cm.DEFAULT_RIDGE
        assert st_d["bytes"] <= cm.DEFAULT_MAP_MEMBER_BYTES
        summary = plan.summary()
        assert summary["batchers"] == {"vmap": 1, "map": 1}
        assert "padded_lanes" in summary and "pad_fraction" in summary

    def test_adaptive_replay_bit_exact_vs_static(self):
        tdg, bufs = _mixed_tdg()
        out_static = ReplayExecutor(tdg, batcher="vmap").run(dict(bufs))
        out_auto = ReplayExecutor(tdg, batcher="auto").run(dict(bufs))
        assert set(out_static) == set(out_auto)
        for k in out_static:
            np.testing.assert_array_equal(np.asarray(out_static[k]),
                                          np.asarray(out_auto[k]))

    def test_executor_plan_key_is_pinned_at_construction(self, monkeypatch):
        tdg, _ = _mixed_tdg()
        ex = ReplayExecutor(tdg, batcher="auto")
        assert ex.plan_key.startswith("auto/")
        monkeypatch.setenv(cm.ADAPTIVE_ENV, "0")
        assert ReplayExecutor(tdg, batcher="auto").plan_key == \
            cm.plan_key("vmap")


# ------------------------------------------------------- bucket boundaries

class TestFitBoundaries:
    def test_exact_fit_on_skewed_modes(self):
        hist = {5: 40, 12: 30, 3: 10, 16: 5}
        bounds = cm.fit_boundaries(hist, max_buckets=8)
        assert bounds == [3, 5, 12, 16]     # zero pad lanes is achievable

    def test_max_included_and_budget_respected(self):
        hist = {3: 1, 5: 1, 7: 1, 9: 1, 11: 1}
        bounds = cm.fit_boundaries(hist, max_buckets=2)
        assert len(bounds) <= 2 and bounds[-1] == 11

    def test_single_bucket_is_the_max(self):
        assert cm.fit_boundaries({4: 10, 7: 1}, max_buckets=1) == [7]

    def test_sub_floor_occupancies_ignored(self):
        assert cm.fit_boundaries({1: 100, 4: 1}, max_buckets=8) == [4]
        assert cm.fit_boundaries({1: 100}, max_buckets=8) == []
        assert cm.fit_boundaries({}, max_buckets=8) == []

    def test_never_beaten_by_pow2(self):
        # the DP is exact: pad under fitted <= pad under pow-2, always
        rng = np.random.default_rng(11)
        for _ in range(10):
            hist = {int(v): int(c) for v, c in zip(
                rng.integers(2, 17, size=5), rng.integers(1, 20, size=5))}

            def bill(bounds):
                total = 0
                for occ, cnt in hist.items():
                    b = next(x for x in sorted(bounds) + [32] if x >= occ)
                    total += cnt * (b - occ)
                return total

            fitted = cm.fit_boundaries(hist, max_buckets=8)
            assert bill(fitted) <= bill(cm.pow2_boundaries(16))


class TestBucketTuner:
    def test_static_tuner_keeps_pow2(self):
        t = cm.BucketTuner(16, adaptive=False, window=4)
        for _ in range(32):
            assert t.observe(5) is False
        assert t.boundaries == cm.pow2_boundaries(16)
        assert t.bucket_for(5) == 8 and t.retunes == 0

    def test_adaptive_tuner_refits_on_window(self):
        t = cm.BucketTuner(16, adaptive=True, window=4)
        changed = [t.observe(5) for _ in range(4)]
        assert changed == [False, False, False, True]
        assert t.boundaries == [5]
        assert t.bucket_for(5) == 5     # pad lanes gone
        assert t.bucket_for(9) == 10    # past the ladder: pow-2 extension
        assert t.retunes == 1 and t.new_buckets_spent == 1

    def test_retrace_budget_freezes_boundaries(self):
        t = cm.BucketTuner(16, adaptive=True, window=4, max_new_buckets=1)
        for _ in range(4):
            t.observe(5)
        assert t.boundaries == [5] and t.new_buckets_spent == 1
        for _ in range(8):              # budget spent: no further retunes
            assert t.observe(3) is False
        assert t.boundaries == [5] and t.retunes == 1

    def test_groups_of_one_never_observed(self):
        t = cm.BucketTuner(16, adaptive=True, window=2)
        assert t.observe(1) is False and t.observations == 0
        assert t.bucket_for(1) == 1

    def test_summary_names_the_numbers(self):
        t = cm.BucketTuner(8, adaptive=True, window=64)
        for _ in range(3):
            t.observe(3)
        s = t.summary()
        assert s["observations"] == 3 and s["histogram"] == {"3": 3}
        assert s["pad_lanes"] == 3      # 3 pads up to pow-2 bucket 4
        assert 0 < s["pad_fraction"] < 1


# ---------------------------------------------------- serving-tier wiring

class TestPoolInvalidate:
    def test_invalidate_counts_and_filters_by_kind(self):
        pool = WarmPool(capacity=8)
        pool.put(("a",), PoolEntry(kind="single", fn=lambda: None))
        pool.put(("b",), PoolEntry(kind="batched", fn=lambda: None))
        pool.put(("c",), PoolEntry(kind="batched", fn=lambda: None))
        n = pool.invalidate(lambda k, e: e.kind == "batched")
        assert n == 2
        stats = pool.stats()
        assert stats["invalidations"] == 2 and stats["entries"] == 1
        assert pool.get(("a",)) is not None


class TestServerAdaptiveBuckets:
    def test_bucket_retune_invalidates_and_stops_padding(self):
        n = 3
        server = RegionServer(max_batch=8, max_wait_ms=500, autostart=False,
                              adaptive=True)
        # Small window so the refit fires within the test instead of at 64.
        server.buckets = cm.BucketTuner(server.max_batch, adaptive=True,
                                        window=3)
        w = jnp.eye(6, dtype=f32)

        def body(x, w):
            return jnp.tanh(x @ w) * 0.5 + x

        def region(i):
            # ONE shared payload across tenants: identical structure is what
            # makes the requests coalesce into occupancy-n batched groups.
            tdg = TDG(f"ab[{i}]")
            for s in range(2):
                tdg.add_task(body, ins=[f"x{s}", "w"], outs=[f"x{s}"])
            return tdg

        tdgs = [region(i) for i in range(n)]
        for i, tdg in enumerate(tdgs):
            server.register_tenant(f"t{i}", tdg)

        def round_(seed):
            rng = np.random.default_rng(seed)
            bufs = [{**{f"x{s}": jnp.asarray(
                rng.standard_normal((6, 6)), f32) for s in range(2)},
                "w": w} for _ in range(n)]
            futs = [server.submit(f"t{i}", b) for i, b in enumerate(bufs)]
            if seed == 0:
                server.start()
            outs = [f.result(120) for f in futs]
            for tdg, b, out in zip(tdgs, bufs, outs):
                want = ReplayExecutor(tdg).run(dict(b))
                for k in want:
                    np.testing.assert_allclose(
                        np.asarray(out[k]), np.asarray(want[k]),
                        rtol=2e-5, atol=2e-5)

        for seed in range(5):
            round_(seed)
        stats = server.stats()
        server.close()
        assert stats["adaptive"] is True
        buckets = stats["buckets"]
        # occupancy-3 groups padded to pow-2 bucket 4 until the window-3
        # refit landed a boundary at 3; after that, zero pad.
        assert buckets["retunes"] >= 1 and 3 in buckets["boundaries"]
        assert buckets["observations"] == 5
        m = stats["metrics"]
        assert m["pad_lanes"] >= 1 and m["bucket_retunes"] >= 1
        assert 0 <= m["pad_fraction"] < 1
        assert stats["pool"]["invalidations"] >= 1

    def test_adaptive_false_pins_pow2(self):
        with RegionServer(adaptive=False, autostart=False) as server:
            assert server.adaptive is False
            assert server.buckets.adaptive is False
            assert server.buckets.boundaries == cm.pow2_boundaries(
                server.max_batch)
