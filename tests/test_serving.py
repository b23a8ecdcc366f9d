"""RegionServer: parity, coalescing, isolation, warm-pool, concurrency.

The serving tier must never trade correctness for batching: every test
checks outputs against the plain ``ReplayExecutor`` ground truth, and the
structural-sharing tests assert the economics (one executable for N
structurally identical tenants) that make multi-tenant replay serving
worthwhile in the first place.
"""
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (TDG, ReplayExecutor, clear_intern_cache,
                        executable_serialization_available, intern_stats,
                        warmup_and_save)
from repro.core.serialize import TaskFnRegistry
from repro.serving import (QueueFull, RateLimited, RegionServer, SmoothWRR,
                           TokenBucket, WarmPool, tier_weight, validate_trace)
from repro.serving import rpc

REG = TaskFnRegistry()


@REG.register()
def _srv_body(x, w):
    return jnp.tanh(x @ w) * 0.5 + x


def _other_body(x, w):
    return x @ w + 1.0


def _region(i, body=_srv_body, waves=2, width=2):
    tdg = TDG(f"srv[{i}]")
    for wv in range(waves):
        for s in range(width):
            tdg.add_task(body, ins=[f"x{s}", "w"], outs=[f"x{s}"],
                         name=f"t{wv}.{s}")
    return tdg


def _bufs(seed, dim=6, width=2, shared_w=None):
    rng = np.random.default_rng(seed)
    b = {f"x{s}": jnp.asarray(rng.standard_normal((dim, dim)), jnp.float32)
         for s in range(width)}
    b["w"] = (shared_w if shared_w is not None
              else jnp.asarray(rng.standard_normal((dim, dim)), jnp.float32))
    return b


def _check(out, tdg, bufs):
    want = ReplayExecutor(tdg).run(dict(bufs))
    assert set(out) == set(want)
    for k in want:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(want[k]),
                                   rtol=2e-5, atol=2e-5)


class TestParity:
    def test_single_tenant_single_request(self):
        tdg = _region(0)
        bufs = _bufs(0)
        with RegionServer(max_batch=1) as server:
            server.register_tenant("a", tdg)
            out = server.serve("a", bufs)
        _check(out, tdg, bufs)

    def test_sequential_requests_reuse_executable(self):
        tdg = _region(0)
        with RegionServer(max_batch=1) as server:
            server.register_tenant("a", tdg)
            b1, b2 = _bufs(1), _bufs(2)
            o1, o2 = server.serve("a", b1), server.serve("a", b2)
        _check(o1, tdg, b1)
        _check(o2, tdg, b2)

    def test_missing_input_slot_rejected_at_submit(self):
        with RegionServer() as server:
            server.register_tenant("a", _region(0))
            bad = _bufs(0)
            del bad["w"]
            with pytest.raises(KeyError, match="missing"):
                server.submit("a", bad)

    def test_unknown_tenant(self):
        with RegionServer() as server:
            with pytest.raises(KeyError, match="unknown tenant"):
                server.serve("ghost", {})


class TestCoalescing:
    def test_identical_structure_batches_and_matches_replay(self):
        n = 4
        w = jnp.asarray(np.random.default_rng(9).standard_normal((6, 6)),
                        jnp.float32)
        server = RegionServer(max_batch=n, max_wait_ms=500, autostart=False)
        tenants = []
        for i in range(n):
            tdg = _region(i)
            server.register_tenant(f"t{i}", tdg)
            tenants.append((tdg, _bufs(10 + i, shared_w=w)))
        futs = [server.submit(f"t{i}", b) for i, (_, b) in enumerate(tenants)]
        server.start()          # deterministic: all n queued before dispatch
        outs = [f.result(120) for f in futs]
        server.close()
        for (tdg, b), out in zip(tenants, outs):
            _check(out, tdg, b)
        m = server.metrics.snapshot()
        assert m["batches"] == 1
        assert m["batch_occupancy_max"] == n
        assert m["coalesced_requests"] == n

    def test_structural_sharing_serial_path(self):
        # N structurally identical tenants, batching off: tenant 2..N must
        # be served from tenant 1's interned executable (>= N-1 hits).
        clear_intern_cache()
        n = 4
        base = intern_stats()
        server = RegionServer(max_batch=1, autostart=True)
        tenants = []
        for i in range(n):
            tdg = _region(i)
            server.register_tenant(f"t{i}", tdg)
            tenants.append((tdg, _bufs(20 + i)))
        for i, (tdg, b) in enumerate(tenants):
            _check(server.serve(f"t{i}", b), tdg, b)
        server.close()
        stats = intern_stats()
        assert stats["hits"] - base["hits"] >= n - 1
        assert stats["misses"] - base["misses"] == 1

    def test_batched_entry_shared_across_batches(self):
        n = 2
        server = RegionServer(max_batch=n, max_wait_ms=500, autostart=False)
        for i in range(n):
            server.register_tenant(f"t{i}", _region(i))
        w = jnp.eye(6, dtype=jnp.float32)
        for round_ in range(3):
            futs = [server.submit(f"t{i}", _bufs(30 + i, shared_w=w))
                    for i in range(n)]
            if round_ == 0:
                server.start()
            for f in futs:
                f.result(120)
        server.close()
        pool = server.pool.stats()
        assert pool["misses"] == 1          # one batched executable built
        assert pool["hits"] >= 2            # ... reused by later batches
        assert server.metrics.snapshot()["batches"] == 3

    def test_compile_batched_is_the_served_program(self):
        # What compile_batched hands back is the pooled entry a coalesced
        # step then runs: 3 members in the 4-lane bucket, w broadcast.
        n = 3
        w = jnp.eye(6, dtype=jnp.float32)
        server = RegionServer(max_batch=4, max_wait_ms=500, autostart=False)
        for i in range(n):
            server.register_tenant(f"t{i}", _region(i))
        compiled = server.compile_batched(
            "t0", [_bufs(60 + i, shared_w=w) for i in range(n)])
        assert "f32[4,6,6]" in compiled.as_text()
        futs = [server.submit(f"t{i}", _bufs(70 + i, shared_w=w))
                for i in range(n)]
        server.start()
        for f in futs:
            f.result(120)
        server.close()
        pool = server.pool.stats()
        assert (pool["misses"], pool["hits"]) == (1, 1)

    def test_shared_buffer_broadcast_not_stacked(self):
        # All members pass the SAME w object: results must still be exact
        # per-tenant (their private x slots differ).
        n = 3
        w = jnp.asarray(np.random.default_rng(1).standard_normal((6, 6)),
                        jnp.float32)
        server = RegionServer(max_batch=n, max_wait_ms=500, autostart=False)
        tenants = []
        for i in range(n):
            tdg = _region(i)
            server.register_tenant(f"t{i}", tdg)
            tenants.append((tdg, _bufs(40 + i, shared_w=w)))
        futs = [server.submit(f"t{i}", b) for i, (_, b) in enumerate(tenants)]
        server.start()
        outs = [f.result(120) for f in futs]
        server.close()
        for (tdg, b), out in zip(tenants, outs):
            _check(out, tdg, b)

    def test_fully_shared_buffers_one_evaluation(self):
        # Every slot is the same object across members: served by one
        # single-request replay, identical outputs for all.
        n = 3
        shared = _bufs(50)
        server = RegionServer(max_batch=n, max_wait_ms=500, autostart=False)
        tenants = [server.register_tenant(f"t{i}", _region(i))
                   for i in range(n)]
        futs = [server.submit(f"t{i}", shared) for i in range(n)]
        server.start()
        outs = [f.result(120) for f in futs]
        server.close()
        for t, out in zip(tenants, outs):
            _check(out, t.tdg, shared)


class TestIsolation:
    def test_different_payloads_never_coalesce(self):
        server = RegionServer(max_batch=4, max_wait_ms=100, autostart=False)
        t_a = _region("a")
        t_b = _region("b", body=_other_body)
        server.register_tenant("a", t_a)
        server.register_tenant("b", t_b)
        ba, bb = _bufs(60), _bufs(61)
        fa, fb = server.submit("a", ba), server.submit("b", bb)
        server.start()
        oa, ob = fa.result(120), fb.result(120)
        server.close()
        _check(oa, t_a, ba)
        _check(ob, t_b, bb)
        assert server.metrics.snapshot()["batch_occupancy_max"] <= 1

    def test_different_kernel_modes_never_coalesce(self):
        server = RegionServer(max_batch=4, max_wait_ms=100, autostart=False)
        t_a, t_b = _region("a"), _region("b")
        server.register_tenant("a", t_a, kernel_mode="ref")
        server.register_tenant("b", t_b, kernel_mode="interpret")
        assert server.tenant("a").kernel_mode == "ref"
        assert server.tenant("b").kernel_mode == "interpret"
        ba, bb = _bufs(62), _bufs(63)
        fa, fb = server.submit("a", ba), server.submit("b", bb)
        server.start()
        oa, ob = fa.result(120), fb.result(120)
        server.close()
        _check(oa, t_a, ba)
        _check(ob, t_b, bb)
        assert server.metrics.snapshot()["batch_occupancy_max"] <= 1

    def test_different_shapes_never_coalesce(self):
        server = RegionServer(max_batch=4, max_wait_ms=100, autostart=False)
        t_a, t_b = _region("a"), _region("b")
        server.register_tenant("a", t_a)
        server.register_tenant("b", t_b)
        ba, bb = _bufs(64, dim=6), _bufs(65, dim=8)
        fa, fb = server.submit("a", ba), server.submit("b", bb)
        server.start()
        oa, ob = fa.result(120), fb.result(120)
        server.close()
        _check(oa, t_a, ba)
        _check(ob, t_b, bb)
        assert server.metrics.snapshot()["batch_occupancy_max"] <= 1


class TestFallbackAndErrors:
    def test_batched_failure_falls_back_to_serial(self, monkeypatch):
        n = 3
        server = RegionServer(max_batch=n, max_wait_ms=500, autostart=False)
        tenants = []
        for i in range(n):
            tdg = _region(i)
            server.register_tenant(f"t{i}", tdg)
            tenants.append((tdg, _bufs(70 + i)))
        monkeypatch.setattr(
            server, "_build_batched",
            lambda tenant: (_ for _ in ()).throw(RuntimeError("no vmap rule")))
        futs = [server.submit(f"t{i}", b) for i, (_, b) in enumerate(tenants)]
        server.start()
        outs = [f.result(120) for f in futs]
        server.close()
        for (tdg, b), out in zip(tenants, outs):
            _check(out, tdg, b)
        m = server.metrics.snapshot()
        assert m["batch_fallbacks"] == 1
        assert m["completed"] == n

    def test_fallback_failure_isolated_per_request(self, monkeypatch):
        # Regression: when a coalesced batch falls back to serial replay
        # and ONE member fails, its siblings must still get their results
        # — not the failing member's exception.
        t0, t1 = _region(0), _region(1)
        server = RegionServer(max_batch=2, max_wait_ms=500, autostart=False)
        server.register_tenant("ok", t0)
        server.register_tenant("doomed", t1)
        monkeypatch.setattr(
            server, "_build_batched",
            lambda tenant: (_ for _ in ()).throw(RuntimeError("no vmap")))
        real_single = server._run_single

        def poisoned_single(req):
            if req.tenant.name == "doomed":
                raise ValueError("poison")
            return real_single(req)

        monkeypatch.setattr(server, "_run_single", poisoned_single)
        good = _bufs(75)
        f_ok = server.submit("ok", good)
        f_bad = server.submit("doomed", _bufs(76, shared_w=good["w"]))
        server.start()
        _check(f_ok.result(120), t0, good)
        with pytest.raises(ValueError, match="poison"):
            f_bad.result(120)
        server.close()
        m = server.metrics.snapshot()
        assert m["batch_fallbacks"] == 1
        assert m["completed"] == 1 and m["failed"] == 1

    def test_payload_error_propagates_to_future(self):
        def bad(x, w):
            raise ValueError("broken payload")

        tdg = TDG("bad")
        tdg.add_task(bad, ins=["x0", "w"], outs=["x0"])
        with RegionServer(max_batch=1) as server:
            server.register_tenant("a", tdg)
            fut = server.submit("a", _bufs(80, width=1))
            with pytest.raises(ValueError, match="broken payload"):
                fut.result(120)
        m = server.metrics.snapshot()
        assert m["failed"] == 1 and m["completed"] == 0

    def test_fallback_groups_not_counted_as_coalesced(self, monkeypatch):
        n = 3
        server = RegionServer(max_batch=n, max_wait_ms=500, autostart=False)
        for i in range(n):
            server.register_tenant(f"t{i}", _region(i))
        monkeypatch.setattr(
            server, "_build_batched",
            lambda tenant: (_ for _ in ()).throw(RuntimeError("no vmap")))
        w = jnp.eye(6, dtype=jnp.float32)
        futs = [server.submit(f"t{i}", _bufs(77 + i, shared_w=w))
                for i in range(n)]
        server.start()
        for f in futs:
            f.result(120)
        server.close()
        m = server.metrics.snapshot()
        assert m["batch_fallbacks"] == 1
        assert m["batch_occupancy_max"] == n      # admission group size...
        assert m["coalesced_requests"] == 0       # ...but nothing was fused

    def test_close_before_start_drains_queued_requests(self):
        # Regression: close() on a never-started server must not abandon
        # queued futures.
        server = RegionServer(max_batch=2, max_wait_ms=50, autostart=False)
        server.register_tenant("a", _region(0))
        bufs = _bufs(78)
        futs = [server.submit("a", bufs) for _ in range(3)]
        server.close()                             # never start()ed
        for f in futs:
            assert f.done()
            _check(f.result(0), server.tenant("a").tdg, bufs)

    def test_submit_after_close_rejected(self):
        server = RegionServer()
        server.register_tenant("a", _region(0))
        server.close()
        with pytest.raises(RuntimeError, match="closed"):
            server.submit("a", _bufs(0))

    def test_close_drains_pending(self):
        server = RegionServer(max_batch=2, max_wait_ms=50, autostart=False)
        server.register_tenant("a", _region(0))
        bufs = _bufs(81)
        futs = [server.submit("a", bufs) for _ in range(4)]
        server.start()
        server.close()                      # must drain, not drop
        for f in futs:
            assert f.done()
            _check(f.result(0), server.tenant("a").tdg, bufs)

    def test_duplicate_tenant_rejected(self):
        with RegionServer() as server:
            server.register_tenant("a", _region(0))
            with pytest.raises(ValueError, match="already registered"):
                server.register_tenant("a", _region(1))

    def test_tdg_xor_warm_path_required(self):
        with RegionServer() as server:
            with pytest.raises(ValueError, match="exactly one"):
                server.register_tenant("a")
            with pytest.raises(ValueError, match="exactly one"):
                server.register_tenant("a", _region(0), warm_path="x.json")


class TestWarmPoolAndAot:
    def test_warm_pool_lru_eviction(self):
        pool = WarmPool(capacity=2)
        from repro.serving import PoolEntry
        pool.put(("k1",), PoolEntry("single", lambda: 1))
        pool.put(("k2",), PoolEntry("single", lambda: 2))
        assert pool.get(("k1",)) is not None      # refresh k1
        pool.put(("k3",), PoolEntry("single", lambda: 3))
        assert pool.get(("k2",)) is None          # evicted (LRU)
        assert pool.get(("k3",)) is not None
        s = pool.stats()
        assert s["evictions"] == 1 and s["entries"] == 2

    def test_server_warmup_installs_aot(self):
        tdg = _region(0)
        bufs = _bufs(90)
        with RegionServer(max_batch=1) as server:
            server.register_tenant("a", tdg)
            info = server.warmup("a", bufs)
            assert info["trace_seconds"] > 0
            out = server.serve("a", bufs)
            _check(out, tdg, bufs)
            assert server.metrics.snapshot()["aot_served"] == 1

    def test_warmup_wrong_shapes_falls_back(self):
        tdg = _region(0)
        with RegionServer(max_batch=1) as server:
            server.register_tenant("a", tdg)
            server.warmup("a", _bufs(91, dim=6))
            other = _bufs(92, dim=8)          # different shapes: no AOT
            _check(server.serve("a", other), tdg, other)
            assert server.metrics.snapshot()["aot_served"] == 0

    @pytest.mark.skipif(not executable_serialization_available(),
                        reason="jax build lacks serialize_executable")
    def test_cold_tenant_hydrates_from_sidecar(self, tmp_path):
        tdg = _region(0)
        bufs = _bufs(93)
        path = tmp_path / "tenant.tdg.json"
        warmup_and_save(tdg, bufs, path, REG)
        with RegionServer(max_batch=1) as server:
            tenant = server.register_tenant("cold", warm_path=str(path),
                                            fn_registry=REG)
            assert tenant.aot_key is not None
            out = server.serve("cold", bufs)
            _check(out, tdg, bufs)
            m = server.metrics.snapshot()
            assert m["aot_served"] == 1
            assert server.pool.stats()["hydrations"] == 1

    def test_cold_tenant_missing_sidecar_falls_back(self, tmp_path):
        from repro.core import save_tdg
        tdg = _region(0)
        bufs = _bufs(94)
        path = tmp_path / "plain.tdg.json"
        save_tdg(tdg, path, REG)              # graph only, no .aot sidecar
        with RegionServer(max_batch=1) as server:
            tenant = server.register_tenant("cold", warm_path=str(path),
                                            fn_registry=REG)
            assert tenant.aot_key is None     # nothing hydrated
            out = server.serve("cold", bufs)  # interned lazy path
            _check(out, tdg, bufs)
            assert server.metrics.snapshot()["aot_served"] == 0


class TestMetrics:
    def test_percentile_nearest_rank(self):
        from repro.serving import percentile
        vals = [float(i) for i in range(1, 11)]      # 1..10
        assert percentile(vals, 50) == 5.0           # ceil(0.5*10)=5th value
        assert percentile(vals, 99) == 10.0
        assert percentile(vals, 0) == 1.0
        assert percentile(vals, 100) == 10.0
        assert percentile([], 50) == 0.0
        cent = [float(i) for i in range(1, 101)]
        assert percentile(cent, 50) == 50.0
        assert percentile(cent, 99) == 99.0

    def test_latency_reservoir_bounded(self):
        from repro.serving import LatencyReservoir
        r = LatencyReservoir(capacity=8)
        for i in range(100):
            r.record(float(i))
        s = r.summary()
        assert s["count"] == 100
        assert s["max_s"] == 99.0                    # recent window survives


class TestConcurrency:
    def test_many_tenants_many_rounds_threaded(self):
        n, rounds = 4, 3
        w = jnp.asarray(np.random.default_rng(5).standard_normal((6, 6)),
                        jnp.float32)
        server = RegionServer(max_batch=n, max_wait_ms=20)
        tenants = []
        for i in range(n):
            tdg = _region(i)
            server.register_tenant(f"t{i}", tdg)
            tenants.append((tdg, _bufs(100 + i, shared_w=w)))
        finals = [None] * n
        errors = []

        def loop(i):
            try:
                tdg, start = tenants[i]
                bufs = dict(start)
                for _ in range(rounds):
                    out = server.serve(f"t{i}", bufs, timeout=300)
                    bufs.update(out)
                    bufs["w"] = w
                finals[i] = bufs
            except BaseException as e:
                errors.append(e)

        threads = [threading.Thread(target=loop, args=(i,)) for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        server.close()
        assert not errors
        # ground truth: replay each tenant's chain serially
        for i, (tdg, start) in enumerate(tenants):
            ex = ReplayExecutor(tdg)
            bufs = dict(start)
            for _ in range(rounds):
                out = ex.run(dict(bufs))
                bufs.update(out)
                bufs["w"] = w
            for k in ("x0", "x1"):
                np.testing.assert_allclose(
                    np.asarray(finals[i][k]), np.asarray(bufs[k]),
                    rtol=2e-4, atol=2e-4)
        m = server.metrics.snapshot()
        assert m["completed"] == n * rounds
        assert m["failed"] == 0


def _chain_oracle(tdg, start, steps, rtol=2e-4):
    """Serial ground truth for a stream: replay ``steps`` times, carrying
    outputs into the same-named input slots between iterations."""
    ex = ReplayExecutor(tdg)
    bufs = dict(start)
    out = {}
    for _ in range(steps):
        out = ex.run(dict(bufs))
        bufs.update({k: v for k, v in out.items() if k in bufs})
    return out


def _assert_stream(out, tdg, start, steps):
    want = _chain_oracle(tdg, start, steps)
    assert set(out) == set(want)
    for k in want:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(want[k]),
                                   rtol=2e-4, atol=2e-4)


class TestContinuous:
    """Iteration-level batching: resident per-class batches with tenants
    joining/leaving between fused steps (the tentpole of the serving tier's
    continuous mode)."""

    def test_stream_parity_vs_replay_chain(self):
        w = jnp.asarray(np.random.default_rng(7).standard_normal((6, 6)),
                        jnp.float32)
        server = RegionServer(max_batch=4, continuous=True, autostart=False)
        tenants = []
        for i in range(3):
            tdg = _region(i)
            server.register_tenant(f"t{i}", tdg)
            tenants.append((tdg, _bufs(200 + i, shared_w=w)))
        futs = [server.submit_stream(f"t{i}", b, steps=5)
                for i, (_, b) in enumerate(tenants)]
        server.start()
        outs = [f.result(120) for f in futs]
        server.close()
        for (tdg, b), out in zip(tenants, outs):
            _assert_stream(out, tdg, b, steps=5)

    def test_join_leave_mid_stream_no_retrace(self):
        # Two long streams and two short ones share one resident batch; the
        # short pair retires after step 2 WITHOUT draining the batch, and
        # the shrink must re-slice pooled executables, never retrace.
        w = jnp.asarray(np.random.default_rng(8).standard_normal((6, 6)),
                        jnp.float32)
        server = RegionServer(max_batch=4, continuous=True, autostart=False)
        plans = [4, 4, 2, 2]          # steps per tenant
        tenants = []
        for i, steps in enumerate(plans):
            tdg = _region(i)
            server.register_tenant(f"t{i}", tdg)
            tenants.append((tdg, _bufs(210 + i, shared_w=w), steps))
        futs = [server.submit_stream(f"t{i}", b, steps=s)
                for i, (_, b, s) in enumerate(tenants)]
        server.start()
        outs = [f.result(120) for f in futs]
        server.close()
        for (tdg, b, s), out in zip(tenants, outs):
            _assert_stream(out, tdg, b, steps=s)
        # Execution pattern: 2 full steps at occupancy 4, then 2 at 2.
        trace = server.metrics.trace.snapshot()
        assert [r["occupancy"] for r in trace] == [4, 4, 2, 2]
        assert trace[1]["leaves"] == 2      # short pair retires in place
        assert trace[3]["leaves"] == 2
        m = server.metrics.snapshot()
        assert m["joins"] == 4 and m["leaves"] == 4
        assert m["batches"] == 4
        # ONE batched executable serves every step — churn re-sliced it
        # (misses stay at 1, every later step is a pool hit on the same
        # entry), it did not rebuild.
        pool = server.pool.stats()
        assert pool["misses"] == 1
        assert pool["hits"] == 3
        assert pool["hot"] == [{"kind": "batched", "hits": 3}]

    def test_mid_stream_join_and_early_leave_parity(self):
        # A 3-step stream and a 1-step request admitted at the same
        # boundary: the single rides step 1 of the resident batch and
        # leaves; the stream continues alone. Both match serial oracles.
        w = jnp.eye(6, dtype=jnp.float32)
        server = RegionServer(max_batch=2, continuous=True, autostart=False)
        tdg_a, tdg_b = _region("a"), _region("b")
        server.register_tenant("a", tdg_a)
        server.register_tenant("b", tdg_b)
        ba, bb = _bufs(220, shared_w=w), _bufs(221, shared_w=w)
        fa = server.submit_stream("a", ba, steps=3)
        fb = server.submit("b", bb)
        server.start()
        out_a, out_b = fa.result(120), fb.result(120)
        server.close()
        _assert_stream(out_a, tdg_a, ba, steps=3)
        _check(out_b, tdg_b, bb)
        trace = server.metrics.trace.snapshot()
        assert [r["occupancy"] for r in trace] == [2, 1, 1]
        assert trace[0]["joins"] == 2 and trace[0]["leaves"] == 1

    def test_deterministic_step_boundary_admission(self):
        # All requests queued before start: admission order is a pure
        # function of (FIFO within tier) x (smooth weighted round-robin
        # across tiers), so the trace tier tallies are reproducible.
        w = jnp.eye(6, dtype=jnp.float32)
        server = RegionServer(max_batch=2, continuous=True, autostart=False)
        for i in range(8):
            server.register_tenant(f"t{i}", _region(i), tier=i % 2)
        futs = [server.submit(f"t{i % 8}", _bufs(230 + i, shared_w=w))
                for i in range(24)]
        server.start()
        for f in futs:
            f.result(120)
        server.close()
        trace = server.metrics.trace.snapshot()
        assert len(trace) == 12
        assert all(r["occupancy"] == 2 for r in trace)
        tiers = [r["tiers"] for r in trace]
        # tier-1 holds a 2x admission weight: it is never behind tier-0
        # cumulatively, and drains first, leaving an all-tier-0 tail.
        cum = {"0": 0, "1": 0}
        for t in tiers:
            for k, n in t.items():
                cum[k] += n
            assert cum["1"] >= cum["0"] or cum["1"] == 12
        assert cum == {"0": 12, "1": 12}
        assert tiers[0] == {"0": 1, "1": 1}
        assert tiers[-3:] == [{"0": 2}] * 3     # tier-1 exhausted first

    def test_submit_stream_requires_continuous(self):
        with RegionServer(continuous=False) as server:
            server.register_tenant("a", _region(0))
            with pytest.raises(RuntimeError, match="continuous"):
                server.submit_stream("a", _bufs(0), steps=2)
        with RegionServer(continuous=True) as server:
            server.register_tenant("a", _region(0))
            with pytest.raises(ValueError, match="steps"):
                server.submit_stream("a", _bufs(0), steps=0)

    def test_continuous_stats_flag_and_trace_dump(self, tmp_path):
        with RegionServer(continuous=True) as server:
            server.register_tenant("a", _region(0))
            _check(server.serve("a", _bufs(240)), _region(0), _bufs(240))
            assert server.stats()["continuous"] is True
            path = tmp_path / "trace.json"
            dumped = server.dump_trace(str(path))
        assert path.exists()
        assert dumped["summary"]["steps"] >= 1


class TestQoS:
    """Per-tenant admission shaping: token buckets, priority tiers, and
    tier-aware shedding (compose with the queue bound + deadlines)."""

    def test_token_bucket_accounting_under_burst(self):
        b = TokenBucket(rate=2.0, burst=2.0, now=0.0)
        assert b.take(now=0.0) and b.take(now=0.0)      # burst drains
        assert not b.take(now=0.0)                      # empty
        assert not b.take(now=0.4)                      # 0.8 tokens: < 1
        assert b.take(now=0.5)                          # refilled exactly 1
        assert not b.take(now=0.5)
        assert b.available(now=100.0) == pytest.approx(2.0)   # capped
        assert b.take(n=2, now=100.0)
        with pytest.raises(ValueError):
            TokenBucket(rate=0.0)

    def test_smooth_wrr_is_proportional_and_interleaved(self):
        wrr = SmoothWRR()
        weights = {1: 2, 0: 1}
        picks = [wrr.pick(weights) for _ in range(6)]
        assert picks == [1, 0, 1, 1, 0, 1]
        assert tier_weight(1) == 2 * tier_weight(0)

    def test_rate_limited_is_typed_and_counted(self):
        server = RegionServer(continuous=True, autostart=False)
        server.register_tenant("a", _region(0), rate=1.0)   # burst of 1
        fut = server.submit("a", _bufs(0))
        with pytest.raises(RateLimited, match="rate limit"):
            server.submit("a", _bufs(1))
        server.start()
        fut.result(120)
        server.close()
        m = server.metrics.snapshot()
        assert m["rate_limited"] == 1
        assert m["completed"] == 1

    def test_low_tier_shed_first_at_queue_bound(self):
        # Queue at its bound, all waiters tier-0: a tier-1 arrival evicts
        # the NEWEST low-tier waiter instead of being refused; a further
        # tier-0 arrival (nothing lower to evict) is refused outright.
        w = jnp.eye(6, dtype=jnp.float32)
        server = RegionServer(max_batch=8, continuous=True, autostart=False,
                              queue_bound=4)
        server.register_tenant("low", _region("lo"), tier=0)
        server.register_tenant("high", _region("hi"), tier=1)
        low_futs = [server.submit("low", _bufs(300 + i, shared_w=w))
                    for i in range(4)]
        high_fut = server.submit("high", _bufs(310, shared_w=w))
        with pytest.raises(QueueFull, match="tier-1"):
            low_futs[-1].result(1)          # newest low waiter was shed
        with pytest.raises(QueueFull):
            server.submit("low", _bufs(311, shared_w=w))
        server.start()
        for f in low_futs[:-1] + [high_fut]:
            f.result(120)
        server.close()
        m = server.metrics.snapshot()
        assert m["shed"] == 2               # the victim + the refusal
        assert m["completed"] == 4

    def test_qos_env_defaults(self, monkeypatch):
        monkeypatch.setenv("REPRO_TENANT_TIER", "a=2,*=0")
        monkeypatch.setenv("REPRO_TENANT_RATE", "a=5,*=0")
        with RegionServer(autostart=False) as server:
            ta = server.register_tenant("a", _region(0))
            tb = server.register_tenant("b", _region(1))
        assert ta.tier == 2 and ta.rate == 5.0 and ta.bucket is not None
        assert tb.tier == 0 and tb.rate == 0.0 and tb.bucket is None

    def test_typed_errors_cross_the_wire_by_name(self):
        from repro.serving.server import DeadlineExceeded
        assert rpc.wire_error_class("RateLimited: tenant 'a' ...") \
            is RateLimited
        assert rpc.wire_error_class("QueueFull: bound") is QueueFull
        assert rpc.wire_error_class("DeadlineExceeded: late") \
            is DeadlineExceeded
        assert rpc.wire_error_class("ValueError: nope") is None
        assert rpc.wire_error_class("no colon here") is None
