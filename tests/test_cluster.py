"""Cluster tier: RPC codec, sticky routing, artifact shipping, failover.

Process-spawning tests share module-scoped frontends (spawning a jax worker
costs seconds; the suites amortize it) and check every distributed answer
against the in-process ``ReplayExecutor``/``RegionServer`` ground truth —
the RPC front must never change WHAT is computed, only WHERE. The remote
bootstrap suite drives *subprocess* workers (``python -m
repro.serving.worker`` over localhost TCP — no ``multiprocessing`` handle),
which is exactly the multi-host attach path. Multi-worker soak lives behind
the ``slow`` marker.
"""
import itertools
import json
import os
import pickle
import shutil
import socket
import struct
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ReplayExecutor, TopologyMismatch,
                        executable_from_bytes,
                        executable_serialization_available,
                        topology_fingerprint, warmup_and_save)
from repro.serving import (ClusterError, ClusterFrontend, ClusterRemoteError,
                           RateLimited, RegionServer, ShmRing, StickyRouter,
                           rpc)
from repro.serving.metrics import validate_trace
from repro.serving.cluster import WorkerNode, _WorkerHandle, resolve_registry
from repro.serving.demo import DEMO_REGISTRY, demo_affine, demo_mix, demo_region
from repro.serving.spawner import SpawnedWorker, parse_worker_spec
from repro.serving.worker import spawn_worker_subprocess

REGISTRY_SPEC = "repro.serving.demo:DEMO_REGISTRY"
DIM = 6


def _bufs(seed, width=2, shared_w=None):
    rng = np.random.default_rng(seed)
    b = {f"x{s}": jnp.asarray(rng.standard_normal((DIM, DIM)), jnp.float32)
         for s in range(width)}
    b["w"] = (shared_w if shared_w is not None
              else jnp.asarray(rng.standard_normal((DIM, DIM)), jnp.float32))
    return b


def _check(out, tdg, bufs):
    want = ReplayExecutor(tdg).run(dict(bufs))
    assert set(out) == set(want)
    for k in want:
        np.testing.assert_allclose(np.asarray(out[k]), np.asarray(want[k]),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Wire codec (no processes)
# ---------------------------------------------------------------------------

class TestRpcCodec:
    def _roundtrip(self, obj):
        return rpc.decode(rpc.encode(obj))

    def test_scalars_and_containers(self):
        obj = {"op": "x", "id": 3, "none": None, "flag": True,
               "f": 2.5, "s": "text", "tup": (1, 2), "lst": [1, [2, 3]],
               ("k", 1): "tuple-key"}
        back = self._roundtrip(obj)
        assert back == obj
        assert isinstance(back["tup"], tuple)
        assert isinstance(back["lst"], list)

    def test_array_dtypes_and_zero_d(self):
        arrays = {
            "f32": jnp.asarray(np.arange(6, dtype=np.float32).reshape(2, 3)),
            "bf16": jnp.asarray([[1.5, -2.0]], jnp.bfloat16),
            "i32_0d": jnp.asarray(7, jnp.int32),
            "np_scalar": np.float32(1.25),
            "bool_arr": np.array([True, False]),
        }
        back = self._roundtrip(arrays)
        assert back["f32"].dtype == np.float32
        np.testing.assert_array_equal(back["f32"], np.asarray(arrays["f32"]))
        assert str(back["bf16"].dtype) == "bfloat16"
        np.testing.assert_array_equal(
            back["bf16"].astype(np.float32),
            np.asarray(arrays["bf16"]).astype(np.float32))
        assert back["i32_0d"].shape == () and int(back["i32_0d"]) == 7
        assert back["np_scalar"].dtype == np.float32
        assert float(back["np_scalar"]) == 1.25
        np.testing.assert_array_equal(back["bool_arr"],
                                      np.array([True, False]))

    def test_nested_pytree_and_bytes(self):
        obj = {"caches": [{"k": jnp.ones((2, 2)), "v": (jnp.zeros((1,)),)}],
               "artifact": b"\x00\x01binary\xff"}
        back = self._roundtrip(obj)
        assert back["artifact"] == b"\x00\x01binary\xff"
        np.testing.assert_array_equal(back["caches"][0]["k"], np.ones((2, 2)))
        assert isinstance(back["caches"][0]["v"], tuple)

    def test_decoded_arrays_are_writable(self):
        back = self._roundtrip({"x": np.zeros((2,), np.float32)})
        back["x"][0] = 1.0      # frombuffer views are read-only; copies aren't
        assert back["x"][0] == 1.0

    def test_unencodable_rejected(self):
        with pytest.raises(TypeError, match="cannot encode"):
            rpc.encode({"fn": lambda: None})

    def test_truncated_frame_rejected(self):
        data = rpc.encode({"a": jnp.ones((4,))})
        with pytest.raises(rpc.ProtocolError):
            rpc.decode(data[:8])


def _frame(header_obj, blobs=()):
    """Hand-roll a v2 JSON frame body (adversarial tests build invalid ones).

    Layout: ``[1B tag 'J'][u32 hlen][header][u32 nblobs]`` then per blob
    ``[1B placement=inline][u64 len][bytes]``.
    """
    header = json.dumps(header_obj).encode("utf-8")
    parts = [b"J", struct.pack(">I", len(header)), header,
             struct.pack(">I", len(blobs))]
    for b in blobs:
        parts.append(b"\x00")
        parts.append(struct.pack(">Q", len(b)))
        parts.append(b)
    return b"".join(parts)


class TestRpcFramingAdversarial:
    """Bytes a peer could actually send must fail as ProtocolError — never
    as a numpy/json traceback from half-parsed attacker-controlled data."""

    def test_truncated_header_length(self):
        with pytest.raises(rpc.ProtocolError, match="missing header"):
            rpc.decode(b"\x00\x01")

    def test_header_overruns_body(self):
        with pytest.raises(rpc.ProtocolError, match="header overruns"):
            rpc.decode(b"J" + struct.pack(">I", 100) + b"{}")

    def test_bad_magic_tag_rejected(self):
        with pytest.raises(rpc.ProtocolError, match="codec tag"):
            rpc.decode(b"\x00" + struct.pack(">I", 2) + b"{}"
                       + struct.pack(">I", 0))

    def test_truncated_blob_length(self):
        good = _frame({"t": "b", "i": 0}, [b"payload"])
        with pytest.raises(rpc.ProtocolError, match="blob length"):
            rpc.decode(good[:-len(b"payload") - 4])   # cut mid length prefix

    def test_blob_overruns_body(self):
        good = _frame({"t": "b", "i": 0}, [b"payload"])
        with pytest.raises(rpc.ProtocolError, match="blob overruns"):
            rpc.decode(good[:-3])

    def test_blob_index_out_of_range(self):
        with pytest.raises(rpc.ProtocolError, match="out of range"):
            rpc.decode(_frame({"t": "b", "i": 7}, [b"x"]))

    def test_array_blob_shape_mismatch(self):
        # 3 bytes of data for a float32[4]: without validation this escapes
        # as a numpy frombuffer/reshape error deep in the codec.
        bad = _frame({"t": "a", "i": 0, "d": "float32", "s": [4]}, [b"abc"])
        with pytest.raises(rpc.ProtocolError, match="disagrees"):
            rpc.decode(bad)

    def test_array_negative_dim(self):
        # float32[-1] with 4 bytes would pass a naive size check (numpy
        # infers -1) and reshape attacker-chosen geometry.
        bad = _frame({"t": "a", "i": 0, "d": "float32", "s": [-1]},
                     [b"\x00" * 4])
        with pytest.raises(rpc.ProtocolError, match="invalid shape"):
            rpc.decode(bad)

    def test_unknown_node_type(self):
        with pytest.raises(rpc.ProtocolError, match="unknown codec node"):
            rpc.decode(_frame({"t": "zz", "v": 1}))

    def test_non_list_shape_rejected(self):
        bad = _frame({"t": "a", "i": 0, "d": "float32", "s": 1},
                     [b"\x00" * 4])
        with pytest.raises(rpc.ProtocolError, match="invalid shape"):
            rpc.decode(bad)

    def test_missing_node_keys_are_protocol_errors(self):
        # A node without "t"/"d"/"i" must not escape as KeyError from deep
        # inside the codec — the reader loops only treat ProtocolError (and
        # socket errors) as fatal-but-handled.
        with pytest.raises(rpc.ProtocolError, match="malformed codec"):
            rpc.decode(_frame({"v": 1}))
        with pytest.raises(rpc.ProtocolError, match="malformed codec"):
            rpc.decode(_frame({"t": "a", "i": 0, "s": [1]}, [b"\x00" * 4]))

    def test_bogus_dtype_is_protocol_error(self):
        bad = _frame({"t": "a", "i": 0, "d": "no-such-dtype", "s": [1]},
                     [b"\x00" * 4])
        with pytest.raises(rpc.ProtocolError, match="malformed codec"):
            rpc.decode(bad)

    def test_non_json_header_is_protocol_error(self):
        body = (b"J" + struct.pack(">I", 4) + b"\xff\xfe{{"
                + struct.pack(">I", 0))
        with pytest.raises(rpc.ProtocolError, match="not valid JSON"):
            rpc.decode(body)

    def test_protocol_error_mid_stream_fails_pending_futures(self):
        # A desynced frame on a live frontend connection must mark the
        # worker dead (failing in-flight futures fast), not kill the
        # reader thread silently with futures hung.
        import itertools

        from repro.serving.cluster import _WorkerHandle
        from repro.serving.spawner import SpawnedWorker

        sa, sb = socket.socketpair()
        handle = _WorkerHandle(
            0, SpawnedWorker(idx=0, kind="remote", address=("x", 1),
                             conn=rpc.RpcConnection(sa)),
            itertools.count(1), lambda idx: None)
        fut = handle.request_async({"op": "stats"})
        rpc.recv_msg(sb)                          # consume the request
        sb.sendall(struct.pack(">Q", rpc.max_frame_bytes() + 1))
        with pytest.raises(Exception, match="died"):
            fut.result(timeout=10)
        assert not handle.alive
        sb.close()
        handle.close()

    def test_oversized_length_prefix_refused(self):
        a, b = socket.socketpair()
        try:
            a.sendall(struct.pack(">Q", rpc.max_frame_bytes() + 1))
            with pytest.raises(rpc.ProtocolError, match="exceeding"):
                rpc.recv_msg(b)
        finally:
            a.close()
            b.close()

    def test_max_frame_env_caps_both_directions(self, monkeypatch):
        monkeypatch.setenv("REPRO_RPC_MAX_FRAME", "64")
        assert rpc.max_frame_bytes() == 64
        a, b = socket.socketpair()
        try:
            with pytest.raises(rpc.ProtocolError, match="exceeds"):
                rpc.send_msg(a, {"x": np.zeros(100, np.float32)})
            a.sendall(struct.pack(">Q", 65))
            with pytest.raises(rpc.ProtocolError, match="exceeding"):
                rpc.recv_msg(b)
        finally:
            a.close()
            b.close()

    def test_max_frame_env_invalid_is_loud(self, monkeypatch):
        # ProtocolError, not bare ValueError: the cap is read on wire
        # paths, and reader loops only treat ProtocolError as a handled
        # fatal error (futures fail fast instead of threads dying silent).
        monkeypatch.setenv("REPRO_RPC_MAX_FRAME", "not-a-number")
        with pytest.raises(rpc.ProtocolError, match="REPRO_RPC_MAX_FRAME"):
            rpc.max_frame_bytes()
        monkeypatch.setenv("REPRO_RPC_MAX_FRAME", "-1")
        with pytest.raises(rpc.ProtocolError, match="positive"):
            rpc.max_frame_bytes()

    def test_hello_frame_capped_preauth(self):
        # An unauthenticated peer's first frame is bounded by
        # HELLO_MAX_BYTES regardless of the (multi-GiB) general cap.
        sa, sb = socket.socketpair()
        a, b = rpc.RpcConnection(sa), rpc.RpcConnection(sb)
        try:
            a.send({"op": "hello", "proto": rpc.PROTOCOL_VERSION,
                    "token": "x" * (rpc.HELLO_MAX_BYTES + 1)})
            with pytest.raises(rpc.ProtocolError, match="exceeding"):
                rpc.server_handshake(b, token="t")
        finally:
            a.close()
            b.close()

    def test_handshake_deadline_is_absolute(self):
        # A trickler that sends nothing must be cut off by the deadline.
        sa, sb = socket.socketpair()
        b = rpc.RpcConnection(sb)
        try:
            t0 = time.monotonic()
            with pytest.raises(rpc.ProtocolError, match="deadline"):
                rpc.server_handshake(b, token="t", timeout=0.3)
            assert time.monotonic() - t0 < 5.0
        finally:
            sa.close()
            b.close()


class TestRpcAccounting:
    """The satellite bugfix: recv() must account real wire bytes, not
    "1 per message", and both directions must be observable."""

    def test_bytes_received_matches_peer_bytes_sent(self):
        sa, sb = socket.socketpair()
        a, b = rpc.RpcConnection(sa), rpc.RpcConnection(sb)
        try:
            payload = {"op": "x", "arr": np.arange(32, dtype=np.float32),
                       "blob": b"\x00" * 100}
            a.send(payload)
            a.send({"op": "tiny"})
            got1, got2 = b.recv(), b.recv()
            assert got1["op"] == "x" and got2["op"] == "tiny"
            assert a.messages_sent == 2
            assert b.messages_received == 2
            # REAL byte symmetry: everything a put on the wire, b counted.
            assert a.bytes_sent == b.bytes_received
            assert b.bytes_received > 128 + 100     # not a message count
            ws = b.wire_stats()
            assert ws["bytes_sent"] == 0
            assert ws["bytes_received"] == b.bytes_received
            assert ws["messages_sent"] == 0
            assert ws["messages_received"] == 2
            assert ws["decode_seconds"] > 0.0
            assert ws["transport"] == "tcp"
            aw = a.wire_stats()
            assert aw["encode_seconds"] > 0.0
            assert aw["shm_bytes_sent"] == 0
        finally:
            a.close()
            b.close()


class TestRegistryResolution:
    def test_instance_passthrough(self):
        assert resolve_registry(DEMO_REGISTRY) is DEMO_REGISTRY

    def test_spec_string(self):
        assert resolve_registry(REGISTRY_SPEC) is DEMO_REGISTRY

    def test_bad_spec(self):
        with pytest.raises(ValueError, match="module:attr"):
            resolve_registry("not-a-spec")


# ---------------------------------------------------------------------------
# Routing (no processes)
# ---------------------------------------------------------------------------

class TestStickyRouter:
    def test_sticky_by_key(self):
        r = StickyRouter(4)
        alive = {0, 1, 2, 3}
        w = r.route("sigA", alive)
        for _ in range(5):
            assert r.route("sigA", alive) == w

    def test_distinct_structures_spread_least_loaded(self):
        r = StickyRouter(2)
        alive = {0, 1}
        workers = {r.route(f"sig{i}", alive) for i in range(2)}
        assert workers == {0, 1}

    def test_reroute_excludes_dead(self):
        r = StickyRouter(3)
        alive = {0, 1, 2}
        w = r.route("sig", alive)
        w2 = r.reroute("sig", alive - {w}, exclude={w})
        assert w2 != w
        assert r.route("sig", alive - {w}) == w2   # sticky on the new home

    def test_no_live_workers(self):
        r = StickyRouter(2)
        with pytest.raises(Exception, match="no live workers"):
            r.route("sig", set())


# ---------------------------------------------------------------------------
# Live cluster (module-scoped 2-worker frontend)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def frontend():
    fe = ClusterFrontend(workers=2, registry=REGISTRY_SPEC, max_wait_ms=5.0,
                         name="test-cluster")
    yield fe
    fe.close()


@pytest.fixture(scope="module")
def shared_w():
    return jnp.asarray(np.random.default_rng(99).standard_normal((DIM, DIM)),
                       jnp.float32)


class TestClusterServing:
    def test_parity_vs_inprocess_ground_truth(self, frontend, shared_w):
        tenants = []
        for i in range(4):
            tdg = demo_region(f"par[{i}]")
            frontend.register_tenant(f"par{i}", tdg, pinned={"w": shared_w})
            tenants.append((tdg, _bufs(20 + i, shared_w=shared_w)))
        futs = [frontend.submit(f"par{i}",
                                {k: v for k, v in b.items() if k != "w"})
                for i, (_, b) in enumerate(tenants)]
        outs = [f.result(120) for f in futs]
        for (tdg, b), out in zip(tenants, outs):
            _check(out, tdg, b)

    def test_sticky_routing_by_structure(self, frontend, shared_w):
        # 3 tenants of one structure + 2 of another: each structure must
        # land whole on exactly one worker (warm state never splits).
        for i in range(3):
            frontend.register_tenant(
                f"stA{i}", demo_region(f"stA[{i}]", waves=3),
                pinned={"w": shared_w})
        for i in range(2):
            frontend.register_tenant(
                f"stB{i}", demo_region(f"stB[{i}]", waves=3,
                                       body=demo_affine),
                pinned={"w": shared_w})
        a_workers = {frontend.tenant(f"stA{i}").worker for i in range(3)}
        b_workers = {frontend.tenant(f"stB{i}").worker for i in range(2)}
        assert len(a_workers) == 1
        assert len(b_workers) == 1
        # different payload symbol => different routing key; least-loaded
        # assignment puts it on the other worker of the pair
        assert a_workers != b_workers

    def test_cross_process_coalescing(self, frontend, shared_w):
        # Same-structure tenants routed to one worker still coalesce there:
        # the fleet's coalesced_requests must rise when we fire concurrently.
        before = frontend.stats()["aggregate"]["coalesced_requests"]
        for i in range(3):
            frontend.register_tenant(
                f"co{i}", demo_region(f"co[{i}]", waves=4),
                pinned={"w": shared_w})
        bufs = [_bufs(40 + i, shared_w=shared_w) for i in range(3)]
        for _ in range(3):      # several rounds: at least one coalesces
            futs = [frontend.submit(
                f"co{i}", {k: v for k, v in bufs[i].items() if k != "w"})
                for i in range(3)]
            [f.result(120) for f in futs]
        after = frontend.stats()["aggregate"]["coalesced_requests"]
        assert after > before

    def test_request_error_is_isolated(self, frontend):
        frontend.register_tenant("err", demo_region("err[0]"))
        with pytest.raises(ClusterRemoteError, match="missing"):
            frontend.serve("err", {"x0": jnp.ones((DIM, DIM))})  # no x1/w
        # the worker survived the bad request
        assert len(frontend._alive()) == 2
        good = _bufs(50)
        out = frontend.serve("err", good)
        _check(out, demo_region("err[0]"), good)

    def test_unknown_tenant(self, frontend):
        with pytest.raises(KeyError, match="unknown tenant"):
            frontend.serve("ghost", {})

    def test_duplicate_tenant_rejected(self, frontend):
        frontend.register_tenant("dup", demo_region("dup[0]"))
        with pytest.raises(ValueError, match="already registered"):
            frontend.register_tenant("dup", demo_region("dup[1]"))

    def test_aggregate_sums_worker_metrics(self, frontend):
        st = frontend.stats()
        live = [s for s in st["workers"].values() if s is not None]
        assert st["aggregate"]["admitted"] == sum(
            s["metrics"]["admitted"] for s in live)
        assert st["frontend"]["alive"] == 2
        assert set(st["aggregate"]) >= {
            "admitted", "completed", "failed", "coalesced_requests",
            "aot_served", "aot_hydrate_failures", "pool", "intern"}

    def test_pinned_group_ships_once_per_worker(self, frontend, shared_w):
        # Every pinned registration in this module passes the SAME shared_w
        # object, so there is exactly one pin group, shipped to at most one
        # worker per distinct placement — never once per tenant.
        st = frontend.stats()
        pinned_workers = {r["worker"] for r in st["tenants"].values()}
        assert 1 <= st["frontend"]["pin_groups_shipped"] <= len(pinned_workers)
        for s in st["workers"].values():
            if s is not None:
                assert s["worker"]["pin_groups"] <= 1

    def test_failed_registration_leaves_no_phantom(self, frontend,
                                                   monkeypatch):
        from repro.core import TDG

        def unregistered_payload(x, w):
            return x + w
        bad = TDG("phantom[0]")
        bad.add_task(unregistered_payload, ins=["x0", "w"], outs=["x0"])
        # frontend-side failure (payload has no symbol in DEMO_REGISTRY):
        # fails before any record exists
        with pytest.raises(ValueError, match="not registered"):
            frontend.register_tenant("phantom", bad)
        # worker-side failure (registration RPC errors after the record is
        # inserted): the record must be rolled back, not left as a phantom
        # that blocks the retry
        def boom(widx, record):
            raise ClusterRemoteError("worker rejected registration")
        monkeypatch.setattr(frontend, "_register_on", boom)
        with pytest.raises(ClusterRemoteError, match="rejected"):
            frontend.register_tenant("phantom", demo_region("phantom[0]"))
        monkeypatch.undo()
        frontend.register_tenant("phantom", demo_region("phantom[1]"))
        good = _bufs(55)
        _check(frontend.serve("phantom", good),
               demo_region("phantom[1]"), good)

    def test_health(self, frontend):
        rows = frontend.health()
        assert len(rows) == 2
        assert all(r["alive"] and r["process_alive"] for r in rows)
        assert all(isinstance(r["pid"], int) for r in rows)


# ---------------------------------------------------------------------------
# Warm-artifact shipping + poisoned artifacts (1-worker frontend)
# ---------------------------------------------------------------------------

@pytest.mark.skipif(not executable_serialization_available(),
                    reason="jax build cannot serialize executables")
class TestArtifactShipping:
    @pytest.fixture(scope="class")
    def cold_frontend(self):
        fe = ClusterFrontend(workers=1, registry=REGISTRY_SPEC,
                             name="test-cold")
        yield fe
        fe.close()

    @pytest.fixture(scope="class")
    def warm_artifact(self, tmp_path_factory):
        tdg = demo_region("warm[0]")
        bufs = _bufs(60)
        path = str(tmp_path_factory.mktemp("warm") / "region.json")
        warmup_and_save(tdg, bufs, path, DEMO_REGISTRY)
        return path, tdg, bufs

    def test_cold_worker_hydrates_without_relowering(self, cold_frontend,
                                                     warm_artifact):
        path, tdg, bufs = warm_artifact
        rec = cold_frontend.register_tenant("warm", warm_path=path)
        assert rec.artifact is not None          # sidecar held for re-shipping
        out = cold_frontend.serve("warm", bufs)
        _check(out, tdg, bufs)
        st = cold_frontend.stats()
        wk = st["workers"][0]
        assert st["aggregate"]["hydrated_inband"] == 1
        assert st["aggregate"]["aot_served"] >= 1
        # THE cold-start claim: the worker served from the shipped binary
        # and never lowered anything itself.
        assert wk["intern"]["misses"] == 0
        assert st["aggregate"]["aot_hydrate_failures"] == 0

    def test_poisoned_artifact_is_loud_but_survivable(self, cold_frontend,
                                                      warm_artifact,
                                                      tmp_path):
        path, tdg, bufs = warm_artifact
        poisoned = str(tmp_path / "poisoned.json")
        with open(path) as f:
            graph = f.read()
        with open(poisoned, "w") as f:
            f.write(graph)
        with open(poisoned + ".aot", "wb") as f:
            f.write(b"not an executable")
        before = cold_frontend.stats()["aggregate"]["aot_hydrate_failures"]
        cold_frontend.register_tenant("poison", warm_path=poisoned)
        out = cold_frontend.serve("poison", bufs)   # lazy fallback still right
        _check(out, tdg, bufs)
        after = cold_frontend.stats()["aggregate"]["aot_hydrate_failures"]
        assert after == before + 1


class TestHydrateFailureMetricInProcess:
    """The satellite bugfix: RegionServer itself must count silent fallbacks."""

    def test_corrupt_sidecar_counts_hydrate_failure(self, tmp_path):
        tdg = demo_region("hf[0]")
        path = str(tmp_path / "hf.json")
        from repro.core.serialize import save_tdg
        save_tdg(tdg, path, DEMO_REGISTRY)
        with open(path + ".aot", "wb") as f:
            f.write(b"garbage bytes")
        with RegionServer(max_batch=1) as server:
            server.register_tenant("hf", warm_path=path,
                                   fn_registry=DEMO_REGISTRY)
            bufs = _bufs(70)
            out = server.serve("hf", bufs)
            _check(out, tdg, bufs)
            assert server.metrics.snapshot()["aot_hydrate_failures"] == 1

    def test_missing_sidecar_is_not_a_failure(self, tmp_path):
        tdg = demo_region("nf[0]")
        path = str(tmp_path / "nf.json")
        from repro.core.serialize import save_tdg
        save_tdg(tdg, path, DEMO_REGISTRY)   # graph only, no .aot at all
        with RegionServer(max_batch=1) as server:
            server.register_tenant("nf", warm_path=path,
                                   fn_registry=DEMO_REGISTRY)
            assert server.metrics.snapshot()["aot_hydrate_failures"] == 0


# ---------------------------------------------------------------------------
# Worker death -> requeue (own 2-worker frontend: it kills one)
# ---------------------------------------------------------------------------

class TestWorkerDeathRequeue:
    def test_kill_requeues_to_sibling_with_parity(self):
        # heartbeat_secs=0 pins the supervisor OFF: this test asserts the
        # bare death->requeue contract (victim stays dead, tenant moves to
        # the sibling for good); self-healing respawn has its own tests.
        with ClusterFrontend(workers=2, registry=REGISTRY_SPEC,
                             heartbeat_secs=0,
                             name="test-kill") as fe:
            shared = jnp.asarray(
                np.random.default_rng(7).standard_normal((DIM, DIM)),
                jnp.float32)
            tdg = demo_region("kill[0]")
            fe.register_tenant("k", tdg, pinned={"w": shared})
            bufs = {f"x{s}": jnp.asarray(
                np.random.default_rng(8 + s).standard_normal((DIM, DIM)),
                jnp.float32) for s in range(2)}
            out_before = fe.serve("k", bufs)
            _check(out_before, tdg, {**bufs, "w": shared})
            victim = fe.tenant("k").worker
            fe._handles[victim].process.terminate()
            fe._handles[victim].process.join(timeout=30)
            deadline = time.monotonic() + 30
            while fe._handles[victim].alive and time.monotonic() < deadline:
                time.sleep(0.05)     # reader notices EOF
            out_after = fe.serve("k", bufs)
            for key in out_before:
                np.testing.assert_allclose(np.asarray(out_after[key]),
                                           np.asarray(out_before[key]),
                                           rtol=2e-5, atol=2e-5)
            st = fe.stats()
            assert fe.tenant("k").worker != victim
            assert st["frontend"]["worker_deaths"] >= 1
            assert st["frontend"]["requeues"] >= 1
            assert st["frontend"]["alive"] == 1

    def test_kill_mid_window_all_futures_resolve(self, monkeypatch):
        # The hard case: the pipeline window holds SEVERAL inflight batch
        # frames (tiny _WIRE_BATCH forces multi-frame windows) on a
        # shm-transport worker when it is SIGKILLed mid-conversation.
        # Every outstanding future must resolve — retried to the sibling
        # with ground-truth parity, zero hangs — and the respawned
        # replacement comes back on TCP, leaving a mixed shm+tcp fleet
        # that still serves both tenants correctly.
        import repro.serving.cluster as cluster_mod
        monkeypatch.setattr(cluster_mod, "_WIRE_BATCH", 2)
        with ClusterFrontend(workers=2, registry=REGISTRY_SPEC,
                             transport="shm", window=4,
                             heartbeat_secs=0.3, lease_misses=3,
                             respawn_max=3, name="test-midwindow") as fe:
            assert all(h.transport == "shm" for h in fe._handles)
            shared = jnp.asarray(
                np.random.default_rng(17).standard_normal((DIM, DIM)),
                jnp.float32)
            tdg_a = demo_region("mwA[0]")
            tdg_b = demo_region("mwB[0]", body=demo_affine)
            fe.register_tenant("mwA", tdg_a, pinned={"w": shared})
            fe.register_tenant("mwB", tdg_b, pinned={"w": shared})
            bufs = {f"x{s}": jnp.asarray(
                np.random.default_rng(18 + s).standard_normal((DIM, DIM)),
                jnp.float32) for s in range(2)}
            send = {k: v for k, v in bufs.items() if k != "w"}
            ground_a = ReplayExecutor(tdg_a).run({**bufs, "w": shared})
            ground_b = ReplayExecutor(tdg_b).run({**bufs, "w": shared})
            # warm both workers so the kill round is pure replay traffic
            fe.serve("mwA", send, timeout=300)
            fe.serve("mwB", send, timeout=300)
            victim = fe.tenant("mwA").worker
            respawns_before = fe.respawns
            futs = [fe.submit("mwA", send) for _ in range(16)]
            fe._handles[victim].process.kill()      # SIGKILL mid-window
            for f in futs:
                out = f.result(timeout=120)          # zero hangs
                for key in ground_a:
                    np.testing.assert_allclose(
                        np.asarray(out[key]), np.asarray(ground_a[key]),
                        rtol=2e-5, atol=2e-5)
            st = fe.stats()["frontend"]
            assert st["worker_deaths"] >= 1
            assert st["requeues"] >= 1
            # the replacement connects TCP-first: genuinely mixed fleet
            deadline = time.monotonic() + 120
            while fe.respawns == respawns_before \
                    and time.monotonic() < deadline:
                time.sleep(0.1)
            assert fe.respawns > respawns_before
            assert {h.transport for h in fe._handles} == {"shm", "tcp"}
            out_a = fe.serve("mwA", send, timeout=120)
            out_b = fe.serve("mwB", send, timeout=120)
            for key in ground_a:
                np.testing.assert_allclose(np.asarray(out_a[key]),
                                           np.asarray(ground_a[key]),
                                           rtol=2e-5, atol=2e-5)
            for key in ground_b:
                np.testing.assert_allclose(np.asarray(out_b[key]),
                                           np.asarray(ground_b[key]),
                                           rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Continuous batching + QoS over the wire (own frontends)
# ---------------------------------------------------------------------------

class TestContinuousCluster:
    def test_kill_mid_resident_batch_supervised_all_resolve(self):
        # Workers run continuous RegionServers (the default): a burst of
        # concurrent submits forms a resident batch on the victim when it
        # is SIGKILLed. With the supervisor ON, every in-flight step must
        # resolve — requeued to the sibling with ground-truth parity or
        # failed with a typed error, zero hangs — and the respawned slot
        # must keep serving. The surviving fleet's execution-pattern trace
        # must be retrievable over the wire and schema-valid.
        with ClusterFrontend(workers=2, registry=REGISTRY_SPEC,
                             heartbeat_secs=0.3, lease_misses=3,
                             respawn_max=3, name="test-contkill") as fe:
            shared = jnp.asarray(
                np.random.default_rng(27).standard_normal((DIM, DIM)),
                jnp.float32)
            tdg = demo_region("ck[0]")
            fe.register_tenant("ck", tdg, pinned={"w": shared}, tier=1)
            bufs = {f"x{s}": jnp.asarray(
                np.random.default_rng(28 + s).standard_normal((DIM, DIM)),
                jnp.float32) for s in range(2)}
            ground = ReplayExecutor(tdg).run({**bufs, "w": shared})
            fe.serve("ck", bufs, timeout=300)       # warm the victim
            victim = fe.tenant("ck").worker
            respawns_before = fe.respawns
            futs = [fe.submit("ck", bufs) for _ in range(12)]
            fe._handles[victim].process.kill()      # SIGKILL mid-batch
            ok, typed = 0, 0
            for f in futs:
                try:
                    out = f.result(timeout=120)      # zero hangs
                except (ClusterError, ClusterRemoteError, RuntimeError):
                    typed += 1
                    continue
                for key in ground:
                    np.testing.assert_allclose(
                        np.asarray(out[key]), np.asarray(ground[key]),
                        rtol=2e-5, atol=2e-5)
                ok += 1
            assert ok + typed == 12 and ok >= 1
            st = fe.stats()["frontend"]
            assert st["worker_deaths"] >= 1
            deadline = time.monotonic() + 120
            while fe.respawns == respawns_before \
                    and time.monotonic() < deadline:
                time.sleep(0.1)
            assert fe.respawns > respawns_before    # supervised comeback
            out_after = fe.serve("ck", bufs, timeout=120)
            for key in ground:
                np.testing.assert_allclose(np.asarray(out_after[key]),
                                           np.asarray(ground[key]),
                                           rtol=2e-5, atol=2e-5)
            traces = [t for t in fe.trace().values() if t is not None]
            assert traces                            # fleet trace reachable
            for t in traces:
                validate_trace(t["records"])
            assert any(t["summary"]["steps"] >= 1 for t in traces)

    def test_rate_limited_crosses_the_wire_typed(self):
        # A tenant registered with rate=0.001 req/s has a one-token burst:
        # the first request spends it, the second must come back as the
        # TYPED RateLimited (matched by name through the rpc error
        # registry), not an opaque ClusterRemoteError — and must NOT be
        # retried onto another worker.
        with ClusterFrontend(workers=1, registry=REGISTRY_SPEC,
                             heartbeat_secs=0,
                             name="test-ratewire") as fe:
            tdg = demo_region("rl[0]")
            fe.register_tenant("rl", tdg, tier=0, rate=0.001)
            bufs = _bufs(31)
            out = fe.serve("rl", bufs, timeout=300)  # spends the only token
            _check(out, tdg, bufs)
            with pytest.raises(RateLimited, match="rate limit"):
                fe.serve("rl", bufs, timeout=120)
            st = fe.stats()
            assert st["aggregate"]["rate_limited"] == 1


# ---------------------------------------------------------------------------
# Multi-worker soak (slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestClusterSoak:
    def test_four_workers_dependent_chains(self):
        with ClusterFrontend(workers=4, registry=REGISTRY_SPEC,
                             max_wait_ms=10.0, name="test-soak") as fe:
            shared = jnp.asarray(
                np.random.default_rng(1).standard_normal((DIM, DIM)),
                jnp.float32)
            tenants = []
            for i in range(8):
                tdg = demo_region(f"soak[{i}]", waves=2 + (i % 4))
                fe.register_tenant(f"s{i}", tdg, pinned={"w": shared})
                tenants.append((tdg, _bufs(100 + i, shared_w=shared)))
            # dependent chains: each round feeds the next
            state = [dict(b, w=shared) for _, b in tenants]
            for _ in range(6):
                futs = [fe.submit(f"s{i}", {k: v for k, v in state[i].items()
                                            if k != "w"})
                        for i in range(8)]
                for i, f in enumerate(futs):
                    state[i].update(f.result(300))
                    state[i]["w"] = shared
            # ground truth: replay the same chain in-process
            for i, (tdg, b) in enumerate(tenants):
                ex = ReplayExecutor(tdg)
                ref = dict(b)
                for _ in range(6):
                    ref.update(ex.run(dict(ref)))
                    ref["w"] = shared
                for k in ("x0", "x1"):
                    np.testing.assert_allclose(
                        np.asarray(state[i][k]), np.asarray(ref[k]),
                        rtol=2e-4, atol=2e-4)
            st = fe.stats()
            used = {r["worker"] for r in st["tenants"].values()}
            assert len(used) == 4          # 4 structures spread over 4 workers
            assert st["aggregate"]["failed"] == 0


# ---------------------------------------------------------------------------
# Handshake + auth (in-process WorkerNode: no subprocess needed)
# ---------------------------------------------------------------------------

class TestHandshakeAndAuth:
    @pytest.fixture()
    def node(self):
        node = WorkerNode(DEMO_REGISTRY, token="sekrit", max_batch=1)
        t = threading.Thread(target=node.serve_forever, daemon=True)
        t.start()
        yield node
        if not node._stop.is_set():
            conn = rpc.connect("127.0.0.1", node.port)
            rpc.client_handshake(conn, token="sekrit")
            conn.request({"op": "shutdown", "id": 0})
            conn.close()
        t.join(timeout=10)

    def test_good_token_handshake_advertises_identity(self, node):
        conn = rpc.connect("127.0.0.1", node.port)
        try:
            ack = rpc.client_handshake(conn, token="sekrit")
            assert ack["proto"] == rpc.PROTOCOL_VERSION
            assert ack["pid"] == os.getpid()       # in-process node
            assert ack["topology"] == topology_fingerprint()
            reply = conn.request({"op": "ping", "id": 1})
            assert reply["port"] == node.port
        finally:
            conn.close()

    def test_bad_token_rejected(self, node):
        conn = rpc.connect("127.0.0.1", node.port)
        try:
            with pytest.raises(rpc.AuthError, match="token"):
                rpc.client_handshake(conn, token="wrong")
        finally:
            conn.close()

    def test_missing_token_rejected(self, node):
        conn = rpc.connect("127.0.0.1", node.port)
        try:
            with pytest.raises(rpc.AuthError):
                rpc.client_handshake(conn, token=None)
        finally:
            conn.close()

    def test_protocol_version_mismatch_rejected(self, node):
        conn = rpc.connect("127.0.0.1", node.port)
        try:
            conn.send({"op": "hello", "proto": 99, "token": "sekrit"})
            reply = conn.recv()
            assert reply["op"] == "error" and reply["code"] == "proto"
        finally:
            conn.close()

    def test_rejected_connection_cannot_dispatch(self, node):
        # After a failed handshake the worker drops the socket: a follow-up
        # op must never reach the dispatcher.
        conn = rpc.connect("127.0.0.1", node.port)
        try:
            with pytest.raises(rpc.AuthError):
                rpc.client_handshake(conn, token="wrong")
            with pytest.raises((rpc.ConnectionClosed, OSError)):
                conn.send({"op": "stats", "id": 2})
                conn.recv()
        finally:
            conn.close()


class TestWorkerSpecParsing:
    def test_local_and_remote_specs(self):
        assert parse_worker_spec("local") is None
        assert parse_worker_spec(" LOCAL ") is None
        assert parse_worker_spec("10.0.0.5:7077") == ("10.0.0.5", 7077)
        assert parse_worker_spec("worker-3.fleet.internal:80") == \
            ("worker-3.fleet.internal", 80)

    @pytest.mark.parametrize("bad", ["justahost", ":1234x", "h:0", "h:99999",
                                     "h:", 7077, None])
    def test_bad_specs_fail_at_construction(self, bad):
        with pytest.raises(ValueError, match="worker spec"):
            parse_worker_spec(bad)


class TestLocalSpawnPlatform:
    def test_worker_without_parent_platform_fails_at_spawn(self, monkeypatch):
        from repro.serving.spawner import LocalSpawner, SpawnError

        # A parent on a platform the child cannot get (as on a TPU host
        # whose one chip the parent holds): the child must die at spawn,
        # never fall back to serving from the CPU.
        monkeypatch.setattr(jax, "default_backend", lambda: "cuda")
        spawner = LocalSpawner("repro.launch.serve:build_decode_registry",
                               None, None, token=None)
        assert spawner.platform == "cuda"
        pending = spawner.launch(0, "platform-probe")
        proc = pending[1]
        try:
            with pytest.raises(SpawnError, match="exited"):
                spawner.connect(pending, timeout=120)
            proc.join(timeout=10)
            assert not proc.is_alive() and proc.exitcode != 0
        finally:
            if proc.is_alive():
                proc.kill()
                proc.join(timeout=10)


# ---------------------------------------------------------------------------
# Device-topology fingerprint (serialize layer)
# ---------------------------------------------------------------------------

@pytest.mark.skipif(not executable_serialization_available(),
                    reason="jax build cannot serialize executables")
class TestTopologyFingerprint:
    @pytest.fixture(scope="class")
    def artifact_bytes(self, tmp_path_factory):
        path = str(tmp_path_factory.mktemp("topo") / "t.json")
        warmup_and_save(demo_region("topo[0]"), _bufs(80), path,
                        DEMO_REGISTRY)
        with open(path + ".aot", "rb") as f:
            return f.read()

    def test_fingerprint_embedded_and_matching_hydrates(self, artifact_bytes):
        blob = pickle.loads(artifact_bytes)
        assert blob["topology"] == topology_fingerprint()
        assert executable_from_bytes(artifact_bytes) is not None

    def test_mismatch_rejected_before_xla(self, artifact_bytes):
        blob = pickle.loads(artifact_bytes)
        blob["topology"] = dict(blob["topology"], platform="tpu",
                                device_kind="TPU v4")
        # Poison the XLA payload too: if the fingerprint check ran AFTER
        # deserialization, this would crash inside XLA instead.
        blob["payload"] = b"not an xla executable"
        with pytest.raises(TopologyMismatch, match="re-lower"):
            executable_from_bytes(pickle.dumps(blob))

    def test_jax_version_skew_rejected(self, artifact_bytes):
        blob = pickle.loads(artifact_bytes)
        blob["topology"] = dict(blob["topology"], jax="0.0.1")
        with pytest.raises(TopologyMismatch):
            executable_from_bytes(pickle.dumps(blob))


# ---------------------------------------------------------------------------
# Remote bootstrap: subprocess workers over localhost TCP (the multi-host
# attach path — the frontend holds NO process handle for these workers)
# ---------------------------------------------------------------------------

WORKER_TOKEN = "test-remote-token"


@pytest.fixture(scope="module")
def remote_workers():
    """Two pre-started subprocess workers via the shared bootstrap helper
    (`repro.serving.worker.spawn_worker_subprocess` — the same one
    `benchmarks/cluster.py` uses, so the READY/argv contract has one home).
    Spawning happens in threads so the two jax cold starts overlap."""
    results: list = [None, None]

    def boot(i):
        results[i] = spawn_worker_subprocess(REGISTRY_SPEC,
                                             token=WORKER_TOKEN)

    threads = [threading.Thread(target=boot, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=180)
    if any(r is None for r in results):
        for r in results:
            if r is not None:
                r[0].kill()
        pytest.fail("worker subprocess bootstrap timed out")
    try:
        yield results
    finally:
        for p, _addr in results:
            if p.poll() is None:
                p.kill()
            p.wait(timeout=10)


class TestRemoteBootstrap:
    @pytest.fixture(scope="class")
    def mixed_frontend(self, remote_workers):
        # One pre-started remote worker + one locally spawned: both kinds
        # behind the same router/shipping/requeue machinery.
        (_, addr0), _ = remote_workers
        fe = ClusterFrontend(workers=[addr0, "local"],
                             registry=REGISTRY_SPEC, token=WORKER_TOKEN,
                             max_wait_ms=5.0, name="test-remote-mixed")
        yield fe
        fe.close()

    def test_parity_vs_inprocess_ground_truth(self, mixed_frontend, shared_w):
        # The existing 2-worker parity contract, now with a remote worker
        # in the fleet: WHAT is computed must not change with WHERE.
        tenants = []
        for i in range(4):
            tdg = demo_region(f"rpar[{i}]", waves=2 + (i % 2))
            mixed_frontend.register_tenant(f"rpar{i}", tdg,
                                           pinned={"w": shared_w})
            tenants.append((tdg, _bufs(200 + i, shared_w=shared_w)))
        futs = [mixed_frontend.submit(
            f"rpar{i}", {k: v for k, v in b.items() if k != "w"})
            for i, (_, b) in enumerate(tenants)]
        outs = [f.result(120) for f in futs]
        for (tdg, b), out in zip(tenants, outs):
            _check(out, tdg, b)
        # both kinds of worker actually served something
        used = {mixed_frontend.tenant(f"rpar{i}").worker for i in range(4)}
        assert used == {0, 1}

    def test_health_reports_kinds_and_topology(self, mixed_frontend):
        rows = mixed_frontend.health()
        assert [r["kind"] for r in rows] == ["remote", "local"]
        assert all(r["alive"] for r in rows)
        assert rows[0]["process_alive"] is None      # no handle for remote
        assert rows[1]["process_alive"] is True
        assert rows[0]["topology"] == topology_fingerprint()

    def test_remote_request_error_is_isolated(self, mixed_frontend):
        mixed_frontend.register_tenant("rerr", demo_region("rerr[0]"))
        with pytest.raises(ClusterRemoteError, match="missing"):
            mixed_frontend.serve("rerr", {"x0": jnp.ones((DIM, DIM))})
        good = _bufs(210)
        _check(mixed_frontend.serve("rerr", good),
               demo_region("rerr[0]"), good)

    def test_wire_totals_are_real_bytes(self, mixed_frontend):
        st = mixed_frontend.stats()
        for idx, w in st["wire"].items():
            assert w["messages_sent"] >= 1
            # frames are length-prefixed: bytes must dwarf message counts
            assert w["bytes_sent"] > w["messages_sent"] * 8
            assert w["bytes_received"] > w["messages_received"] * 8
        total = st["frontend"]["wire"]
        assert total["bytes_sent"] == sum(
            w["bytes_sent"] for w in st["wire"].values())


@pytest.mark.skipif(not executable_serialization_available(),
                    reason="jax build cannot serialize executables")
class TestRemoteColdHydration:
    """The acceptance gate: a pre-started remote worker hydrates the
    shipped artifact (0 intern misses, aot_served >= 1) and rejects a
    topology-mismatched artifact loudly instead of crashing."""

    @pytest.fixture(scope="class")
    def cold_remote(self, remote_workers):
        _, (_, addr1) = remote_workers
        fe = ClusterFrontend(workers=[addr1], registry=REGISTRY_SPEC,
                             token=WORKER_TOKEN, name="test-remote-cold")
        yield fe
        fe.close()

    @pytest.fixture(scope="class")
    def warm_artifact(self, tmp_path_factory):
        tdg = demo_region("rwarm[0]", waves=3)
        bufs = _bufs(220)
        path = str(tmp_path_factory.mktemp("rwarm") / "region.json")
        warmup_and_save(tdg, bufs, path, DEMO_REGISTRY)
        return path, tdg, bufs

    def test_cold_remote_worker_hydrates_without_relowering(
            self, cold_remote, warm_artifact):
        path, tdg, bufs = warm_artifact
        rec = cold_remote.register_tenant("rwarm", warm_path=path)
        assert rec.artifact is not None
        out = cold_remote.serve("rwarm", bufs)
        _check(out, tdg, bufs)
        st = cold_remote.stats()
        wk = st["workers"][0]
        assert st["aggregate"]["hydrated_inband"] == 1
        assert st["aggregate"]["aot_served"] >= 1
        assert wk["intern"]["misses"] == 0       # never lowered anything
        assert st["aggregate"]["aot_hydrate_failures"] == 0

    def test_topology_mismatch_rejected_loudly_not_crash(
            self, cold_remote, warm_artifact, tmp_path):
        path, tdg, bufs = warm_artifact
        bad = str(tmp_path / "badtopo.json")
        shutil.copy(path, bad)
        with open(path + ".aot", "rb") as f:
            blob = pickle.loads(f.read())
        blob["topology"] = dict(blob["topology"], platform="tpu",
                                device_kind="TPU v4")
        with open(bad + ".aot", "wb") as f:
            f.write(pickle.dumps(blob))
        before = cold_remote.stats()["aggregate"]
        cold_remote.register_tenant("badtopo", warm_path=bad)
        out = cold_remote.serve("badtopo", bufs)   # re-lower fallback works
        _check(out, tdg, bufs)
        after = cold_remote.stats()["aggregate"]
        assert after["aot_topology_rejects"] == \
            before["aot_topology_rejects"] + 1
        assert after["aot_hydrate_failures"] == \
            before["aot_hydrate_failures"] + 1
        assert len(cold_remote._alive()) == 1      # worker survived

    def test_close_shuts_down_remote_worker(self, cold_remote,
                                            remote_workers):
        # Must run LAST in this class: the frontend owns no process handle,
        # so the best-effort shutdown RPC is the only thing that can stop
        # the subprocess — assert it actually does, with a clean exit.
        proc = remote_workers[1][0]
        cold_remote.close()
        proc.wait(timeout=30)
        assert proc.returncode == 0


# ---------------------------------------------------------------------------
# close() escalation: terminate -> kill, never a leaked local process
# ---------------------------------------------------------------------------

class TestCloseEscalation:
    def test_worker_ignoring_shutdown_is_killed_and_reaped(self, monkeypatch):
        fe = ClusterFrontend(workers=1, registry=REGISTRY_SPEC,
                             shutdown_grace=0.5, name="test-escalate")
        h = fe._handles[0]
        proc = h.process
        assert proc.is_alive()
        # Simulate a worker that never sees the shutdown RPC *and* shrugs
        # off SIGTERM: close() must escalate to kill() and still reap it.
        monkeypatch.setattr(
            h, "request",
            lambda *a, **k: (_ for _ in ()).throw(RuntimeError("rpc down")))
        monkeypatch.setattr(proc, "terminate", lambda: None)
        fe.close()
        assert not proc.is_alive()
        assert proc.exitcode is not None           # reaped, not abandoned


# ---------------------------------------------------------------------------
# Binary header codec (no processes)
# ---------------------------------------------------------------------------

class TestBinaryCodec:
    """The hot-path codec must be a bit-exact substitute for JSON framing:
    same objects out, same blob discipline, smaller headers."""

    def _roundtrip(self, obj):
        return rpc.decode(rpc.encode(obj, codec="binary"))

    def test_scalars_containers_and_tuple_keys(self):
        obj = {"op": "submit_batch", "id": 3, "none": None, "flag": True,
               "f": 2.5, "s": "text", "tup": (1, 2), "lst": [1, [2, 3]],
               ("k", 1): "tuple-key", "neg": -(1 << 40)}
        back = self._roundtrip(obj)
        assert back == obj
        assert isinstance(back["tup"], tuple)
        assert isinstance(back["lst"], list)

    def test_arrays_bytes_and_dtypes(self):
        obj = {
            "f32": jnp.asarray(np.arange(6, dtype=np.float32).reshape(2, 3)),
            "bf16": jnp.asarray([[1.5, -2.0]], jnp.bfloat16),
            "i32_0d": jnp.asarray(7, jnp.int32),
            "blob": b"\x00\x01binary\xff",
        }
        back = self._roundtrip(obj)
        assert back["blob"] == obj["blob"]
        assert back["f32"].dtype == np.float32
        np.testing.assert_array_equal(back["f32"], np.asarray(obj["f32"]))
        assert str(back["bf16"].dtype) == "bfloat16"
        assert back["i32_0d"].shape == () and int(back["i32_0d"]) == 7
        back["f32"][0, 0] = 9.0          # decoded arrays stay writable copies

    def test_parity_with_json_codec_on_a_submit_frame(self):
        frame = {"op": "submit_batch", "entries": [
            {"id": 11, "tenant": "t", "buffers":
                {"x0": np.arange(12, dtype=np.float32).reshape(3, 4)}}]}
        via_bin = rpc.decode(rpc.encode(frame, codec="binary"))
        via_json = rpc.decode(rpc.encode(frame, codec="json"))
        np.testing.assert_array_equal(
            via_bin["entries"][0]["buffers"]["x0"],
            via_json["entries"][0]["buffers"]["x0"])
        assert via_bin["entries"][0]["id"] == via_json["entries"][0]["id"]
        # the point of the codec: same bytes in the blobs, smaller header
        assert len(rpc.encode(frame, codec="binary")) < \
            len(rpc.encode(frame, codec="json"))

    def test_out_of_range_int_points_at_json(self):
        with pytest.raises(TypeError, match="64-bit"):
            rpc.encode({"n": 1 << 70}, codec="binary")

    def test_unencodable_rejected(self):
        with pytest.raises(TypeError, match="cannot encode"):
            rpc.encode({"fn": lambda: None}, codec="binary")


def _bin_frame(header, blobs=()):
    """Hand-roll a v2 binary frame body around a raw header byte string."""
    parts = [b"B", struct.pack(">I", len(header)), header,
             struct.pack(">I", len(blobs))]
    for b in blobs:
        parts.append(b"\x00")
        parts.append(struct.pack(">Q", len(b)))
        parts.append(b)
    return b"".join(parts)


class TestBinaryHeaderAdversarial:
    """Every malformed binary header a peer could send must surface as
    ProtocolError — never a struct.error / KeyError traceback."""

    def test_unknown_tag(self):
        with pytest.raises(rpc.ProtocolError, match="unknown binary codec"):
            rpc.decode(_bin_frame(b"\x7f"))

    def test_truncated_int_node(self):
        with pytest.raises(rpc.ProtocolError, match="truncated int"):
            rpc.decode(_bin_frame(b"\x03\x00\x00"))

    def test_string_overruns_header(self):
        header = b"\x05" + struct.pack(">I", 999) + b"ab"
        with pytest.raises(rpc.ProtocolError, match="overruns the header"):
            rpc.decode(_bin_frame(header))

    def test_string_invalid_utf8(self):
        header = b"\x05" + struct.pack(">I", 2) + b"\xff\xfe"
        with pytest.raises(rpc.ProtocolError, match="not valid utf-8"):
            rpc.decode(_bin_frame(header))

    def test_container_count_lies(self):
        header = b"\x08" + struct.pack(">I", 0xFFFF0000)
        with pytest.raises(rpc.ProtocolError, match="container count"):
            rpc.decode(_bin_frame(header))

    def test_blob_index_out_of_range(self):
        header = b"\x06" + struct.pack(">I", 3)
        with pytest.raises(rpc.ProtocolError, match="out of range"):
            rpc.decode(_bin_frame(header))

    def test_trailing_header_bytes(self):
        with pytest.raises(rpc.ProtocolError, match="trailing bytes"):
            rpc.decode(_bin_frame(b"\x00\x00"))

    def test_unhashable_dict_key(self):
        # {[]: None} — a list node in key position decodes but cannot hash
        header = (b"\x09" + struct.pack(">I", 1)
                  + b"\x08" + struct.pack(">I", 0) + b"\x00")
        with pytest.raises(rpc.ProtocolError, match="unhashable"):
            rpc.decode(_bin_frame(header))

    def test_bogus_array_dtype(self):
        dt = b"no-such"
        header = (b"\x0a" + struct.pack(">I", 0) + bytes([len(dt)]) + dt
                  + bytes([1]) + struct.pack(">I", 4))
        with pytest.raises(rpc.ProtocolError, match="malformed codec node"):
            rpc.decode(_bin_frame(header, blobs=(b"\x00" * 16,)))

    def test_array_blob_size_mismatch(self):
        dt = b"float32"
        header = (b"\x0a" + struct.pack(">I", 0) + bytes([len(dt)]) + dt
                  + bytes([1]) + struct.pack(">I", 4))
        with pytest.raises(rpc.ProtocolError, match="disagrees with"):
            rpc.decode(_bin_frame(header, blobs=(b"\x00" * 3,)))

    def test_shm_reference_without_a_ring(self):
        # placement=1 blob on a ring-less decode: clean refusal, no deref
        header = b"\x06" + struct.pack(">I", 0)
        body = (b"B" + struct.pack(">I", len(header)) + header
                + struct.pack(">I", 1) + b"\x01" + struct.pack(">QQ", 0, 16))
        with pytest.raises(rpc.ProtocolError, match="no ring attached"):
            rpc.decode(body)


# ---------------------------------------------------------------------------
# Shared-memory ring (no processes)
# ---------------------------------------------------------------------------

class TestShmRing:
    def test_roundtrip_attach_and_stats(self):
        ring = ShmRing.create(4096)
        try:
            pos = ring.alloc(100)
            ring.write(pos, b"x" * 100)
            assert ring.read(pos, 100) == b"x" * 100
            # a second attachment sees the same bytes (the cross-process
            # contract, exercised in-process)
            peer = ShmRing.attach(ring.name, ring.size)
            assert peer.read(pos, 100) == b"x" * 100
            peer.close()
            st = ring.stats()
            assert st["allocated"] == 100 and st["outstanding"] == 100
            ring.ack(pos + 100)
            assert ring.stats()["outstanding"] == 0
        finally:
            ring.close()

    def test_alloc_pads_to_segment_end_instead_of_wrapping(self):
        ring = ShmRing.create(4096)
        try:
            a = ring.alloc(1500)
            ring.ack(a + 1500)
            b = ring.alloc(1500)
            ring.ack(b + 1500)
            c = ring.alloc(1500)            # 3000 + 1500 > 4096: must pad
            assert c % ring.size == 0       # lands at the segment start
            ring.write(c, b"z" * 1500)
            assert ring.read(c, 1500) == b"z" * 1500
        finally:
            ring.close()

    def test_full_ring_blocks_until_peer_acks(self):
        ring = ShmRing.create(4096)
        try:
            first = ring.alloc(2000)
            ring.alloc(2000)
            released = threading.Event()

            def _late_ack():
                time.sleep(0.3)
                released.set()
                ring.ack(first + 2000)

            threading.Thread(target=_late_ack, daemon=True).start()
            t0 = time.monotonic()
            pos = ring.alloc(2000, timeout=30)   # blocks until the ack
            assert released.is_set()
            assert time.monotonic() - t0 >= 0.2
            assert pos % ring.size == 0
        finally:
            ring.close()

    def test_oversized_blob_is_a_value_error(self):
        ring = ShmRing.create(4096)
        try:
            with pytest.raises(ValueError, match="contiguity bound"):
                ring.alloc(3000)                 # > size // 2
        finally:
            ring.close()

    def test_reads_are_bounds_checked(self):
        ring = ShmRing.create(4096)
        try:
            with pytest.raises(rpc.ProtocolError, match="sane segment span"):
                ring.read(0, 10 ** 9)
            with pytest.raises(rpc.ProtocolError, match="sane segment span"):
                ring.read(-1, 4)
            with pytest.raises(rpc.ProtocolError, match="overruns"):
                ring.read(4090, 100)
        finally:
            ring.close()

    def test_closed_ring_fails_allocators(self):
        ring = ShmRing.create(4096)
        ring.close()
        with pytest.raises(rpc.ProtocolError, match="closed"):
            ring.alloc(16)


# ---------------------------------------------------------------------------
# Dispatcher: batching, pipelining window, reply demux (socketpair, no jax)
# ---------------------------------------------------------------------------

def _handle_pair(window=None):
    """A _WorkerHandle wired to a fake worker: the test drives the peer
    end of a socketpair with raw protocol frames."""
    sa, sb = socket.socketpair()
    deaths = []
    handle = _WorkerHandle(
        0,
        SpawnedWorker(idx=0, kind="remote", address=("fake", 0),
                      conn=rpc.RpcConnection(sa)),
        itertools.count(1), deaths.append, window=window)
    return handle, rpc.RpcConnection(sb), deaths


class TestDispatcherWirePath:
    def test_window_pressure_packs_and_replies_demux_out_of_order(self):
        h, peer, _ = _handle_pair(window=1)
        try:
            f1 = h.submit_async("t", {})
            frame1 = peer.recv()
            assert frame1["op"] == "submit_batch"
            assert len(frame1["entries"]) == 1
            # window=1 with frame1 unanswered: these five must queue, and
            # the dispatcher must NOT put another frame on the wire
            futs = [h.submit_async("t", {"n": np.float32(i)})
                    for i in range(5)]
            # poll (not a fixed sleep): wait until all five are queued,
            # then the window invariant — exactly one frame in flight —
            # must hold
            deadline = time.monotonic() + 30
            while h.dispatch_stats()["queued_entries"] < 5 \
                    and time.monotonic() < deadline:
                time.sleep(0.005)
            ds = h.dispatch_stats()
            assert ds["inflight_frames"] == 1
            assert ds["queued_entries"] == 5
            # answering frame1 frees the window slot -> the backlog goes
            # out pre-coalesced: five submissions, ONE frame
            peer.send({"op": "result_batch", "entries": [
                {"id": frame1["entries"][0]["id"], "out": {"ok": 1}}]},
                codec="binary")
            assert f1.result(30)["out"]["ok"] == 1
            frame2 = peer.recv()
            ids = [e["id"] for e in frame2["entries"]]
            assert len(ids) == 5
            # out-of-order completion: reply reversed, each future still
            # resolves to ITS entry by id
            peer.send({"op": "result_batch", "entries": [
                {"id": m, "out": {"echo": m}} for m in reversed(ids)]},
                codec="binary")
            for fut, mid in zip(futs, ids):
                got = fut.result(30)
                assert got["id"] == mid and got["out"]["echo"] == mid
            ds = h.dispatch_stats()
            assert ds["frames_sent"] == 2 and ds["entries_sent"] == 6
            assert ds["inflight_frames"] == 0 and ds["queued_entries"] == 0
            assert ds["entries_per_frame"] == 3.0
        finally:
            h.close()
            peer.close()

    def test_error_entries_fail_only_their_future(self):
        h, peer, _ = _handle_pair()
        try:
            f_ok = h.submit_async("t", {})
            f_bad = h.submit_async("t", {})
            got = []
            while sum(len(f["entries"]) for f in got) < 2:
                got.append(peer.recv())
            mids = [e["id"] for f in got for e in f["entries"]]
            peer.send({"op": "result_batch", "entries": [
                {"id": mids[0], "out": {"y": 1}},
                {"id": mids[1], "error": "KeyError: nope"}]}, codec="binary")
            assert f_ok.result(30)["out"]["y"] == 1
            with pytest.raises(ClusterRemoteError, match="nope"):
                f_bad.result(30)
            assert h.alive                  # a remote error is not a death
        finally:
            h.close()
            peer.close()

    def test_control_timeout_disowns_pending_and_is_counted(self):
        h, peer, _ = _handle_pair()
        try:
            with pytest.raises(ClusterError, match="no reply"):
                h.request({"op": "ping"}, timeout=0.3)
            # the fixed leak: the demux table must NOT retain the entry
            with h._lock:
                assert not h._pending
            assert h.dispatch_stats()["timeouts"] == 1
            # the late reply arrives anyway; the reader drops it silently
            late = peer.recv()
            peer.send({"op": "result", "id": late["id"], "pong": True})

            def _answer_next():
                msg = peer.recv()
                peer.send({"op": "result", "id": msg["id"], "pong": True})

            t = threading.Thread(target=_answer_next, daemon=True)
            t.start()
            # ...and the connection is still healthy for the next request
            assert h.request({"op": "ping"}, timeout=30)["pong"] is True
            t.join(timeout=10)
            assert h.alive
        finally:
            h.close()
            peer.close()


# ---------------------------------------------------------------------------
# Batch admission (in-process RegionServer, no processes)
# ---------------------------------------------------------------------------

class TestSubmitManyAdmission:
    def test_mixed_batch_is_positionally_aligned(self):
        with RegionServer(max_batch=4, name="many") as server:
            tdg = demo_region("many[0]")
            server.register_tenant("m", tdg)
            good_a, good_b = _bufs(300), _bufs(301)
            futs = server.submit_many([
                ("m", good_a),
                ("ghost", good_a),                  # unknown tenant
                ("m", {"x0": good_a["x0"]}),        # missing input slots
                ("m", good_b),
            ])
            assert len(futs) == 4
            _check(futs[0].result(300), tdg, good_a)
            with pytest.raises(KeyError, match="ghost"):
                futs[1].result(300)
            with pytest.raises(KeyError, match="missing"):
                futs[2].result(300)
            _check(futs[3].result(300), tdg, good_b)
            assert server.metrics.snapshot()["admitted"] >= 2


# ---------------------------------------------------------------------------
# Wire path on a live cluster (module-scoped frontend)
# ---------------------------------------------------------------------------

class TestWirePathCluster:
    def test_burst_parity_and_wire_stats(self, frontend, shared_w):
        tdg = demo_region("wire[0]")
        frontend.register_tenant("wire", tdg, pinned={"w": shared_w})
        before = frontend.stats()["frontend"]["wire"]
        bufs_list = [{f"x{s}": jnp.asarray(
            np.random.default_rng(700 + 10 * i + s)
            .standard_normal((DIM, DIM)), jnp.float32) for s in range(2)}
            for i in range(24)]
        futs = [frontend.submit("wire", b) for b in bufs_list]
        for b, f in zip(bufs_list, futs):
            _check(f.result(300), tdg, {**b, "w": shared_w})
        st = frontend.stats()
        after = st["frontend"]["wire"]
        # every submission went through the batch path, never one frame
        # per request more than the burst size
        assert after["entries_sent"] - before["entries_sent"] >= 24
        assert after["frames_sent"] - before["frames_sent"] <= 24
        assert after["frames_sent"] <= after["entries_sent"]
        assert after["encode_seconds"] > 0.0
        assert after["decode_seconds"] > 0.0
        assert after["timeouts"] == 0
        fr = st["frontend"]
        assert fr["transport"] in ("tcp", "shm", "auto")
        assert fr["window"] >= 1
        for row in st["wire"].values():
            assert row["window"] == fr["window"]
            assert row["entries_per_frame"] >= 1.0 or row["frames_sent"] == 0
            assert row["transport"] in ("tcp", "shm")
            assert row["inflight_frames"] == 0      # drained after the burst


# ---------------------------------------------------------------------------
# Shared-memory transport end to end (own 1-worker frontends)
# ---------------------------------------------------------------------------

class TestShmTransport:
    def test_shm_data_plane_carries_tensors_with_parity(self):
        big = 32                # 32x32 f32 = 4 KiB/blob: over the shm floor
        with ClusterFrontend(workers=1, registry=REGISTRY_SPEC,
                             transport="shm", name="test-shm") as fe:
            row = fe.stats()["wire"][0]
            if row["transport"] != "shm":
                pytest.skip("shm attach refused on this host")
            tdg = demo_region("shm[0]")
            fe.register_tenant("sm", tdg)
            rng = np.random.default_rng(42)
            bufs = {k: jnp.asarray(rng.standard_normal((big, big)),
                                   jnp.float32) for k in ("x0", "x1", "w")}
            out = fe.serve("sm", bufs)
            _check(out, tdg, bufs)
            st = fe.stats()
            row = st["wire"][0]
            assert row["shm_bytes_sent"] >= 3 * big * big * 4
            assert row["shm_bytes_received"] > 0    # replies rode shm too
            assert st["frontend"]["shm_fallbacks"] == 0
            assert st["frontend"]["wire"]["shm_bytes_sent"] == \
                row["shm_bytes_sent"]

    def test_tcp_pinned_worker_forces_counted_fallback(self, monkeypatch):
        # The spawned worker inherits the env pin and refuses the rings;
        # the frontend must land on tcp, count it, and keep full parity.
        monkeypatch.setenv("REPRO_RPC_TRANSPORT", "tcp")
        with ClusterFrontend(workers=1, registry=REGISTRY_SPEC,
                             transport="shm", name="test-shm-fb") as fe:
            st = fe.stats()
            assert st["wire"][0]["transport"] == "tcp"
            assert st["frontend"]["shm_fallbacks"] == 1
            tdg = demo_region("shmfb[0]")
            fe.register_tenant("fb", tdg)
            bufs = _bufs(500)
            _check(fe.serve("fb", bufs), tdg, bufs)
            assert fe.stats()["wire"][0]["shm_bytes_sent"] == 0


class TestShmSetupRefusal:
    """shm-setup is peer-controlled input: a bogus offer must be refused
    with a reason on a connection that stays fully usable."""

    def _spin_node(self, **kwargs):
        node = WorkerNode(DEMO_REGISTRY, max_batch=1, **kwargs)
        t = threading.Thread(target=node.serve_forever, daemon=True)
        t.start()
        return node, t

    def _shutdown(self, conn, t):
        conn.request({"op": "shutdown", "id": 99})
        conn.close()
        t.join(timeout=10)

    def test_unattachable_segments_refused_not_fatal(self):
        node, t = self._spin_node()
        conn = rpc.connect("127.0.0.1", node.port)
        try:
            rpc.client_handshake(conn)
            reply = conn.request({"op": "shm-setup", "id": 7,
                                  "tx": "repro-ring-no-such-segment",
                                  "rx": "repro-ring-no-such-segment",
                                  "size": 4096})
            assert reply["attached"] is False
            assert reply["reason"]
            # the refusal must not poison the connection
            assert conn.request({"op": "ping", "id": 8})["port"] == node.port
        finally:
            self._shutdown(conn, t)

    def test_tcp_pinned_node_refuses_real_segments(self):
        node, t = self._spin_node(transport="tcp")
        conn = rpc.connect("127.0.0.1", node.port)
        tx, rx = ShmRing.create(4096), ShmRing.create(4096)
        try:
            rpc.client_handshake(conn)
            reply = conn.request({"op": "shm-setup", "id": 7,
                                  "tx": tx.name, "rx": rx.name,
                                  "size": 4096})
            assert reply["attached"] is False
            assert "tcp" in reply["reason"]
        finally:
            tx.close()
            rx.close()
            self._shutdown(conn, t)
