"""Differential harness for multi-device sharded fused replay.

The tentpole claim: constraining a fused class's stacked batch axis onto a
mesh (``sharding.replay.shard_leading``) changes WHERE each lane computes,
never WHAT it computes — every lane is independent, so sharded replay must
be *bit-exact* against the single-device fused form (``assert_array_equal``,
not allclose), and match the unrolled/eager forms to float tolerance.

Tier-1 (1 CPU device) runs the mesh-resolution / fingerprint / padding unit
tests; the multi-device differentials skip themselves. ``scripts/ci.sh``
runs this module a second time under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` where everything is
live. Tests gate on ``jax.device_count()`` at runtime, so they also work at
2 or 4 faked devices.
"""
import itertools
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (EagerExecutor, ReplayExecutor, TDG, TopologyMismatch,
                        clear_intern_cache, executable_from_bytes,
                        executable_serialization_available,
                        executable_to_bytes, fused_tdg_as_function,
                        intern_stats, lower_tdg, taskgraph,
                        topology_fingerprint)
from repro.core.lower import aot_compile_tdg
from repro.core.serialize import TaskFnRegistry, load_warm, warmup_and_save
from repro.launch.mesh import make_replay_mesh
from repro.serving.server import RegionServer
from repro.sharding import partition as _partition
from repro.sharding import replay as shreplay

DEVICES = jax.device_count()

MESH_LEG_HINT = ("run via scripts/ci.sh mesh leg "
                 "(XLA_FLAGS=--xla_force_host_platform_device_count=8)")


def needs(n):
    return pytest.mark.skipif(DEVICES < n,
                              reason=f"needs {n} devices; {MESH_LEG_HINT}")


def _largest_mesh(cap=8):
    """Biggest power-of-two device count available, capped."""
    n = 1
    while n * 2 <= min(DEVICES, cap):
        n *= 2
    return n


# ---------------------------------------------------------------------------
# graph builders (mirroring tests/test_fusion.py idiom)
# ---------------------------------------------------------------------------

def _mm(x):
    return jnp.tanh(x @ x.T) @ x * 0.5 + x


def _gelu_mix(x):
    return jax.nn.gelu(x) @ x + x.sum(axis=-1, keepdims=True)


def _shared_proj(x, w):
    return jnp.tanh(x @ w) @ w.T + x


def _grid_tdg(occupancy, n_waves=2, name="mesh_grid"):
    """``occupancy`` independent chains of ``n_waves`` identical tasks: each
    wave is one fusion class of exactly ``occupancy`` members."""
    tdg = TDG(region=f"{name}_{occupancy}x{n_waves}")
    for c in range(occupancy):
        src = f"x{c}"
        for w in range(n_waves):
            dst = f"h{c}_{w}"
            tdg.add_task(_mm, ins=[src], outs=[dst], name=f"t{c}_{w}")
            src = dst
    return tdg


def _grid_inputs(occupancy, dim=4, seed=0):
    rng = np.random.default_rng(seed)
    return {f"x{c}": jnp.asarray(rng.standard_normal((dim, dim)),
                                 jnp.float32)
            for c in range(occupancy)}


def _shared_w_tdg(occupancy):
    """Every class member shares the constant-signature slot ``w`` (the MoE
    router-weight shape from test_fusion): only ``x`` stacks and shards."""
    tdg = TDG(region=f"mesh_shared_{occupancy}")
    for c in range(occupancy):
        tdg.add_task(_shared_proj, ins=[f"x{c}", "w"], outs=[f"y{c}"],
                     name=f"proj{c}")
    return tdg


_SWEEP_PAYLOADS = (_mm, _gelu_mix)


def _random_wave_tdg(seed, occupancy, n_waves):
    """Seeded wave-structured TDG: each wave picks one payload for all its
    tasks (so it fuses into a single class) and random fan-in from the
    previous wave — the property-test structure space."""
    rng = np.random.default_rng(seed)
    tdg = TDG(region=f"mesh_rand_{seed}_{occupancy}x{n_waves}")
    prev = [f"x{c}" for c in range(occupancy)]
    for w in range(n_waves):
        fn = _SWEEP_PAYLOADS[int(rng.integers(len(_SWEEP_PAYLOADS)))]
        width = max(1, int(rng.integers(1, occupancy + 1)))
        cur = []
        for c in range(width):
            src = prev[int(rng.integers(len(prev)))]
            dst = f"h{w}_{c}"
            tdg.add_task(fn, ins=[src], outs=[dst], name=f"t{w}_{c}")
            cur.append(dst)
        prev = cur
    return tdg


def _assert_tree_equal(a, b):
    ka, kb = sorted(a), sorted(b)
    assert ka == kb
    for k in ka:
        np.testing.assert_array_equal(np.asarray(a[k]), np.asarray(b[k]),
                                      err_msg=f"slot {k!r}")


def _assert_tree_close(a, b, tol=2e-5):
    for k in sorted(a):
        np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                   rtol=tol, atol=tol, err_msg=f"slot {k!r}")


# ---------------------------------------------------------------------------
# mesh resolution / fingerprint / padding (1-device safe)
# ---------------------------------------------------------------------------

class TestResolveMesh:
    def test_none_stays_none(self):
        assert shreplay.resolve_mesh(None) is None
        assert shreplay.mesh_fingerprint(None) is None

    def test_auto_without_env_or_scope_is_none(self, monkeypatch):
        monkeypatch.delenv(shreplay.MESH_ENV, raising=False)
        assert shreplay.resolve_mesh("auto") is None

    @pytest.mark.parametrize("raw", ["", "0", "off", "false", "no", "none",
                                     "OFF", "False"])
    def test_env_off_values(self, monkeypatch, raw):
        monkeypatch.setenv(shreplay.MESH_ENV, raw)
        assert shreplay.resolve_mesh("auto") is None

    def test_env_junk_raises(self, monkeypatch):
        monkeypatch.setenv(shreplay.MESH_ENV, "banana")
        with pytest.raises(ValueError, match=shreplay.MESH_ENV):
            shreplay.resolve_mesh("auto")

    def test_env_one_device_normalizes_to_none(self, monkeypatch):
        # A 1-way batch axis shards nothing: resolve to the single-device
        # path instead of paying GSPMD constraint overhead for free.
        monkeypatch.setenv(shreplay.MESH_ENV, "1")
        assert shreplay.resolve_mesh("auto") is None

    def test_non_auto_string_rejected(self):
        with pytest.raises(ValueError):
            shreplay.resolve_mesh("data=8")

    def test_one_device_mesh_normalizes_to_none(self):
        assert shreplay.resolve_mesh(make_replay_mesh(1)) is None

    def test_make_replay_mesh_bad_count(self):
        with pytest.raises(ValueError):
            make_replay_mesh(0)

    def test_make_replay_mesh_too_many_mentions_flag(self):
        with pytest.raises(RuntimeError,
                           match="xla_force_host_platform_device_count"):
            make_replay_mesh(DEVICES + 1)

    def test_pad_group_no_mesh_is_identity(self):
        members = [jnp.zeros(3), jnp.ones(3)]
        assert shreplay.pad_group(members, None) == 0
        assert len(members) == 2

    @needs(2)
    def test_fingerprint_is_stable_string(self):
        mesh = make_replay_mesh(2)
        fp = shreplay.mesh_fingerprint(mesh)
        assert fp == "data=2"
        # the fingerprint crosses the cluster's JSON wire — must round-trip
        assert json.loads(json.dumps(fp)) == fp

    @needs(2)
    def test_pad_group_rounds_up_repeating_last(self):
        mesh = make_replay_mesh(2)
        a, b, c = jnp.zeros(3), jnp.ones(3), jnp.full(3, 2.0)
        members = [a, b, c]
        assert shreplay.pad_group(members, mesh) == 1
        assert len(members) == 4 and members[3] is c

    @needs(2)
    def test_env_count_resolves(self, monkeypatch):
        monkeypatch.setenv(shreplay.MESH_ENV, "2")
        assert shreplay.mesh_fingerprint(shreplay.resolve_mesh("auto")) == \
            "data=2"
        monkeypatch.setenv(shreplay.MESH_ENV, "all")
        assert shreplay.mesh_fingerprint(shreplay.resolve_mesh("auto")) == \
            f"data={DEVICES}"

    @needs(4)
    def test_scope_beats_env(self, monkeypatch):
        monkeypatch.setenv(shreplay.MESH_ENV, "2")
        with _partition.use_mesh(make_replay_mesh(4)):
            fp = shreplay.mesh_fingerprint(shreplay.resolve_mesh("auto"))
        assert fp == "data=4"
        # scope restored: env wins again outside
        assert shreplay.mesh_fingerprint(shreplay.resolve_mesh("auto")) == \
            "data=2"

    @needs(4)
    def test_explicit_mesh_beats_scope(self):
        with _partition.use_mesh(make_replay_mesh(2)):
            fp = shreplay.mesh_fingerprint(
                shreplay.resolve_mesh(make_replay_mesh(4)))
        assert fp == "data=4"

    @needs(2)
    def test_batch_axis_size(self):
        assert shreplay.batch_axis_size(None) == 1
        assert shreplay.batch_axis_size(make_replay_mesh(2)) == 2


# ---------------------------------------------------------------------------
# the differential: sharded == unsharded exactly, == unrolled/eager closely
# ---------------------------------------------------------------------------

def _differential(tdg, buffers, mesh):
    """Run the three forms and cross-check: this is THE harness invariant."""
    sharded = lower_tdg(tdg, mesh=mesh)(buffers)
    plain = lower_tdg(tdg, mesh=None)(buffers)
    unrolled = lower_tdg(tdg, fuse=False, mesh=None)(buffers)
    _assert_tree_equal(sharded, plain)
    _assert_tree_close(sharded, unrolled)
    return sharded


class TestDifferential:
    @pytest.mark.parametrize("n_dev", [2, 4, 8])
    @pytest.mark.parametrize("occupancy", [1, 3, 8])
    def test_grid_parity(self, n_dev, occupancy):
        if DEVICES < n_dev:
            pytest.skip(f"needs {n_dev} devices; {MESH_LEG_HINT}")
        tdg = _grid_tdg(occupancy, name=f"grid{n_dev}")
        _differential(tdg, _grid_inputs(occupancy, seed=occupancy),
                      make_replay_mesh(n_dev))

    @needs(2)
    @pytest.mark.parametrize("occupancy", [3, 5, 7])
    def test_non_divisible_occupancy_pads_exactly(self, occupancy):
        """Odd class sizes on every available mesh width: the pad lanes are
        computed but never read back, so results stay bit-exact."""
        n_dev = _largest_mesh()
        tdg = _grid_tdg(occupancy, name=f"pad{occupancy}")
        _differential(tdg, _grid_inputs(occupancy, seed=occupancy + 100),
                      make_replay_mesh(n_dev))

    @needs(2)
    def test_shared_constant_arg_not_sharded(self):
        """The shared slot ``w`` has constant signature: only the varying
        ``x`` members stack/shard, ``w`` broadcasts — still bit-exact."""
        occupancy = 5
        tdg = _shared_w_tdg(occupancy)
        rng = np.random.default_rng(7)
        buffers = _grid_inputs(occupancy, seed=7)
        buffers["w"] = jnp.asarray(rng.standard_normal((4, 4)), jnp.float32)
        _differential(tdg, buffers, make_replay_mesh(_largest_mesh()))

    @needs(2)
    def test_seeded_random_sweep(self):
        """Always-on miniature of the hypothesis property test: random
        wave-structured TDGs x occupancy x every available device count."""
        for seed in range(6):
            rng = np.random.default_rng(1000 + seed)
            occupancy = int(rng.integers(1, 11))
            n_waves = int(rng.integers(1, 4))
            tdg = _random_wave_tdg(seed, occupancy, n_waves)
            buffers = _grid_inputs(occupancy, seed=seed)
            for n_dev in (2, 4, 8):
                if n_dev > DEVICES:
                    continue
                sharded = lower_tdg(tdg, mesh=make_replay_mesh(n_dev))(buffers)
                plain = lower_tdg(tdg, mesh=None)(buffers)
                _assert_tree_equal(sharded, plain)
            eager = EagerExecutor(tdg).run(dict(buffers))
            for k in plain:
                np.testing.assert_allclose(np.asarray(plain[k]),
                                           np.asarray(eager[k]),
                                           rtol=2e-5, atol=2e-5)

    @needs(2)
    def test_unbatchable_class_falls_back_single_device(self):
        """A payload with no usable vmap path degrades its class to the
        unrolled (single-device) form under a mesh — the per-class fallback
        — while other classes in the same TDG still fuse and shard."""
        from jax.interpreters.batching import BatchTracer

        def stubborn(x):
            if isinstance(x, BatchTracer):
                raise TypeError("no batching rule for this payload")
            return x * 2.0 + 1.0

        occupancy = 4
        tdg = TDG(region="mesh_fallback")
        for c in range(occupancy):
            tdg.add_task(stubborn, ins=[f"x{c}"], outs=[f"s{c}"],
                         name=f"stub{c}")
        for c in range(occupancy):
            tdg.add_task(_mm, ins=[f"s{c}"], outs=[f"y{c}"], name=f"mm{c}")
        buffers = _grid_inputs(occupancy, seed=42)
        mesh = make_replay_mesh(_largest_mesh())

        fn = fused_tdg_as_function(tdg, mesh=mesh)
        out = fn(buffers)
        fused_flags = {cls.fused for cls in fn.last_plan.classes}
        assert fused_flags == {True, False}  # mm wave fused, stubborn not

        expected = EagerExecutor(tdg).run(dict(buffers))
        for k in out:
            np.testing.assert_allclose(np.asarray(out[k]),
                                       np.asarray(expected[k]),
                                       rtol=2e-5, atol=2e-5)
        # and through the full jitted lowering path
        _assert_tree_equal(lower_tdg(tdg, mesh=mesh)(buffers),
                           lower_tdg(tdg, mesh=None)(buffers))

    @needs(2)
    def test_repeated_operand_class_stays_whole_under_mesh(self):
        """``x[i] @ w[j]`` over all pairs: ``w[j]`` repeats across members.
        One device cuts the class by ``j`` and broadcasts ``w[j]``; under a
        mesh the class stays whole and every argument is a stacked copy,
        and the two replays agree."""
        def pair(x, w):
            return jnp.tanh(x @ w) + x

        n = 4
        tdg = TDG(region="mesh_repeated")
        for i in range(n):
            for j in range(n):
                tdg.add_task(pair, ins=[f"x{i}", f"w{j}"], outs=[f"y{i}_{j}"])
        rng = np.random.default_rng(5)
        buffers = {f"{p}{i}": jnp.asarray(rng.standard_normal((128, 128)),
                                          jnp.float32)
                   for p in "xw" for i in range(n)}
        mesh = make_replay_mesh(_largest_mesh())

        plain = fused_tdg_as_function(tdg)
        plain_out = plain(buffers)
        assert plain.last_plan.split_off == n - 1
        sharded = fused_tdg_as_function(tdg, mesh=mesh)
        sharded_out = sharded(buffers)
        [cls] = sharded.last_plan.classes
        assert cls.split is None and cls.args == ("stacked", "stacked")
        _assert_tree_close(sharded_out, plain_out, tol=1e-5)
        _assert_tree_close(lower_tdg(tdg, mesh=mesh)(buffers),
                           lower_tdg(tdg, mesh=None)(buffers), tol=1e-5)

    @needs(2)
    def test_map_batcher_ignores_mesh(self):
        """lax.map serializes class members on purpose — it must stay
        single-device (mesh silently dropped), and still agree."""
        tdg = _grid_tdg(4, name="mapb")
        buffers = _grid_inputs(4, seed=9)
        out = lower_tdg(tdg, batcher="map",
                        mesh=make_replay_mesh(_largest_mesh()))(buffers)
        _assert_tree_equal(out, lower_tdg(tdg, mesh=None)(buffers))


# optional deep property test (hypothesis is not a tier-1 dependency)
try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYP = True
except ImportError:  # pragma: no cover - env-dependent
    HAVE_HYP = False

if HAVE_HYP:
    @needs(2)
    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 2**16),
           occupancy=st.integers(1, 64),
           n_waves=st.integers(1, 4),
           n_dev=st.sampled_from([1, 2, 4, 8]))
    def test_property_sharded_replay_bit_exact(seed, occupancy, n_waves,
                                               n_dev):
        if n_dev > DEVICES:
            n_dev = DEVICES
        tdg = _random_wave_tdg(seed, occupancy, n_waves)
        buffers = _grid_inputs(occupancy, dim=2, seed=seed)
        mesh = make_replay_mesh(n_dev) if n_dev > 1 else None
        sharded = lower_tdg(tdg, mesh=mesh)(buffers)
        plain = lower_tdg(tdg, mesh=None)(buffers)
        _assert_tree_equal(sharded, plain)


# ---------------------------------------------------------------------------
# executor / region / env plumbing
# ---------------------------------------------------------------------------

_REGION_IDS = itertools.count()


class TestExecutorAndRegion:
    @needs(2)
    def test_replay_executor_mesh_parity_and_keys(self):
        tdg = _grid_tdg(4, name="exec")
        buffers = _grid_inputs(4, seed=3)
        mesh = make_replay_mesh(2)
        ex_m = ReplayExecutor(tdg, mesh=mesh)
        ex_p = ReplayExecutor(tdg, mesh=None)
        assert ex_m.mesh_fp == "data=2" and ex_p.mesh_fp is None
        _assert_tree_equal(ex_m.run(dict(buffers)), ex_p.run(dict(buffers)))

    @needs(2)
    def test_region_mesh_resolves_per_replay(self, monkeypatch):
        """A region built with the default mesh="auto" picks up REPRO_MESH
        at replay time, keys its cache by fingerprint, and flipping the env
        re-lowers instead of serving a stale single-device executable."""
        monkeypatch.delenv(shreplay.MESH_ENV, raising=False)

        @taskgraph(name=f"mesh_region_{next(_REGION_IDS)}")
        def region(g, x):
            g.task(_mm, ins=["x"], outs=["h"], name="a")
            g.task(_mm, ins=["h"], outs=["y"], name="b")

        x = jnp.asarray(np.random.default_rng(5).standard_normal((4, 4)),
                        jnp.float32)
        o_plain = region(x=x)           # record
        o_plain = region(x=x)           # replay, single-device
        monkeypatch.setenv(shreplay.MESH_ENV, "2")
        o_mesh = region(x=x)            # replay, sharded
        _assert_tree_equal(o_mesh, o_plain)
        fps = {key[2] for key in region._replay_cache}
        assert fps == {None, "data=2"}

    @needs(2)
    def test_env_and_explicit_mesh_intern_to_same_executable(self):
        """REPRO_MESH=2 and an explicit 2-device mesh produce the same
        fingerprint, so the global intern cache serves one executable."""
        tdg = _grid_tdg(3, name="internhit")
        buffers = _grid_inputs(3, seed=11)
        clear_intern_cache()
        out1 = lower_tdg(tdg, mesh=make_replay_mesh(2))(buffers)
        with _partition.use_mesh(make_replay_mesh(2)):
            out2 = lower_tdg(tdg)(buffers)  # mesh="auto" -> ambient scope
        stats = intern_stats()
        assert stats["entries"] == 1 and stats["hits"] >= 1
        _assert_tree_equal(out1, out2)

    @needs(2)
    def test_mesh_and_no_mesh_never_collide_in_intern_cache(self):
        tdg = _grid_tdg(3, name="internmiss")
        buffers = _grid_inputs(3, seed=12)
        clear_intern_cache()
        out_m = lower_tdg(tdg, mesh=make_replay_mesh(2))(buffers)
        out_p = lower_tdg(tdg, mesh=None)(buffers)
        stats = intern_stats()
        assert stats["entries"] == 2 and stats["misses"] == 2
        _assert_tree_equal(out_m, out_p)


# ---------------------------------------------------------------------------
# serving: batched dispatch under a mesh, pool keys, eviction
# ---------------------------------------------------------------------------

def _serve_rounds(server, rounds):
    """Submit each round as one frame; returns [round][request] outputs."""
    results = []
    for reqs in rounds:
        futures = server.submit_many(reqs)
        results.append([f.result(timeout=60) for f in futures])
    return results


class TestServingUnderMesh:
    @needs(2)
    @pytest.mark.parametrize("occupancy", [4, 3])
    def test_batched_dispatch_parity(self, occupancy):
        """The same admission batch through a sharded and a plain server is
        bit-exact, including non-power-of-two (bucket-rounded) occupancy."""
        n_dev = _largest_mesh()
        rng = np.random.default_rng(occupancy)
        reqs = [("t0", {"x": jnp.asarray(rng.standard_normal((4, 4)),
                                         jnp.float32)})
                for _ in range(occupancy)]

        def one(mesh):
            srv = RegionServer(max_batch=8, max_wait_ms=30.0, mesh=mesh)
            try:
                @taskgraph(name=f"srv_region_{next(_REGION_IDS)}")
                def region(g, x):
                    g.task(_mm, ins=["x"], outs=["h"], name="a")
                    g.task(_mm, ins=["h"], outs=["y"], name="b")
                region(x=reqs[0][1]["x"])  # record
                srv.register_tenant("t0", region.tdg)
                return _serve_rounds(srv, [reqs])[0], srv.stats()
            finally:
                srv.close()

        out_m, stats_m = one(make_replay_mesh(n_dev))
        out_p, stats_p = one(None)
        assert stats_m["mesh"] == f"data={n_dev}" and stats_p["mesh"] is None
        for a, b in zip(out_m, out_p):
            _assert_tree_equal(a, b)

    @needs(2)
    def test_pool_keys_carry_mesh_fingerprint(self, tmp_path):
        """WarmPool AOT + batched keys end in the server's mesh fingerprint
        — a 1-device worker can never serve an N-device executable."""
        n_dev = _largest_mesh()
        srv = RegionServer(max_batch=4, max_wait_ms=20.0,
                           mesh=make_replay_mesh(n_dev))
        try:
            tdg = _grid_tdg(2, name="poolkeys")
            srv.register_tenant("pk", tdg)
            # per-request DISTINCT arrays: members sharing the very same
            # buffer objects collapse to the all-shared single-replay path
            # and never exercise the batched callable
            reqs = [("pk", _grid_inputs(2, seed=21 + i)) for i in range(2)]
            # dispatch BEFORE warming: a warm AOT executable would serve the
            # frame per-request and skip the batched-callable path entirely
            futures = srv.submit_many(reqs)
            for f in futures:
                f.result(timeout=60)
            srv.warmup("pk", _grid_inputs(2, seed=21))
            keys = list(srv.pool._entries)
            assert keys, "warmup + dispatch should have populated the pool"
            for key in keys:
                assert key[-1] == f"data={n_dev}", key
            assert {k[0] for k in keys} >= {"aot", "batched"}
        finally:
            srv.close()

    @needs(2)
    def test_pool_eviction_under_mesh_preserves_parity(self):
        """pool_capacity=1 with two alternating structures: every round
        evicts and recompiles, and sharded results stay exact throughout."""
        n_dev = _largest_mesh()

        def payload_b(x):
            return jax.nn.relu(x @ x.T) - x

        tdg_a = _grid_tdg(2, name="evict_a")
        tdg_b = TDG(region="evict_b")
        for c in range(2):
            tdg_b.add_task(payload_b, ins=[f"x{c}"], outs=[f"y{c}"],
                           name=f"b{c}")
        # distinct per-request data (identical objects would collapse to
        # the all-shared single-replay path and bypass the pool entirely)
        rounds = []
        for i, name in enumerate(["a", "b", "a", "b"]):
            rounds.append([(name, _grid_inputs(2, seed=31 + 10 * i + j))
                           for j in range(2)])

        def run(mesh):
            srv = RegionServer(max_batch=4, max_wait_ms=20.0,
                               pool_capacity=1, mesh=mesh)
            try:
                srv.register_tenant("a", tdg_a)
                srv.register_tenant("b", tdg_b)
                out = _serve_rounds(srv, rounds)
                return out, srv.pool.stats()
            finally:
                srv.close()

        out_m, pool_m = run(make_replay_mesh(n_dev))
        out_p, _ = run(None)
        assert pool_m["evictions"] > 0
        for rm, rp in zip(out_m, out_p):
            for a, b in zip(rm, rp):
                _assert_tree_equal(a, b)


# ---------------------------------------------------------------------------
# topology fingerprint / artifact hydration (satellite 3)
# ---------------------------------------------------------------------------

needs_serialization = pytest.mark.skipif(
    not executable_serialization_available(),
    reason="jax build lacks executable serialization")


class TestTopologyMesh:
    def test_fingerprint_has_mesh_and_is_json_stable(self):
        fp = topology_fingerprint(mesh=None)
        assert fp["mesh"] is None
        assert json.loads(json.dumps(fp)) == fp

    @needs(2)
    def test_fingerprint_mesh_field(self):
        fp = topology_fingerprint(mesh=make_replay_mesh(2))
        assert fp["mesh"] == "data=2"
        assert json.loads(json.dumps(fp)) == fp

    @needs(2)
    @needs_serialization
    def test_artifact_mesh_mismatch_raises(self):
        """An executable compiled under an N-device replay mesh must refuse
        to hydrate on a worker whose replay mesh differs — same device
        count, same platform: the MESH is the distinguishing factor. (A
        differing device_count already tripped the pre-existing fields;
        this is the gap satellite 3 closes.)"""
        n_dev = _largest_mesh()
        tdg = _grid_tdg(2, name="topo")
        buffers = _grid_inputs(2, seed=51)
        aot = aot_compile_tdg(tdg, buffers, mesh=make_replay_mesh(n_dev))
        assert aot.mesh_fp == f"data={n_dev}"
        blob = executable_to_bytes(aot)

        with pytest.raises(TopologyMismatch):
            executable_from_bytes(blob, mesh=None)

        back = executable_from_bytes(blob, mesh=f"data={n_dev}")
        assert back.mesh_fp == f"data={n_dev}"
        _assert_tree_equal(back(buffers), lower_tdg(tdg, mesh=None)(buffers))

    @needs(2)
    @needs_serialization
    def test_server_rejects_foreign_mesh_artifact_but_still_serves(
            self, tmp_path):
        """Full warm-path: artifact warmed under a mesh, hydrated by a
        server replaying WITHOUT one. The sidecar is rejected (loud in
        metrics, not silently wrong), and the tenant still serves correct
        results through the lazy path."""
        n_dev = _largest_mesh()
        reg = TaskFnRegistry()
        reg.register("mesh_mm")(_mm)
        tdg = TDG(region="warm_mesh")
        tdg.add_task(_mm, ins=["x"], outs=["y"], name="t")
        buffers = {"x": _grid_inputs(1, seed=61)["x0"]}
        path = str(tmp_path / "warm.json")
        warmup_and_save(tdg, buffers, path, reg,
                        mesh=make_replay_mesh(n_dev))

        # a consumer replaying under the SAME mesh hydrates fine
        _, aot_ok = load_warm(path, reg, mesh=f"data={n_dev}")
        assert aot_ok is not None

        srv = RegionServer(max_batch=1, max_wait_ms=1.0, mesh=None)
        try:
            srv.register_tenant("wm", warm_path=path, fn_registry=reg)
            assert srv.metrics.snapshot()["aot_hydrate_failures"] == 1
            out = srv.submit("wm", buffers).result(timeout=60)
            _assert_tree_equal(out, lower_tdg(tdg, mesh=None)(buffers))
        finally:
            srv.close()
