"""Record-and-replay region semantics (paper §4.2/4.3)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import TaskGraphRegion, registry, taskgraph


def _mk_region(nowait=False):
    @taskgraph(nowait=nowait)
    def region(g, x, a):
        g.task(lambda x, a: x * a, ins=["x", "a"], outs=["y"], name="scale")
        g.task(lambda y: y + 1.0, ins=["y"], outs=["z"], name="shift")
        g.task(lambda y, z: (y * z).sum(), ins=["y", "z"], outs=["w"], name="dot")
    return region


def test_first_call_records_then_replays():
    region = _mk_region()
    x = jnp.arange(4.0)
    o1 = region(x=x, a=jnp.float32(3.0))
    assert region.records == 1 and region.replays == 0
    o2 = region(x=x, a=jnp.float32(3.0))
    assert region.replays == 1
    for k in o1:
        np.testing.assert_allclose(o1[k], o2[k], rtol=1e-6)


def test_replay_new_data_changes_result():
    region = _mk_region()
    region(x=jnp.arange(4.0), a=jnp.float32(1.0))
    o = region(x=jnp.arange(4.0), a=jnp.float32(2.0))  # fill_data path
    np.testing.assert_allclose(o["y"], 2.0 * jnp.arange(4.0))


def test_replay_cache_per_signature():
    region = _mk_region()
    region(x=jnp.arange(4.0), a=jnp.float32(1.0))
    region(x=jnp.arange(4.0), a=jnp.float32(1.0))
    region(x=jnp.arange(8.0), a=jnp.float32(1.0))   # new shape -> new exec
    assert len(region._replay_cache) == 2


def test_replay_cache_keyed_by_kernel_mode():
    """Flipping the global kernel mode between replays must re-lower, not
    serve a stale-substrate executable (regression: cache was sig-only)."""
    from repro.kernels import registry as kreg

    region = _mk_region()
    region(x=jnp.arange(4.0), a=jnp.float32(1.0))      # record
    with kreg.kernel_mode_scope("ref"):
        region(x=jnp.arange(4.0), a=jnp.float32(1.0))
    with kreg.kernel_mode_scope("interpret"):
        region(x=jnp.arange(4.0), a=jnp.float32(1.0))
    assert len(region._replay_cache) == 2
    modes = {key[1] for key in region._replay_cache}
    assert modes == {"ref", "interpret"}


def test_static_build_matches_recorded_shape():
    rec = _mk_region()
    rec(x=jnp.arange(4.0), a=jnp.float32(1.0))

    @taskgraph(name="static_twin")
    def twin(g, x, a):
        g.task(lambda x, a: x * a, ins=["x", "a"], outs=["y"])
        g.task(lambda y: y + 1.0, ins=["y"], outs=["z"])
        g.task(lambda y, z: (y * z).sum(), ins=["y", "z"], outs=["w"])

    twin.build_static(x=jax.ShapeDtypeStruct((4,), jnp.float32),
                      a=jax.ShapeDtypeStruct((), jnp.float32))
    assert twin.static
    assert twin.tdg.num_tasks == rec.tdg.num_tasks
    assert twin.tdg.num_edges == rec.tdg.num_edges
    o = twin(x=jnp.arange(4.0), a=jnp.float32(1.0))  # replay w/o recording
    assert twin.records == 0 and twin.replays == 1
    np.testing.assert_allclose(o["w"],
                               (jnp.arange(4.0) * (jnp.arange(4.0) + 1)).sum())


def test_source_location_registry():
    region = _mk_region()
    assert region.source_location in registry()
    # same source location twice -> non-conforming (paper §4.1 rule 3)
    with pytest.raises(ValueError):
        TaskGraphRegion(region.build_fn, name=region.name)


def test_non_recurrent_runs_without_tdg():
    @taskgraph(recurrent=False)
    def once(g, x):
        g.task(lambda x: x + 1, ins=["x"], outs=["y"])
    o = once(x=jnp.zeros(()))
    assert once.tdg is None            # Algorithm 4.1 line 23 fallback
    np.testing.assert_allclose(o["y"], 1.0)


def test_outputs_restriction():
    @taskgraph(outputs=("z",))
    def region(g, x):
        g.task(lambda x: x * 2, ins=["x"], outs=["y"])
        g.task(lambda y: y + 1, ins=["y"], outs=["z"])
    o = region(x=jnp.ones(()))
    assert set(o) == {"z"}
    o = region(x=jnp.ones(()))
    assert set(o) == {"z"}


def test_schedule_summary():
    region = _mk_region()
    region(x=jnp.arange(4.0), a=jnp.float32(1.0))
    s = region.schedule_summary()
    assert s["tasks"] == 3 and s["waves"] == 3 and s["roots"] == 1
    assert s["dep_lookups_at_record"] > 0


def _tiled_cholesky(nb):
    def potrf(a):
        return jnp.linalg.cholesky(a)

    def trsm(l_kk, a):
        return jax.scipy.linalg.solve_triangular(l_kk, a.T, lower=True).T

    def syrk(a, l):
        return a - l @ l.T

    def gemm(a, l1, l2):
        return a - l1 @ l2.T

    @taskgraph(name=f"cholesky_key_{nb}")
    def region(g, **tiles):
        for k in range(nb):
            g.task(potrf, ins=[f"A{k}_{k}"], outs=[f"L{k}_{k}"])
            for i in range(k + 1, nb):
                g.task(trsm, ins=[f"L{k}_{k}", f"A{i}_{k}"], outs=[f"L{i}_{k}"])
            for i in range(k + 1, nb):
                g.task(syrk, ins=[f"A{i}_{i}", f"L{i}_{k}"], outs=[f"A{i}_{i}"])
                for j in range(k + 1, i):
                    g.task(gemm, ins=[f"A{i}_{j}", f"L{i}_{k}", f"L{j}_{k}"],
                           outs=[f"A{i}_{j}"])
    return region


def test_warm_replays_hit_the_cache_from_specs():
    """Warmed up from ShapeDtypeStruct specs, replays on arrays hit the
    replay cache; one tile in another dtype misses once."""
    from repro.core import spans

    nb, bs = 8, 8
    n = nb * bs
    m = np.random.default_rng(0).standard_normal((n, n)).astype(np.float32)
    a = jnp.asarray(m @ m.T + n * np.eye(n, dtype=np.float32))
    tiles = {f"A{i}_{j}": a[i * bs:(i + 1) * bs, j * bs:(j + 1) * bs]
             for i in range(nb) for j in range(i + 1)}
    assert len(tiles) == 36
    region = _tiled_cholesky(nb)
    region.record(**tiles)
    region.warmup(**{k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                     for k, v in tiles.items()})

    def count(name):
        return spans.counters().get(f"taskgraph.replay.{name}", 0)

    hits, misses = count("cache_hit"), count("cache_miss")
    for _ in range(3):
        out = region(**tiles)
    assert count("cache_miss") == misses
    assert count("cache_hit") == hits + 3
    assert len(region._replay_cache) == 1
    l = np.tril(np.block([[out[f"L{i}_{j}"] if j <= i else np.zeros((bs, bs))
                           for j in range(nb)] for i in range(nb)]))
    np.testing.assert_allclose(l @ l.T, np.asarray(a), rtol=1e-4, atol=1e-3)

    region(**{**tiles, "A7_1": tiles["A7_1"].astype(jnp.bfloat16)})
    assert count("cache_miss") == misses + 1
    assert count("cache_hit") == hits + 3
    assert len(region._replay_cache) == 2
