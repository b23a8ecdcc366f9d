"""The span and counter facility (``core/spans.py``) and the spans that
record, warmup and replay emit. No test here asserts a wall time."""
import glob
import json
import re
import threading

import jax
import jax.numpy as jnp
import pytest

from repro.core import spans, taskgraph


def _scale(a, b):
    return a * b + 1.0


def _region():
    @taskgraph(batcher="vmap")
    def region(g, a0, a1, b):
        for i in range(2):
            g.task(_scale, ins=[f"a{i}", "b"], outs=[f"c{i}"],
                   name=f"scale{i}")
        g.task(lambda c0, c1: c0 - c1, ins=["c0", "c1"], outs=["d"],
               name="diff")
    return region


def _buffers():
    return dict(a0=jnp.ones(4), a1=jnp.full(4, 2.0), b=jnp.ones(4))


def _since(mark: int) -> list:
    return [r for r in spans.recent() if r.id > mark]


def _mark() -> int:
    with spans.span("test.mark") as sp:
        pass
    return sp.record.id


def _children(records, root) -> list[str]:
    return [r.name for r in records if r.parent == root.id]


def test_nesting_parent_and_root():
    rec = spans.Recorder()
    with spans.span("outer", rec, region="r"):
        with spans.span("mid", rec):
            with spans.span("inner", rec, n=3):
                pass
        with spans.span("sibling", rec):
            pass
    with spans.span("next", rec):
        pass
    by = {r.name: r for r in rec.recent()}
    assert [r.name for r in rec.recent()] == ["inner", "mid", "sibling",
                                             "outer", "next"]
    assert by["outer"].parent is None and by["outer"].root == by["outer"].id
    assert by["mid"].parent == by["outer"].id
    assert by["inner"].parent == by["mid"].id
    assert by["sibling"].parent == by["outer"].id
    assert {by[n].root for n in ("mid", "inner", "sibling")} == {by["outer"].id}
    assert by["next"].root == by["next"].id != by["outer"].id
    assert by["outer"].attrs == {"region": "r"}
    assert by["inner"].attrs == {"n": 3}
    assert by["outer"].t0_ns <= by["mid"].t0_ns <= by["inner"].t0_ns
    assert by["inner"].t1_ns <= by["mid"].t1_ns <= by["outer"].t1_ns


def test_attrs_are_ints_and_strings():
    with pytest.raises(TypeError):
        spans.span("bad", spans.Recorder(), x=jnp.ones(2))
    rec = spans.Recorder()
    with spans.span("s", rec) as sp:
        sp.set(tasks=5)
        with pytest.raises(TypeError):
            sp.set(ratio=0.5)
    assert rec.recent()[0].attrs == {"tasks": 5}


def test_ring_stays_bounded():
    rec = spans.Recorder(capacity=8)
    for i in range(20):
        with spans.span("s", rec, i=i):
            pass
    kept = rec.recent()
    assert len(kept) == 8
    assert [r.attrs["i"] for r in kept] == list(range(12, 20))
    assert spans.CAPACITY >= 65_536


def test_span_whose_body_raises_is_recorded():
    rec = spans.Recorder()
    with pytest.raises(ValueError):
        with spans.span("outer", rec):
            with spans.span("fails", rec):
                raise ValueError("boom")
    with spans.span("after", rec):
        pass
    by = {r.name: r for r in rec.recent()}
    assert by["fails"].error and by["outer"].error
    assert not by["after"].error
    assert by["after"].parent is None       # the stack unwound


def test_counters_recent_and_dump(tmp_path):
    rec = spans.Recorder()
    rec.count("a")
    rec.count("a", 4)
    rec.count("b", 2)
    with spans.span("x", rec, k="v"):
        pass
    with spans.span("y", rec):
        pass
    assert rec.counters() == {"a": 5, "b": 2}
    assert [r.name for r in rec.recent("y")] == ["y"]
    path = tmp_path / "spans.json"
    rec.dump(str(path))
    doc = json.loads(path.read_text())
    assert doc["counters"] == {"a": 5, "b": 2}
    assert [s["name"] for s in doc["spans"]] == ["x", "y"]
    assert doc["spans"][0]["attrs"] == {"k": "v"}
    assert set(doc["spans"][0]) == set(spans.SpanRecord._fields)


def test_module_level_counters_and_dump(tmp_path):
    before = spans.counters().get("test.counter", 0)
    spans.count("test.counter", 3)
    assert spans.counters()["test.counter"] == before + 3
    spans.dump(str(tmp_path / "all.json"))
    doc = json.loads((tmp_path / "all.json").read_text())
    assert doc["counters"]["test.counter"] == before + 3


def test_threads_keep_separate_parent_stacks():
    rec = spans.Recorder()
    inside, release = threading.Barrier(2), threading.Event()

    def work(tag):
        with spans.span(f"root.{tag}", rec):
            inside.wait(timeout=10)          # both roots open at once
            with spans.span(f"child.{tag}", rec):
                release.wait(timeout=10)

    threads = [threading.Thread(target=work, args=(t,)) for t in "ab"]
    for t in threads:
        t.start()
    release.set()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    by = {r.name: r for r in rec.recent()}
    assert len(by) == 4
    for tag in "ab":
        assert by[f"root.{tag}"].parent is None
        assert by[f"child.{tag}"].parent == by[f"root.{tag}"].id
        assert by[f"child.{tag}"].root == by[f"root.{tag}"].id


def test_replay_emits_key_dispatch_wait_and_lower_only_when_cold():
    region = _region()
    bufs = _buffers()
    mark = _mark()
    misses = spans.counters().get("taskgraph.replay.cache_miss", 0)
    region(**bufs)                               # record
    region(**bufs)                               # cold replay: lowers
    cold = _since(mark)
    assert [r.attrs["tasks"] for r in cold
            if r.name == "taskgraph.record"] == [3]
    (root,) = [r for r in cold if r.name == "taskgraph.replay"]
    assert _children(cold, root) == ["taskgraph.replay.key",
                                     "taskgraph.replay.lower",
                                     "taskgraph.replay.dispatch",
                                     "taskgraph.replay.wait"]
    assert root.attrs == {"region": region.name, "args": 3, "outputs": 3}
    assert spans.counters()["taskgraph.replay.cache_miss"] == misses + 1

    mark = _mark()
    region(**bufs)
    region(**bufs)
    warm = _since(mark)
    roots = [r for r in warm if r.name == "taskgraph.replay"]
    assert len(roots) == 2
    for root in roots:
        assert root.parent is None
        assert _children(warm, root) == ["taskgraph.replay.key",
                                         "taskgraph.replay.dispatch",
                                         "taskgraph.replay.wait"]
        assert all(r.root == root.id for r in warm if r.parent == root.id)
    assert spans.counters()["taskgraph.replay.cache_miss"] == misses + 1


def test_warmup_spans_and_no_cache_miss_after_warmup():
    region = _region()
    bufs = _buffers()
    region(**bufs)                               # record
    mark = _mark()
    misses = spans.counters().get("taskgraph.replay.cache_miss", 0)
    aot = region.warmup(**bufs)
    region(**bufs)
    region(**bufs)
    recs = _since(mark)
    (warm,) = [r for r in recs if r.name == "taskgraph.warmup"]
    assert _children(recs, warm) == ["taskgraph.warmup.trace",
                                     "taskgraph.warmup.compile"]
    by = {r.name: r for r in recs}
    assert aot.trace_seconds == by["taskgraph.warmup.trace"].seconds > 0
    assert aot.compile_seconds == by["taskgraph.warmup.compile"].seconds > 0
    assert not [r for r in recs if r.name == "taskgraph.replay.lower"]
    assert spans.counters().get("taskgraph.replay.cache_miss", 0) == misses


def test_costmodel_probe_span_and_counter():
    from repro.core.costmodel import CostModel

    model = CostModel()
    mark = _mark()
    probes = spans.counters().get("taskgraph.costmodel.probes", 0)
    spec = jax.ShapeDtypeStruct((8, 8), jnp.float32)
    model.measure(_scale, [spec, spec])
    model.measure(_scale, [spec, spec])          # cached: no second probe
    recs = [r for r in _since(mark) if r.name == "taskgraph.costmodel.probe"]
    assert [r.attrs for r in recs] == [{"payload": "_scale"}]
    assert spans.counters()["taskgraph.costmodel.probes"] == probes + 1


def test_replay_spans_land_on_the_profilers_host_plane(tmp_path):
    region = _region()
    bufs = _buffers()
    region(**bufs)
    region(**bufs)
    with jax.profiler.trace(str(tmp_path)):
        region(**bufs)
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    events = [(plane.name, ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
              for plane in data.planes for line in plane.lines
              for ev in line.events if ev.name.startswith("taskgraph.")]
    assert all(p.startswith("/host:") for p, *_ in events)
    (root,) = [e for e in events if e[1] == "taskgraph.replay"]
    kids = {e[1]: e for e in events if e[1].startswith("taskgraph.replay.")}
    assert set(kids) == {"taskgraph.replay.key", "taskgraph.replay.dispatch",
                         "taskgraph.replay.wait"}
    for _, _, s, e in kids.values():
        assert root[2] <= s <= e <= root[3]


def test_fused_program_carries_class_and_task_scopes():
    region = _region()
    bufs = _buffers()
    region(**bufs)
    aot = region.warmup(**bufs)
    names = set(re.findall(r'op_name="([^"]*)"', aot.compiled.as_text()))
    assert any("/w0._scale.vmap/" in n for n in names), names
    assert any("/w1.diff/" in n for n in names), names
