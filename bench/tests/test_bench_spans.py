"""The readers of the program's spans (``bench/lib/spans.py``), on hand-built
rings; and the seven span metrics in a traced run of the small cell."""
from __future__ import annotations

import itertools
from typing import NamedTuple

import pytest

from bench.lib import harness, spans
from bench.tests import small

SPAN_METRICS = ("replay_key_ms", "replay_dispatch_ms", "replay_wait_ms",
                "replay_outside_ms", "record_s", "warmup_trace_s",
                "warmup_compile_s")
MS = 1_000_000


class SpanRecord(NamedTuple):
    """The fields of ``repro.core.spans.SpanRecord`` that the readers use."""

    name: str
    id: int
    parent: int | None
    root: int
    t0_ns: int
    t1_ns: int
    attrs: dict


class Ring:
    """Builds span records the way the program nests them (times in ns)."""

    def __init__(self):
        self.records: list[SpanRecord] = []
        self._ids = itertools.count(1)

    def add(self, name, t0, t1, parent=None, root=None):
        rid = next(self._ids)
        rec = SpanRecord(name, rid, parent, root or rid, t0, t1, {})
        self.records.append(rec)
        return rec

    def replay(self, t0, key, dispatch, wait, lower=0, self_ns=0, under=None):
        """One replay call; children back to back, ``self_ns`` after them."""
        root_id = next(self._ids)
        parent = under.id if under else None
        root = under.root if under else root_id
        t = t0
        for name, d in (("key", key), ("lower", lower),
                        ("dispatch", dispatch), ("wait", wait)):
            if d:
                rid = next(self._ids)
                self.records.append(SpanRecord(
                    f"taskgraph.replay.{name}", rid, root_id, root, t, t + d,
                    {}))
                t += d
        end = t + self_ns
        self.records.append(SpanRecord("taskgraph.replay", root_id, parent,
                                       root, t0, end, {}))
        return end


def set_up_then_window(window_replays=3):
    """A record, a warmup, two set-up replays (one cold), then the window."""
    ring = Ring()
    ring.add("taskgraph.record", 0, 5 * MS)
    w = ring.add("taskgraph.warmup", 6 * MS, 20 * MS)
    ring.add("taskgraph.warmup.trace", 6 * MS, 9 * MS, w.id, w.id)
    ring.add("taskgraph.warmup.compile", 9 * MS, 19 * MS, w.id, w.id)
    t = ring.replay(30 * MS, key=9 * MS, lower=50 * MS, dispatch=9 * MS,
                    wait=9 * MS)
    t = ring.replay(t + MS, key=9 * MS, dispatch=9 * MS, wait=9 * MS)
    for i in range(window_replays):
        t = ring.replay(t + 2 * MS, key=(1 + i) * MS, dispatch=2 * MS,
                        wait=3 * MS, self_ns=MS // 2)
    return ring.records


def test_window_replays_are_the_last_roots():
    records = set_up_then_window(3)
    win = spans.window_replays(records, 3)
    assert len(win) == 3
    assert all(r.name == "taskgraph.replay" for r, _ in win)
    assert not [c for _, kids in win for c in kids
                if c.name == "taskgraph.replay.lower"]
    assert spans.child_ms(records, 3, "taskgraph.replay.key") == \
        pytest.approx(2.0)                       # (1 + 2 + 3) / 3
    assert spans.child_ms(records, 3, "taskgraph.replay.dispatch") == \
        pytest.approx(2.0)
    assert spans.child_ms(records, 3, "taskgraph.replay.wait") == \
        pytest.approx(3.0)
    assert spans.child_ms(records, 3, "taskgraph.replay.lower") == 0.0
    # the cold set-up replay is inside the last five
    assert spans.child_ms(records, 5, "taskgraph.replay.lower") == \
        pytest.approx(10.0)


def test_nested_replay_spans_are_not_roots():
    ring = Ring()
    outer = ring.add("caller", 0, 100 * MS)
    ring.replay(MS, key=MS, dispatch=MS, wait=MS, under=outer)
    ring.replay(50 * MS, key=MS, dispatch=MS, wait=MS)
    assert len(spans.window_replays(ring.records, 1)) == 1
    assert spans.window_replays(ring.records, 2) is None


def test_too_few_roots_or_no_spans_read_none():
    records = set_up_then_window(3)
    assert spans.window_replays(records, 6) is None
    for read in (lambda r, n: spans.child_ms(r, n, "taskgraph.replay.key"),
                 spans.replay_ms, spans.self_ms,
                 lambda r, n: spans.outside_ms(r, n, 1.0)):
        assert read(records, 6) is None
        assert read(None, 3) is None            # a program without spans
        assert read(records, 0) is None
        assert read(records, None) is None
    assert spans.last_s(None, "taskgraph.record") is None
    assert spans.last_s(records, "taskgraph.absent") is None


def test_outside_is_window_per_replay_less_the_mean_replay():
    records = set_up_then_window(3)
    # window replays last 6.5, 7.5 and 8.5 ms: mean 7.5
    assert spans.replay_ms(records, 3) == pytest.approx(7.5)
    assert spans.outside_ms(records, 3, window_s=0.030) == \
        pytest.approx(30.0 / 3 - 7.5)


def test_self_time_is_the_root_less_its_children():
    records = set_up_then_window(3)
    assert spans.self_ms(records, 3) == pytest.approx(0.5)
    key, dispatch, wait = (spans.child_ms(records, 3, f"taskgraph.replay.{n}")
                           for n in ("key", "dispatch", "wait"))
    assert key + dispatch + wait + spans.self_ms(records, 3) == \
        pytest.approx(spans.replay_ms(records, 3))


def test_set_up_metrics_read_the_last_span_of_the_name():
    records = set_up_then_window(3)
    ring = Ring()
    ring.add("taskgraph.record", 0, 2 * MS)
    records = ring.records + records
    assert spans.last_s(records, "taskgraph.record") == pytest.approx(5e-3)
    assert spans.last_s(records, "taskgraph.warmup.trace") == \
        pytest.approx(3e-3)
    assert spans.last_s(records, "taskgraph.warmup.compile") == \
        pytest.approx(10e-3)


def test_the_seven_metrics_are_entries_with_readers():
    spec = harness.load_spec()
    entries = {m["name"]: m for m in spec["per_layer"]}
    for name in SPAN_METRICS:
        m = entries[name]
        assert m["source"] == "program_span"
        assert m["workloads"] == [small.CHOL]
        assert m["moves"] == ("setup_s" if name.endswith("_s")
                              else "region_ms")
        assert harness.load_plugin("metrics", name).read


def test_readers_give_none_without_the_programs_spans(monkeypatch):
    monkeypatch.setattr(spans, "ring", lambda: None)
    ctx = harness.MetricContext(cell=small.CHOL, config={}, mix={},
                                counters={"replays": 3}, window_s=1.0,
                                trace=None, peaks={})
    for name in SPAN_METRICS:
        assert harness.load_plugin("metrics", name).read(ctx) is None


def test_a_traced_small_run_prints_the_seven_metrics():
    cfg, mix = small.small_cholesky()
    res, _ = small.run_small(small.CHOL, cfg, mix, seed=4294967301,
                             seconds=1.0, trace=1)
    assert res["correct"]
    for name in SPAN_METRICS:
        assert res["metrics"][name]["value"] >= 0, name
        assert res["metrics"][name]["unit"] == ("s" if name.endswith("_s")
                                                else "ms")
