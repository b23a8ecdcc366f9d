"""The reduction from trace events to busy time, kernel time and idle gaps."""
from __future__ import annotations

import json

import pytest

from bench.lib import trace
from bench.tests.small import ROOT

DEV, OPS, MODS = "/device:TPU:0", trace.OPS_LINE, trace.MODULES_LINE


def ev(line, name, start, dur, plane=DEV):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": start, "dur_ns": dur}


def hand_trace():
    """A 1000 ns window: ops busy over [100, 400) and [600, 700)."""
    host = "/host:CPU"
    return [
        ev("python", "bench.window", 0, 1000, host),
        ev("python", "bench.prefill", 0, 150, host),
        ev("python", "bench.serve_step", 350, 650, host),
        ev(OPS, "fusion.1", 100, 200),
        ev(OPS, "rmsnorm", 250, 150),          # overlaps fusion.1
        ev(OPS, "fusion.1", 600, 100),
        ev(OPS, "before", -50, 60),            # 10 ns inside the window
        ev(MODS, "jit_prefill(3)", 100, 300),
        ev(MODS, "jit_batched(9)", 600, 100),
    ]


def test_busy_idle_and_kernel_time_by_name():
    s = trace.reduce_events(hand_trace())
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx((10 + 300 + 100) * 1e-9)
    assert trace.idle_percent(s) == pytest.approx(59.0)
    assert s.seconds_matching("rmsnorm") == (pytest.approx(150e-9), 1)
    assert s.seconds_matching("fusion") == (pytest.approx(300e-9), 2)
    assert s.seconds_matching("prefill", modules=True) == (
        pytest.approx(300e-9), 1)


def test_idle_gaps_go_to_the_host_span_that_covers_them():
    s = trace.reduce_events(hand_trace())
    # gaps: [10,100) under prefill, [400,600) and [700,1000) under serve_step
    assert s.idle_by_span == {"bench.prefill": pytest.approx(90e-9),
                              "bench.serve_step": pytest.approx(500e-9)}
    b = s.breakdown()
    assert b["idle_gaps"][0][0] == "bench.serve_step"
    assert b["device_ops"][0][0] == "fusion.1"


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce_events([e for e in hand_trace()
                             if e["name"] != "bench.window"])


def test_recorded_replay_trace_against_a_brute_force_union():
    """0.5 ms of a replay window recorded on a TPU v5e (device ops of the
    fused Cholesky replay, its host span), reduced two ways."""
    import numpy as np

    events = json.loads(
        (ROOT / "bench/tests/data/replay-window.events.json").read_text())
    s = trace.reduce_events(events)
    w = next(e for e in events if e["name"] == trace.WINDOW_SPAN)
    lo, hi = int(w["start_ns"]), int(w["start_ns"] + w["dur_ns"])
    busy = np.zeros(hi - lo, bool)
    for e in events:
        if e["line"] == OPS:
            a = max(int(np.ceil(e["start_ns"])), lo)
            b = min(int(np.ceil(e["start_ns"] + e["dur_ns"])), hi)
            busy[max(a - lo, 0):max(b - lo, 0)] = True
    assert s.devices == 1
    assert s.busy_s == pytest.approx(busy.sum() * 1e-9, rel=1e-2)
    assert 0 < s.busy_s <= s.window_s
    assert sum(s.idle_by_span.values()) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)
    assert set(s.idle_by_span) <= {"bench.replay", trace.NO_SPAN}
    assert all(not n.startswith("%") or " = " not in n for n in s.op_seconds)
    b = s.breakdown()
    assert 0 < len(b["device_ops"]) <= 10
    assert b["device_ops"] == sorted(b["device_ops"], key=lambda x: -x[1])
