"""From the profiler's trace of the window to device busy time, kernel time
and idle gaps.

``load_events`` flattens an ``.xplane.pb`` into plain event records; every
other function here works on those records, so the reduction is tested on
a small recorded trace (``bench/tests/data``) without a chip.

* Device operations are the events of the ``XLA Ops`` line of each
  ``/device:TPU:<n>`` plane; whole programs are on its ``XLA Modules`` line.
* The window is the host span ``bench.window`` that the harness opens.
* Busy time is the union of a device's operation intervals inside the
  window, averaged over the devices that ran anything.
* An idle gap is a stretch of the window in which no operation runs; it is
  put down to the ``bench.*`` host span (other than the window) that
  overlaps it most, or to ``no bench span``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Iterable

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
NO_SPAN = "no bench span"


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def op_name(text: str) -> str:
    """An op event's name: the HLO instruction's name, without its text."""
    return text.split(" = ", 1)[0]


def load_events(xplane_path: str) -> list[dict]:
    """Device op/module events and ``bench.*`` host spans, as plain dicts."""
    import jax

    data = jax.profiler.ProfileData.from_file(xplane_path)
    events = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not device and not ev.name.startswith("bench."):
                    continue
                events.append({"plane": plane.name, "line": line.name,
                               "name": op_name(ev.name),
                               "start_ns": ev.start_ns,
                               "dur_ns": ev.duration_ns})
    return events


def _union(intervals: Iterable[tuple[float, float]]) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(s: float, e: float, lo: float, hi: float) -> tuple[float, float] | None:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


@dataclasses.dataclass
class TraceSummary:
    """The window's device activity, reduced from the trace's events."""

    window_s: float
    busy_s: float                          # mean over active devices
    op_seconds: dict[str, float]           # device op name -> summed time
    op_calls: dict[str, int]
    module_seconds: dict[str, float]       # program name -> summed time
    module_calls: dict[str, int]
    idle_by_span: dict[str, float]         # host span -> idle device time
    devices: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def seconds_matching(self, pattern: str, modules: bool = False) -> tuple[float, int]:
        """Summed time and call count of ops (or programs) whose name matches."""
        rx = re.compile(pattern)
        secs = self.module_seconds if modules else self.op_seconds
        calls = self.module_calls if modules else self.op_calls
        names = [n for n in secs if rx.search(n)]
        return sum(secs[n] for n in names), sum(calls[n] for n in names)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in gaps]}


def reduce_events(events: list[dict]) -> TraceSummary:
    windows = [e for e in events if e["name"] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    w0 = windows[0]["start_ns"]
    w1 = w0 + windows[0]["dur_ns"]
    spans = [(e["start_ns"], e["start_ns"] + e["dur_ns"], e["name"])
             for e in events
             if e["line"] not in (OPS_LINE, MODULES_LINE)
             and e["name"] != WINDOW_SPAN]

    per_device: dict[str, list[tuple[float, float]]] = {}
    op_s: dict[str, float] = {}
    op_n: dict[str, int] = {}
    mod_s: dict[str, float] = {}
    mod_n: dict[str, int] = {}
    for e in events:
        if e["line"] not in (OPS_LINE, MODULES_LINE):
            continue
        iv = _clip(e["start_ns"], e["start_ns"] + e["dur_ns"], w0, w1)
        if iv is None:
            continue
        secs = (iv[1] - iv[0]) * 1e-9
        if e["line"] == OPS_LINE:
            per_device.setdefault(e["plane"], []).append(iv)
            op_s[e["name"]] = op_s.get(e["name"], 0.0) + secs
            op_n[e["name"]] = op_n.get(e["name"], 0) + 1
        else:
            mod_s[e["name"]] = mod_s.get(e["name"], 0.0) + secs
            mod_n[e["name"]] = mod_n.get(e["name"], 0) + 1

    spans.sort()
    idle: dict[str, float] = {}
    busy_total = 0.0
    for ivs in per_device.values():
        busy = _union(ivs)
        busy_total += sum(e - s for s, e in busy) * 1e-9
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        active: list[tuple[float, float, str]] = []
        nxt = 0
        for s, e in zip(edges[0::2], edges[1::2]):
            if e <= s:
                continue
            while nxt < len(spans) and spans[nxt][0] < e:
                active.append(spans[nxt])
                nxt += 1
            active = [sp for sp in active if sp[1] > s]
            best, best_overlap = NO_SPAN, 0.0
            for hs, he, name in active:
                ov = min(e, he) - max(s, hs)
                if ov > best_overlap:
                    best, best_overlap = name, ov
            idle[best] = idle.get(best, 0.0) + (e - s) * 1e-9 / len(per_device)
    n_dev = len(per_device)
    return TraceSummary(
        window_s=(w1 - w0) * 1e-9,
        busy_s=busy_total / n_dev if n_dev else 0.0,
        op_seconds=op_s, op_calls=op_n, module_seconds=mod_s,
        module_calls=mod_n, idle_by_span=idle, devices=n_dev)


def idle_percent(summary: "TraceSummary | None") -> float | None:
    """The device's idle share of the window, in percent (None: no trace)."""
    if summary is None or summary.devices == 0:
        return None
    return 100.0 * summary.idle_share
