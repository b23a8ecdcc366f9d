"""Finds a cell's files by name, owns the measured window, prints the result.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name that ``BENCHMARK.json``
gives it:

* ``bench/configs/<config>.json`` (the path is the config entry's ``file``);
* ``bench/traffic/<traffic>.json``, whose ``kind`` names the generator
  ``bench/generators/<kind>.py``;
* the config's ``kind`` names the driver ``bench/drivers/<kind>.py``;
* each per-layer metric ``<name>`` has its reader ``bench/metrics/<name>.py``.

A later cell, mix or metric is therefore new files plus new entries in
``BENCHMARK.json``; no file here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import pathlib
import sys
import time
from typing import Any, Callable

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
SPEC_FILE = ROOT / "BENCHMARK.json"

#: The JAX monitoring event that fires once per backend compile, whether the
#: executable is built or loaded from the persistent cache.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ------------------------------------------------------------------ lookup

def load_spec(path: pathlib.Path = SPEC_FILE) -> dict:
    return json.loads(path.read_text())


def find_cell(spec: dict, name: str) -> tuple[dict, dict]:
    """The workload entry called ``name`` and its configuration entry."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise LookupError(f"no workload {name!r} in BENCHMARK.json; known: "
                          f"{sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    if cell["config"] not in configs:
        raise LookupError(f"workload {name!r} names config "
                          f"{cell['config']!r}, which BENCHMARK.json lacks")
    return cell, configs[cell["config"]]


def load_config(entry: dict, root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / entry["file"]).read_text())


def load_mix(traffic: str, bench: pathlib.Path = BENCH) -> dict:
    path = bench / "traffic" / f"{traffic}.json"
    if not path.is_file():
        raise LookupError(f"no traffic mix file {path.relative_to(bench.parent)}")
    return json.loads(path.read_text())


def load_plugin(group: str, name: str, bench: pathlib.Path = BENCH):
    """Import ``bench/<group>/<name>.py`` (names may hold dots)."""
    path = bench / group / f"{name}.py"
    if not path.is_file():
        raise LookupError(f"no {group} module {path.relative_to(bench.parent)}")
    mod_name = f"bench_{group}_" + "".join(
        c if c.isalnum() else "_" for c in name)
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = module
    spec.loader.exec_module(module)
    return module


def metrics_of(spec: dict, cell: str, section: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` entries that cell ``cell`` reports."""
    return [m for m in spec[section]
            if "workloads" not in m or cell in m["workloads"]]


# ------------------------------------------------------------------ window

class Window:
    """The measured window: host clock, compile count and optional trace.

    ``with Window(...) as win:`` starts the clock (and the profiler, with a
    ``bench.window`` span on the host); ``win.mark_end()`` closes the
    window's clock, span and compile count. Leaving the block marks the end
    if the driver did not, and stops the profiler, so a driver may mark the
    end first and finish in-flight work before the block closes.
    """

    def __init__(self, trace_dir: str | None = None):
        self.trace_dir = trace_dir
        self.t0 = self.t1 = None
        self.compiles = 0
        self._counting = False
        self._span = None

    def _on_event(self, name: str, *_a, **_k) -> None:
        if self._counting and name == COMPILE_EVENT:
            self.compiles += 1

    def __enter__(self) -> "Window":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        if self.trace_dir is not None:
            jax.profiler.start_trace(self.trace_dir)
            self._span = jax.profiler.TraceAnnotation("bench.window")
            self._span.__enter__()
        self._counting = True
        self.t0 = time.perf_counter()
        return self

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    def mark_end(self) -> None:
        if self.t1 is not None:
            return
        self.t1 = time.perf_counter()
        self._counting = False
        if self._span is not None:
            self._span.__exit__(None, None, None)
            self._span = None

    def __exit__(self, *exc) -> None:
        import jax

        self.mark_end()
        if self.trace_dir is not None:
            jax.profiler.stop_trace()
        jax.monitoring.unregister_event_duration_listener(self._on_event)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


# ------------------------------------------------------------------ results

@dataclasses.dataclass
class Check:
    """One number compared with its limit; it passes when ``value <= limit``."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class RunResult:
    """What a driver hands back to the harness."""

    attempted: int
    failed: int
    end_to_end: dict[str, float]
    checks: list[Check]
    counters: dict[str, Any]
    window: Window
    memory_peak_bytes: int


@dataclasses.dataclass
class RunContext:
    """What a driver gets: the cell's data, the seed and the window."""

    cell: str
    config: dict
    mix: dict
    seed: int
    seconds: float
    generator: Any
    trace_dir: str | None = None
    log: Callable[[str], None] = log

    def window(self) -> Window:
        return Window(self.trace_dir)


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest device, as the backend reports it."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


@dataclasses.dataclass
class MetricContext:
    """What a per-layer metric's reader sees."""

    cell: str
    config: dict
    mix: dict
    counters: dict[str, Any]
    window_s: float
    trace: Any            # trace.TraceSummary, or None without a trace
    peaks: dict


def result_line(correct: bool, attempted: int, failed: int,
                metrics: dict[str, tuple[float, str]], device: dict,
                checks: list[Check], breakdown: dict | None = None) -> str:
    def num(v: float) -> float | None:     # JSON has no inf or NaN
        return v if math.isfinite(v) else None

    out: dict[str, Any] = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {k: {"value": num(v), "unit": u}
                    for k, (v, u) in metrics.items()},
        "device": device,
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": num(c.value), "limit": c.limit}
                     for c in checks}
    return json.dumps(out)
