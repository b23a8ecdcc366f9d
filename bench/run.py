#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are looked up by name from
``BENCHMARK.json`` (see ``bench/lib/harness.py``). Set-up makes the inputs
from the seed and warms every program the window runs; the window then
measures for ``--seconds``. With ``--trace 0`` the result carries the
cell's end-to-end metrics; with ``--trace 1`` the profiler records the
window and the result carries its per-layer metrics and a breakdown.

Without a TPU, or with fewer chips than the cell asks for, it exits non-zero
and prints no result. The last line of standard output is one JSON object;
the numbers compared for ``correct`` are the last lines of standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import harness  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def find_chips(chips: int) -> dict:
    """The device this run reports; refuses anything but enough TPU chips."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"bench: no TPU (JAX platform is {platform!r}); "
                         f"the benchmark never runs on the CPU")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def enable_compile_cache() -> str:
    """JAX's persistent cache at one fixed path inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` points), every program cached."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def execute(spec: dict, args, device: dict, peaks: dict,
            config: dict | None = None, mix: dict | None = None
            ) -> tuple[str, list]:
    """Everything after the look for a chip: set-up, window, check, metrics.

    Returns the result line and the checks. Tests call this on the CPU with
    a small ``config`` and ``mix`` in place of the cell's files, to see
    faults planted in the program come out as not correct.
    """
    cell, cfg_entry = harness.find_cell(spec, args.workload)
    config = config or harness.load_config(cfg_entry)
    mix = mix or harness.load_mix(cell["traffic"])
    driver = harness.load_plugin("drivers", config["kind"])
    generator = harness.load_plugin("generators", mix["kind"])
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    try:
        ctx = harness.RunContext(cell=cell["name"], config=config, mix=mix,
                                 seed=args.seed, seconds=args.seconds,
                                 generator=generator, trace_dir=trace_dir)
        res = driver.run(ctx)
        setup_s = res.window.t0 - T_START
        print(f"compiles in window: {res.window.compiles}", flush=True)
        summary = None
        if trace_dir is not None:
            from bench.lib import trace

            summary = trace.reduce_events(
                trace.load_events(trace.find_xplane(trace_dir)))
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)

    metrics: dict[str, tuple[float, str]] = {}
    breakdown = None
    if not args.trace:
        values = dict(res.end_to_end, setup_s=setup_s)
        for m in harness.metrics_of(spec, cell["name"], "end_to_end"):
            metrics[m["name"]] = (values[m["name"]], m["unit"])
    else:
        mctx = harness.MetricContext(
            cell=cell["name"], config=config, mix=mix, counters=res.counters,
            window_s=res.window.seconds, trace=summary, peaks=peaks)
        for m in harness.metrics_of(spec, cell["name"], "per_layer"):
            value = harness.load_plugin("metrics", m["name"]).read(mctx)
            if value is not None:
                metrics[m["name"]] = (value, m["unit"])
        device = dict(device, busy_s=summary.busy_s,
                      window_s=summary.window_s)
        breakdown = summary.breakdown()
    device = dict(device, memory_peak_bytes=res.memory_peak_bytes)
    print(f"counters: {res.counters}", flush=True)
    correct = all(c.ok for c in res.checks)
    line = harness.result_line(correct, res.attempted, res.failed, metrics,
                               device, res.checks, breakdown)
    return line, res.checks


def main(argv=None) -> int:
    args = parse(argv)
    spec = harness.load_spec()
    cell, _ = harness.find_cell(spec, args.workload)
    device = find_chips(cell["chips"])
    from bench.lib.peaks import peaks_for

    peaks = peaks_for(device["kind"])
    harness.log(f"compile cache: {enable_compile_cache()}")
    line, checks = execute(spec, args, device, peaks)
    print(line, flush=True)
    for c in checks:
        harness.log(f"check {c.name} {c.value!r} limit {c.limit!r}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
