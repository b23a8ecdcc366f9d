#!/usr/bin/env python3
"""Readings that set the benchmark's limits and rates, run on the chip.

    python bench/calibrate.py readings --workload <cell> --seeds 1,2,3 --seconds 20
    python bench/calibrate.py sweep --workload <chat cell> --rates 4,6,8 --seconds 20

``readings`` runs the cell's own timed path on each seed in one process and
prints, per seed, the number its comparison reads for the program and for
the precision control that stands in the program's place (for the
Cholesky cell also the program's own replay at ``high`` precision). The
benchmark's runs never run the control.

``sweep`` serves the chat mix at each offered rate, after one set-up, and
prints the tails and the backlog at the window's close: the highest rate
without a growing backlog is the knee that the cell's rate is set below.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.lib import harness  # noqa: E402
from bench.lib.seeds import jax_key  # noqa: E402


def _load(cell: str):
    spec = harness.load_spec()
    w, entry = harness.find_cell(spec, cell)
    config = harness.load_config(entry)
    mix = harness.load_mix(w["traffic"])
    return config, mix, harness.load_plugin("generators", mix["kind"])


def cholesky_readings(config, mix, seeds) -> list[dict]:
    import jax

    region_mod = harness.load_plugin("regions", config["region"])
    n, nb = config["n"], mix["nb"]
    regions = {}
    rows = []
    for seed in seeds:
        a, tiles = region_mod.make_input(n, nb, jax_key(seed))
        row = {"seed": seed}
        for prec in (config["matmul_precision"], "high"):
            with jax.default_matmul_precision(prec):
                if prec not in regions:
                    regions[prec] = region_mod.build(nb)
                    regions[prec](**tiles)              # record
                    regions[prec].warmup(**tiles)
                out = regions[prec](**tiles)
            l = region_mod.assemble(out, n, nb)
            row[f"program_{prec}"] = l
        del tiles, out
        l_ref = region_mod.reference(a)
        for k in [k for k in row if k.startswith("program_")]:
            row[k] = region_mod.compare(row[k], l_ref)["l_rel_err"]
        row["control_bf16x3"] = region_mod.compare(
            region_mod.control_factor(a, nb), l_ref)["l_rel_err"]
        del a, l_ref
        gc.collect()
        print(json.dumps(row), flush=True)
        rows.append(row)
    return rows


def chat_readings(config, mix, generator, seeds, seconds) -> list[dict]:
    drv = harness.load_plugin("drivers", config["kind"])
    orig = drv.reference_gaps
    rows = []

    def with_control(weights, cfg, mix_, samples, control=False):
        out = orig(weights, cfg, mix_, samples, control=True)
        rows[-1].update(out)
        return out

    drv.reference_gaps = with_control
    for seed in seeds:
        rows.append({"seed": seed})
        ctx = harness.RunContext(cell="readings", config=config, mix=mix,
                                 seed=seed, seconds=seconds,
                                 generator=generator)
        res = drv.run(ctx)
        rows[-1].update(res.end_to_end, failed=res.failed,
                        attempted=res.attempted,
                        compiles=res.window.compiles)
        print(json.dumps(rows[-1]), flush=True)
        gc.collect()
    return rows


def chat_sweep(config, mix, generator, rates, seconds, seed: int) -> None:
    import jax

    drv = harness.load_plugin("drivers", config["kind"])
    weights = drv.qwen2.make_weights(
        config, jax_key(seed), dtype=jax.numpy.dtype(config["weights_dtype"]))
    chat = drv.Chat(config, mix, weights)
    chat.warm(harness.log)
    for rate in rates:
        m = dict(mix, rate_rps=rate)
        reqs = generator.make(config, m, seed, seconds)
        records = [drv.Record(req=r, due=0.0) for r in reqs]
        sm = chat.server.metrics
        b0, o0 = sm.batches, sm.occupancy_sum
        with harness.Window() as win:
            loop = drv._serve(chat, records, win, seconds, harness.log)
        e2e, counters = drv._e2e(records, config, win.t0, win.t1)
        late = [r for r in records if not r.times or r.times[0] > win.t1]
        offered = sum(r.out_len for r in reqs) / seconds
        print(json.dumps(dict(
            rate=rate, offered_tokens_per_s=offered, **e2e,
            ttft_p50_ms=counters["ttft_p50_ms"],
            ttft_p95_ms=counters["ttft_p95_ms"],
            itl_p50_ms=counters["itl_p50_ms"],
            first_token_after_close=len(late), requests=len(reqs),
            occupancy=(sm.occupancy_sum - o0) / max(sm.batches - b0, 1),
            compiles=win.compiles, **loop)), flush=True)
    chat.close()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("readings", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("calibrate: readings come from the chip only")
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR")
                      or str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    config, mix, generator = _load(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.mode == "sweep":
        chat_sweep(config, mix, generator,
                   [float(r) for r in args.rates.split(",")], args.seconds,
                   seeds[0])
    elif config["kind"] == "taskgraph":
        cholesky_readings(config, mix, seeds)
    else:
        chat_readings(config, mix, generator, seeds, args.seconds)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
