"""Driver for a taskgraph region: record once, compile the fused replay ahead
of time, then replay it back to back on the seeded input.

One replay is in flight at a time and each is waited for before the next,
as a solver that consumes each factor would. The factor that the window's
last replay returned is what the comparison checks.
"""
from __future__ import annotations

import gc

from bench.lib import harness, work
from bench.lib.seeds import jax_key


def run(ctx: harness.RunContext) -> harness.RunResult:
    import jax

    cfg, work_spec = ctx.config, ctx.generator.make(ctx.config, ctx.mix,
                                                      ctx.seed, ctx.seconds)
    n, nb = work_spec["n"], work_spec["nb"]
    region_mod = harness.load_plugin("regions", cfg["region"])

    a, tiles = region_mod.make_input(n, nb, jax_key(ctx.seed))
    region = region_mod.build(nb)
    with jax.default_matmul_precision(cfg["matmul_precision"]):
        region(**tiles)                                 # record
        ctx.log(f"recorded {region.tdg.num_tasks} tasks")
        aot = region.warmup(**tiles)                    # fused AOT compile
        plan = aot.plan.summary()
        plan.pop("decisions")
        ctx.log(f"fusion plan: {plan}")
        out = region(**tiles)                           # one warm replay
        replays = 0
        with ctx.window() as win:
            while replays == 0 or win.elapsed() < ctx.seconds:
                with jax.profiler.TraceAnnotation("bench.replay"):
                    out = region(**tiles)
                replays += 1
            win.mark_end()
    peak = harness.memory_peak_bytes(jax.devices())
    counters = {
        "replays": replays,
        "flops_per_replay": work.cholesky_flops(n),
        "stacked_bytes": work.stacked_bytes(region.tdg, aot.plan, tiles),
        "tasks": region.tdg.num_tasks,
        "waves": aot.plan.num_waves,
    }
    del tiles, region, aot
    gc.collect()

    l_ref = region_mod.reference(a)
    del a
    numbers = region_mod.compare(region_mod.assemble(out, n, nb), l_ref)
    checks = [harness.Check(k, v, cfg["limits"][k]) for k, v in numbers.items()]
    return harness.RunResult(
        attempted=replays, failed=0,
        end_to_end={"region_ms": win.seconds / replays * 1e3},
        checks=checks, counters=counters, window=win,
        memory_peak_bytes=peak)
