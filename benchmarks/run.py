"""Benchmark aggregator — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]
                                            [--kernels MODE[,MODE...]]

Sections:
  contention             Fig. 2 / Table 1  (orchestration overhead vs #tasks)
  speedup_grid           Figs. 6/7         (granularity x workers heatmaps)
  amortization           Figs. 8/9         (record-cost amortization)
  granularity_stability  Fig. 10           (stability under fine granularity)
  roofline               (beyond paper)    (dry-run roofline terms)

``--kernels`` sweeps the kernel substrate (see ``repro.kernels.registry``):
each listed mode (``auto``, ``pallas``, ``ref``, ``interpret``) runs the
selected sections under that substrate, so contention/amortization numbers
for registry-dispatched workloads (rmsnorm, attention) are comparable
across substrates from one invocation.

Prints ``name,us_per_call,derived`` CSV rows per section.
"""
from __future__ import annotations

import argparse
import time


_EPILOG = """\
benchmark modules in this package (sections marked * run via this driver):
  contention.py*            orchestration overhead vs #tasks (Fig. 2/Table 1)
  speedup_grid.py*          granularity x workers heatmaps   (Figs. 6/7)
  amortization.py*          record-cost amortization          (Figs. 8/9)
  granularity_stability.py* stability under fine granularity  (Fig. 10)
  roofline.py*              dry-run roofline terms            (beyond paper)
  fusion.py                 wave-fused vs unrolled lowering; standalone:
                            python -m benchmarks.fusion [--smoke]
  serving.py                multi-tenant batched admission vs serial replay;
                            standalone: python -m benchmarks.serving [--smoke]
  cluster.py                distributed frontend: RPC overhead, warm-artifact
                            cold start, worker scaling; standalone:
                            python -m benchmarks.cluster [--smoke]
"""


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        epilog=_EPILOG, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--quick", action="store_true",
                    help="smaller sweeps (CI-sized)")
    ap.add_argument("--only", default=None,
                    help="run a single section by name")
    ap.add_argument("--kernels", default=None,
                    help="comma-separated kernel substrate sweep "
                         "(auto,pallas,ref,interpret); default: the current "
                         "global mode (REPRO_KERNELS or auto)")
    args = ap.parse_args(argv)

    from repro.kernels import registry
    from repro.launch import compile_cache

    compile_cache.enable()

    if args.kernels is None:
        modes = [registry.kernel_mode()]    # respect REPRO_KERNELS
    else:
        modes = [m.strip() for m in args.kernels.split(",") if m.strip()]
    for m in modes:
        registry.validate_mode(m)   # fail fast, before any section runs

    from . import (amortization, contention, granularity_stability, roofline,
                   speedup_grid)

    sections = {
        "contention": lambda: contention.run(
            task_counts=(1, 4, 16, 64) if args.quick
            else (1, 4, 16, 64, 256, 1024)),
        "speedup_grid": lambda: speedup_grid.run(
            workloads=("cholesky", "axpy", "rmsnorm") if args.quick
            else ("cholesky", "heat", "nbody", "axpy", "dotp",
                  "rmsnorm", "attention"),
            grains=(4, 8) if args.quick else (4, 8, 16),
            workers=(1, 4) if args.quick else (1, 4, 8)),
        "amortization": lambda: amortization.run(
            workloads=("cholesky", "axpy") if args.quick
            else ("cholesky", "heat", "axpy", "dotp", "rmsnorm"),
            iter_counts=(4, 16) if args.quick else (4, 64)),
        "granularity_stability": lambda: granularity_stability.run(
            grains=(4, 8) if args.quick else (2, 4, 8, 16, 32)),
        "roofline": roofline.run,
    }
    for mode in modes:
        if len(modes) > 1:
            print(f"\n########## kernels={mode} ##########", flush=True)
        with registry.kernel_mode_scope(mode):
            for name, fn in sections.items():
                if args.only and name != args.only:
                    continue
                print(f"\n===== {name} [kernels={mode}] =====", flush=True)
                t0 = time.time()
                fn()
                print(f"# section {name} done in {time.time()-t0:.1f}s",
                      flush=True)


if __name__ == "__main__":
    main()
