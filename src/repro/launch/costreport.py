"""Per-structure cost report: print every adaptive decision with its numbers.

``launch/dryrun.py`` audits model-scale lowering (memory, collectives,
roofline); this is its sibling for the *grain* decisions of
``core/costmodel.py``. For a TDG it lowers nothing and runs nothing heavy —
it probes each fused wave class's payload exactly like trace-time adaptive
fusion does and prints, per class, the measured flops / bytes accessed /
arithmetic intensity and the batcher they selected (vmap | lax.map |
unrolled), plus the policy thresholds in force. For a serving occupancy
stream it shows the histogram, the boundaries the bucket tuner would fit,
and the pad-lane bill under pow-2 vs fitted ladders. The point is that the
adaptive path is auditable: every decision traces back to a number printed
here, never to "the model felt like it".

Run:  PYTHONPATH=src python -m repro.launch.costreport [--json OUT]

The built-in demo covers all three batcher outcomes (a compute-bound
matmul class, a memory-bound stencil class, a below-break-even scalar
class) and a skewed occupancy stream whose fitted boundaries beat pow-2.

Library use::

    from repro.launch.costreport import structure_report, bucket_report
    rep = structure_report(tdg, buffers)        # per-class decisions
    buckets = bucket_report(occupancies, max_batch=16)
"""
from __future__ import annotations

import argparse
import collections
import json
from typing import Any, Iterable, Mapping, Sequence

from ..core import costmodel as _costmodel
from ..core import fuse as _fuse
from ..core.tdg import TDG


def structure_report(tdg: TDG, buffers: Mapping[str, Any],
                     min_class_size: int = 2,
                     batcher: str = "auto") -> dict:
    """Per-wave-class batcher decisions for ``tdg`` with measured numbers.

    ``buffers`` holds arrays or ``ShapeDtypeStruct`` trees for the region's
    input slots (no data is touched — shapes propagate by abstract
    evaluation, payload costs by probe compiles). The decisions are exactly
    what ``batcher="auto"`` replay will apply for these shapes: both run
    ``fuse._decide_class`` over the same cost-model cache.
    """
    model = _costmodel.default_model()
    plan = _fuse.plan(tdg, buffers=buffers, min_class_size=min_class_size,
                      batcher=batcher)
    summary = plan.summary()
    return {
        "region": tdg.region,
        "adaptive": _costmodel.adaptive_enabled(),
        "policy": {
            "plan_key": _costmodel.plan_key(batcher),
            "ridge_flops_per_byte": model.ridge,
            "map_member_bytes_max": model.map_member_bytes,
            "map_total_bytes_min": model.map_total_bytes,
            "unroll_flops_breakeven": model.unroll_flops,
            "split_call_bytes_breakeven": model.split_call_bytes,
        },
        "tasks": summary["tasks"],
        "waves": summary["waves"],
        "batchers": summary["batchers"],
        "decisions": summary["decisions"],
    }


def bucket_report(occupancies: Iterable[int], max_batch: int,
                  max_buckets: int = 8) -> dict:
    """What the bucket tuner fits for an occupancy stream, with the bill.

    Returns the histogram (the numbers that drive the fit), the pow-2
    ladder, the fitted boundaries, and total pad lanes under each — the
    operator-facing answer to "why did the server retune".
    """
    hist = collections.Counter(int(n) for n in occupancies if int(n) >= 2)
    pow2 = _costmodel.pow2_boundaries(max_batch)
    fitted = _costmodel.fit_boundaries(hist, max_buckets) or pow2

    def pad_bill(bounds: Sequence[int]) -> int:
        total = 0
        for occ, cnt in hist.items():
            b = next((x for x in sorted(bounds) if x >= occ), None)
            if b is None:
                b = bounds and max(bounds) or occ
                while b < occ:
                    b *= 2
            total += cnt * (b - occ)
        return total

    return {
        "observations": sum(hist.values()),
        "histogram": {str(k): v for k, v in sorted(hist.items())},
        "pow2_boundaries": pow2,
        "fitted_boundaries": fitted,
        "pad_lanes_pow2": pad_bill(pow2),
        "pad_lanes_fitted": pad_bill(fitted),
    }


# ------------------------------------------------------------------ printing

def print_structure_report(rep: dict) -> None:
    pol = rep["policy"]
    print(f"== {rep['region']}: per-class batcher decisions "
          f"(adaptive={'on' if rep['adaptive'] else 'OFF'}, "
          f"plan={pol['plan_key']})")
    print(f"   policy: intensity ridge {pol['ridge_flops_per_byte']:g} "
          f"flops/B | map member<= {pol['map_member_bytes_max']}B, "
          f"batch>= {pol['map_total_bytes_min']}B | unroll< "
          f"{pol['unroll_flops_breakeven']:g} flops | cut saves>= "
          f"{pol['split_call_bytes_breakeven']}B a call")
    for d in rep["decisions"]:
        flops = "?" if d["flops"] is None else f"{d['flops']:g}"
        nbytes = "?" if d["bytes"] is None else f"{d['bytes']:g}"
        inten = "?" if d["intensity"] is None else f"{d['intensity']:g}"
        print(f"   wave {d['wave']} x{d['size']:<3d} -> {d['batcher']:<8s} "
              f"flops={flops:<10s} bytes={nbytes:<10s} int={inten:<8s} "
              f"({d['reason']})")


def print_bucket_report(rep: dict) -> None:
    print(f"== occupancy buckets over {rep['observations']} batched steps")
    print(f"   histogram: {rep['histogram']}")
    print(f"   pow-2 ladder  {rep['pow2_boundaries']} -> "
          f"{rep['pad_lanes_pow2']} pad lanes")
    print(f"   fitted ladder {rep['fitted_boundaries']} -> "
          f"{rep['pad_lanes_fitted']} pad lanes")


# ---------------------------------------------------------------- demo / CLI

def _demo_tdgs() -> list[tuple[TDG, dict]]:
    """Three structures spanning all three batcher outcomes."""
    import jax.numpy as jnp
    import numpy as np

    def mm(a, w):
        return a @ w

    def relax(x):
        return 0.25 * (jnp.roll(x, 1, 0) + jnp.roll(x, -1, 0)
                       + jnp.roll(x, 1, 1) + jnp.roll(x, -1, 1))

    def nudge(x):
        return x + 0.5

    f32 = jnp.float32
    import jax

    mm_tdg = TDG(region="demo_compute_bound")
    for i in range(8):
        mm_tdg.add_task(mm, ins=[f"x{i}", "w"], outs=[f"y{i}"])
    mm_bufs = {f"x{i}": jax.ShapeDtypeStruct((64, 64), f32) for i in range(8)}
    mm_bufs["w"] = jax.ShapeDtypeStruct((64, 64), f32)

    st_tdg = TDG(region="demo_memory_bound")
    for i in range(8):
        st_tdg.add_task(relax, ins=[f"h{i}"], outs=[f"g{i}"])
    st_bufs = {f"h{i}": jax.ShapeDtypeStruct((64, 64), f32)
               for i in range(8)}

    tiny_tdg = TDG(region="demo_below_breakeven")
    for i in range(8):
        tiny_tdg.add_task(nudge, ins=[f"s{i}"], outs=[f"t{i}"])
    tiny_bufs = {f"s{i}": jax.ShapeDtypeStruct((2,), f32) for i in range(8)}

    return [(mm_tdg, mm_bufs), (st_tdg, st_bufs), (tiny_tdg, tiny_bufs)]


def main(argv: Sequence[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", default=None, metavar="OUT",
                    help="also write the full report as JSON")
    args = ap.parse_args(argv)

    doc: dict = {"structures": [], "buckets": None}
    for tdg, bufs in _demo_tdgs():
        rep = structure_report(tdg, bufs)
        doc["structures"].append(rep)
        print_structure_report(rep)

    # A skewed occupancy stream (stragglers pin most steps at 5 or 12):
    # pow-2 rounds them to 8 and 16; the fitted ladder lands on the modes.
    occupancies = [5] * 40 + [12] * 30 + [3] * 10 + [16] * 5
    rep = bucket_report(occupancies, max_batch=16)
    doc["buckets"] = rep
    print_bucket_report(rep)

    if args.json:
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"wrote {args.json}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
