#!/usr/bin/env python3
"""Where fused replay sharded over a mesh parts from single-device replay:
experiments on the gemm classes of the tiled Cholesky.

    python scripts/mesh_bits_probe.py layouts    # no chip needed, ~2 min
    python scripts/mesh_bits_probe.py chip       # one TPU chip
    python scripts/mesh_bits_probe.py mesh       # four TPU chips
    python scripts/mesh_bits_probe.py mesh-cpu   # four CPU devices, ~30 s

All run at ``HIGHEST`` matmul precision. On the chip the tiles are 256x256
f32, the shapes of ``chip_smoke.py --chips 4`` (n=4096, nb=16), whose first
gemm wave is 105 tasks ``a - l1 @ l2.T`` (108 lanes, 27 per device, when
sharded).

``layouts`` compiles that Cholesky replay for a described ``v5e:2x2``, for
one device and for a 4-device mesh, and prints how many batched (rank-3)
dots the compiler laid out row-major and how many otherwise.

``chip``, on one chip:

1. the gemm at 27 lanes, operands and result pinned row-major, then pinned
   with the two minor dimensions swapped: whether the two give the same bits;
2. the gemm at 1, 4, 27 and 105 lanes against the first lanes of 108,
   unpinned and pinned row-major, with the layout of each compiled dot;
3. ``chip_smoke.py``'s phase (a) at the default matmul precision: the
   control for that phase's tolerance.

``mesh``, on four chips:

1. the gemm wave alone as a 105-task TDG, replayed on one device and
   sharded over the four: how many slots agree;
2. gemm classes of 2, 3, 4, 5 and 8 independent tasks (1 or 2 lanes per
   device when sharded), the same comparison;
3. the gemm on arrays already stacked to 108 lanes, on one device and
   split 27 per device: whether the bits agree;
4. ``chip_smoke.py --chips 4`` (the whole replay), its failure printed
   rather than raised.

``mesh-cpu`` runs the same four steps on four CPU devices with 32x32 tiles
(step 4 at n=256, nb=8).
"""
from __future__ import annotations

import argparse
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src"), os.path.join(ROOT, "examples")]

N, NB = 4096, 16
BS = N // NB


def gemm(a, l1, l2):
    return a - l1 @ l2.T


# A dot in compiled TPU HLO: `= f32[27,256,256]{2,1,0:T(8,128)} convolution(`.
_DOT = re.compile(r"= \w+\[([0-9,]+)\]\{([0-9,]+)[:}][^\n]*convolution\("
                  r"[^\n]*op_name=\"[^\"\n]*dot_general\"")


def batched_dot_layouts(hlo_text: str) -> dict[str, int]:
    """Count a compiled TPU module's rank-3 dots by result layout."""
    counts: dict[str, int] = {}
    for dims, minor_to_major in _DOT.findall(hlo_text):
        if dims.count(",") == 2:
            kind = "row-major" if minor_to_major == "2,1,0" else "other"
            counts[kind] = counts.get(kind, 0) + 1
    return counts


def layouts(n: int = N, nb: int = NB) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

    from quickstart import cholesky_region
    from repro.core.lower import aot_compile_tdg
    from repro.launch.mesh import make_mesh

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bs = n // nb
    region = cholesky_region(nb)
    for devices in (1, 4):
        mesh = (make_mesh((4,), ("data",), devices=topo.devices)
                if devices == 4 else None)
        sharding = (NamedSharding(mesh, PartitionSpec()) if mesh
                    else SingleDeviceSharding(topo.devices[0]))
        specs = {f"A{i}{j}": jax.ShapeDtypeStruct((bs, bs), jnp.float32,
                                                  sharding=sharding)
                 for i in range(nb) for j in range(i + 1)}
        with jax.default_matmul_precision("highest"):
            aot = aot_compile_tdg(region.build_static(**specs), specs,
                                  mesh=mesh)
        print(f"devices={devices}: batched dots "
              f"{batched_dot_layouts(aot.compiled.as_text())}", flush=True)


def chip() -> None:
    import jax
    from jax.experimental.layout import Layout, with_layout_constraint

    import chip_smoke
    from repro.launch import compile_cache

    chip_smoke.device_check(1)
    print(f"compile cache: {compile_cache.enable()}", flush=True)

    def program(major_to_minor):
        def run(a, l1, l2):
            if major_to_minor is None:
                return jax.vmap(gemm)(a, l1, l2)
            pin = lambda x: with_layout_constraint(  # noqa: E731
                x, Layout(major_to_minor))
            return pin(jax.vmap(gemm)(pin(a), pin(l1), pin(l2)))
        return jax.jit(run)

    def compiled(major_to_minor, args):
        c = program(major_to_minor).lower(*args).compile()
        return c, batched_dot_layouts(c.as_text())

    a, l1, l2 = _stacked(108)
    with jax.default_matmul_precision("highest"):
        args = (a[:27], l1[:27], l2[:27])
        (row, row_l), (swap, swap_l) = (compiled(m, args)
                                        for m in ((0, 1, 2), (0, 2, 1)))
        x, y = row(*args), swap(*args)
        print(f"27 lanes, pinned row-major {row_l} vs pinned minor-swapped "
              f"{swap_l}: {_same(x, y)}", flush=True)

        for label, m2m in (("unpinned", None), ("pinned", (0, 1, 2))):
            full, full_l = compiled(m2m, (a, l1, l2))
            ref = full(a, l1, l2)
            cells = [f"108:{full_l}"]
            for b in (1, 4, 27, 105):
                part, part_l = compiled(m2m, (a[:b], l1[:b], l2[:b]))
                cells.append(f"{b}:{part_l} "
                             f"{_same(part(a[:b], l1[:b], l2[:b]), ref[:b])}")
            print(f"{label}: " + "; ".join(cells), flush=True)

    try:
        chip_smoke.phase_replay(precision="default")
    except AssertionError as e:
        print(f"control at default precision fails the tolerance: {e}",
              flush=True)


def _class_parity(tdg, mesh, bs: int) -> str:
    """Replay ``tdg`` on one device and sharded over ``mesh``; compare."""
    import jax

    from repro.core import ReplayExecutor

    names = sorted({s for t in tdg.tasks for s in t.ins})
    key = jax.random.PRNGKey(1)
    tiles = {s: jax.random.normal(jax.random.fold_in(key, k), (bs, bs))
             for k, s in enumerate(names)}
    one = ReplayExecutor(tdg, mesh=None).run(dict(tiles))
    four = ReplayExecutor(tdg, mesh=mesh).run(dict(tiles))
    outs = sorted({t.outs[0] for t in tdg.tasks})
    diffs = [_same(one[s], four[s]) for s in outs]
    worst = max((d for d in diffs if d != "same bits"), default="")
    return f"{diffs.count('same bits')}/{len(outs)} slots same bits {worst}"


def mesh_probe(mesh, bs: int = BS, nb: int = NB) -> None:
    """Steps 1 to 3 of ``mesh`` on any 4-device mesh."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.core import TDG

    wave = TDG("gemm_wave")
    for i in range(1, nb):
        for j in range(1, i):
            wave.add_task(gemm, ins=[f"A{i}{j}", f"L{i}0", f"L{j}0"],
                          outs=[f"A{i}{j}"])
    with jax.default_matmul_precision("highest"):
        print(f"gemm wave alone ({len(wave.tasks)} tasks): "
              f"{_class_parity(wave, mesh, bs)}", flush=True)
        for lanes in (2, 3, 4, 5, 8):
            tdg = TDG(f"gemm_{lanes}")
            for i in range(lanes):
                tdg.add_task(gemm, ins=[f"A{i}", f"L{i}", f"M{i}"],
                             outs=[f"A{i}"])
            share = -(-lanes // mesh.size)
            print(f"gemm class of {lanes} tasks ({share} per device): "
                  f"{_class_parity(tdg, mesh, bs)}", flush=True)

        lanes = 108
        stacked = _stacked(lanes, bs)
        f = jax.jit(jax.vmap(gemm))
        split = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))
        x1 = f(*stacked)
        x4 = f(*(jax.device_put(v, split) for v in stacked))
        print(f"stacked gemm, {lanes} lanes on one device vs "
              f"{lanes // mesh.size} per device: {_same(x1, x4)}", flush=True)


def mesh(cpu: bool = False) -> None:
    import chip_smoke
    from repro.launch.mesh import make_replay_mesh

    if cpu:
        mesh_probe(make_replay_mesh(4), bs=32)
        sharded = dict(n=256, nb=8)
    else:
        from repro.launch import compile_cache

        chip_smoke.device_check(4)
        print(f"compile cache: {compile_cache.enable()}", flush=True)
        mesh_probe(make_replay_mesh(4))
        sharded = {}
    try:
        chip_smoke.phase_sharded(4, **sharded)
    except AssertionError as e:
        print(f"chip_smoke --chips 4: {e}", flush=True)


def _stacked(lanes: int, bs: int = BS):
    import jax

    key = jax.random.PRNGKey(0)
    return tuple(jax.random.normal(jax.random.fold_in(key, i), (lanes, bs, bs))
                 for i in range(3))


def _same(x, y) -> str:
    import numpy as np

    x, y = np.asarray(x), np.asarray(y)
    if np.array_equal(x, y):
        return "same bits"
    return f"DIFF max-abs {float(np.max(np.abs(x - y))):.3e}"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("layouts", "chip", "mesh", "mesh-cpu"))
    args = ap.parse_args(argv)
    if args.mode in ("layouts", "mesh-cpu"):
        # Before JAX starts: these modes never touch a chip.
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if args.mode == "mesh-cpu":
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count=4")
    if args.mode == "layouts":
        layouts()
    elif args.mode == "chip":
        chip()
    else:
        mesh(cpu=args.mode == "mesh-cpu")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
