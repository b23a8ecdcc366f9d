#!/usr/bin/env python3
"""Bring-up check of the main path on a TPU: every phase in one process.

    python chip_smoke.py             # one chip: phases (a) and (b)
    python chip_smoke.py --chips 4   # four chips: sharded replay only

(a) The paper's path. The tiled-Cholesky ``@taskgraph`` region of
    ``examples/quickstart.py`` at n=16384, nb=16 (a 1 GiB f32 matrix, 816
    tasks) is recorded, replayed through its fused AOT executable and run
    through ``EagerExecutor``; replay and eager are checked against
    ``jnp.linalg.cholesky`` of the same matrix.
(b) A model served at full width. qwen2.5-3b, all 36 layers at the published
    widths, serves 4 tenants x batch 4 (128-token prompts, 16 generated
    tokens) through the ``RegionServer`` path of ``launch/serve.py
    --server``; every served token is checked against the plain jitted
    single-stream decode of the same prompts.
--chips 4: the same Cholesky region at n=4096, nb=16 (816 tasks, the same
    class widths; the smaller tiles keep the two compiles short), its fused
    replay sharded over a 4-chip mesh against single-device replay, bit for
    bit.

Any failed check raises, so the script exits non-zero and never prints the
ok line. Without a TPU it stops before any phase. The last line of standard
output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), os.path.join(ROOT, "examples")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# Phase (a): the quickstart region at a size that fills HBM, not caches.
CHOL_N, CHOL_NB = 16384, 16

# --chips 4: the same graph with 256x256 tiles.
MESH_N = 4096

# Phase (b): qwen2.5-3b served as 4 tenants x batch 4.
SERVE_ARCH, TENANTS, BATCH, PROMPT_LEN, GEN = "qwen2.5-3b", 4, 4, 128, 16
# A served token that differs from the reference's argmax must still be a
# near-tie there: its reference logit within 2^-5 (8 bf16 ulps) of the top
# logit, relative to the top logit's magnitude (at least 1).
LOGIT_TOL_REL = 2.0 ** -5


def chol_tol(n: int) -> float:
    """Normwise relative error allowed against ``jnp.linalg.cholesky``.

    sqrt(n) * eps(f32), the typical growth of f32 rounding over a length-n
    reduction. On a v5e at n=16384 the replay lands at 1.1e-7 under
    ``highest`` precision and at 2.1e-4 under the default (bf16-pass)
    precision, so a replay that drops ``highest`` fails. A wrong, stale or
    misplaced tile gives an error of order 1.
    """
    return n ** 0.5 * float(jnp.finfo(jnp.float32).eps)


def log(msg: str) -> None:
    print(msg, flush=True)


def device_check(chips: int) -> dict:
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX platform is "
                         f"{platform!r}); this check never runs on the CPU")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} needs {chips} TPU "
                         f"devices, JAX sees {len(devices)}")
    info = {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}
    log(f"jax {jax.__version__}  device {info['kind']}  count {info['count']}")
    return info


# --------------------------------------------------------------- phase (a)

def spd_tiles(n: int, nb: int, seed: int) -> tuple[jax.Array, dict]:
    """A seeded SPD matrix made on the device, and its lower tiles."""
    bs = n // nb

    @jax.jit
    def make(key):
        m = jax.random.normal(key, (n, n), jnp.float32)
        a = m @ m.T + n * jnp.eye(n, dtype=jnp.float32)
        tiles = {f"A{i}{j}": a[i * bs:(i + 1) * bs, j * bs:(j + 1) * bs]
                 for i in range(nb) for j in range(i + 1)}
        return a, tiles

    return make(jax.random.PRNGKey(seed))


def assemble(out: dict, n: int, nb: int) -> jax.Array:
    """The full lower-triangular factor from the region's L tiles."""
    bs = n // nb
    zero = jnp.zeros((bs, bs), jnp.float32)
    return jnp.concatenate([
        jnp.concatenate([out[f"L{i}{j}"] if j <= i else zero
                         for j in range(nb)], axis=1)
        for i in range(nb)], axis=0)


@jax.jit
def rel_err(x, ref):
    return jnp.linalg.norm(x - ref) / jnp.linalg.norm(ref)


@jax.jit
def residual(l, a):
    return jnp.linalg.norm(l @ l.T - a) / jnp.linalg.norm(a)


def check_plan(plan, label: str) -> None:
    summary = plan.summary()
    decisions = summary.pop("decisions")
    log(f"{label} fusion plan: {json.dumps(summary)}")
    fallbacks = [d for d in decisions if d["reason"].startswith("trace fallback")]
    if fallbacks:
        raise AssertionError(f"{len(fallbacks)} fused classes fell back to "
                             f"unrolled at trace time: {fallbacks[:3]}")


def phase_replay(n: int = CHOL_N, nb: int = CHOL_NB, seed: int = 0,
                 reps: int = 3, precision: str = "highest") -> None:
    from quickstart import cholesky_region

    from repro.core import EagerExecutor
    from repro.kernels import registry

    log(f"(a) tiled Cholesky n={n} nb={nb}, kernel mode "
        f"{registry.resolved_mode()}, matmul precision {precision}")
    # The input and the reference at f32 precision, whatever the region's.
    with jax.default_matmul_precision("highest"):
        a, tiles = spd_tiles(n, nb, seed)
        l_ref = jnp.linalg.cholesky(a)
        jax.block_until_ready((tiles, l_ref))
    region = cholesky_region(nb)
    with jax.default_matmul_precision(precision):
        t0 = time.perf_counter()
        region(**tiles)                                  # record
        t_record = time.perf_counter() - t0
        log(f"(a) record: {region.tdg.num_tasks} tasks in "
            f"{t_record * 1e3:.1f} ms")

        t0 = time.perf_counter()
        aot = region.warmup(**tiles)                     # fused AOT compile
        log(f"(a) replay compile: {time.perf_counter() - t0:.1f} s")
        check_plan(aot.plan, "(a)")
        out = region(**tiles)                            # replay
        t0 = time.perf_counter()
        for _ in range(reps):
            out = region(**tiles)
        t_replay = (time.perf_counter() - t0) / reps

        eager = EagerExecutor(region.tdg, n_workers=4)
        eager.run(dict(tiles))
        t0 = time.perf_counter()
        for _ in range(reps):
            out_e = eager.run(dict(tiles))
        t_eager = (time.perf_counter() - t0) / reps
        log(f"(a) replay {t_replay * 1e3:.1f} ms, eager {t_eager * 1e3:.1f} "
            f"ms per run (information only, not a measurement)")

    with jax.default_matmul_precision("highest"):
        l_replay = assemble(out, n, nb)
        errs = {"replay_vs_ref": float(rel_err(l_replay, l_ref)),
                "eager_vs_ref": float(rel_err(assemble(out_e, n, nb), l_ref)),
                "replay_residual": float(residual(l_replay, a))}
    tol = chol_tol(n)
    log(f"(a) normwise relative errors {errs}, tolerance {tol:.3e}")
    bad = {k: v for k, v in errs.items() if not v <= tol}
    if bad:
        raise AssertionError(f"(a) Cholesky outside tolerance: {bad}")
    log("(a) PASS")


# --------------------------------------------------------------- phase (b)

def phase_serve(cfg=None, seed: int = 0) -> None:
    from repro.configs import get_config
    from repro.launch.serve import init_params_jit, prefill_jit, serve_decode
    from repro.models import decode_step, param_count

    if cfg is None:
        # f32 weights (12.35 GB) plus the decode step's hoisted bf16 weight
        # casts (5.17 GB) exceed one v5e's 15.75 GB, as the compile for a
        # described v5e reports; held in bf16 the weights take 6.17 GB.
        cfg = dataclasses.replace(get_config(SERVE_ARCH),
                                  param_dtype="bfloat16")
        log(f"(b) {SERVE_ARCH}: weights held in bfloat16 (f32 weights do not "
            f"fit one chip beside the decode step); all {cfg.num_layers} "
            f"layers at published widths")
    params = init_params_jit(cfg, jax.random.PRNGKey(seed))
    log(f"(b) {cfg.name}: {param_count(params):,} parameters, "
        f"d_model {cfg.d_model}, {cfg.num_heads}x{cfg.head_dim} heads "
        f"(kv {cfg.num_kv_heads}), d_ff {cfg.d_ff}, vocab {cfg.vocab_size}")

    t0 = time.perf_counter()
    res = serve_decode(cfg, params, tenants=TENANTS, batch=BATCH,
                       prompt_len=PROMPT_LEN, gen=GEN, seed=seed,
                       max_wait_ms=2000.0)
    m = res.stats["metrics"]
    log(f"(b) served {TENANTS} tenants x {BATCH} x {GEN} tokens in "
        f"{time.perf_counter() - t0:.1f} s incl. compile (information only): "
        f"{m['batches']} batches, {m['coalesced_requests']} coalesced "
        f"requests, {m['batch_fallbacks']} fallbacks, "
        f"{m['aot_hydrate_failures']} hydrate failures, "
        f"occupancy max {m['batch_occupancy_max']}, kernel modes "
        f"{sorted(set(res.kernel_modes))}")
    problems = []
    if set(res.kernel_modes) != {"pallas"}:
        problems.append(f"kernel modes {res.kernel_modes}, want pallas")
    if m["batch_fallbacks"] != 0:
        problems.append(f"{m['batch_fallbacks']} batch fallbacks")
    if m["coalesced_requests"] < 1:
        problems.append("no coalesced batch")
    if m["aot_hydrate_failures"] != 0:
        problems.append(f"{m['aot_hydrate_failures']} hydrate failures")
    if m["failed"] != 0:
        problems.append(f"{m['failed']} failed requests")

    # The program the server ran: its pooled batched decode step, compiled
    # at the served occupancy with params shared and the rest per member.
    max_len = PROMPT_LEN + GEN
    _, caches0, pos0 = prefill_jit(params, cfg, {"tokens": res.prompts[0]},
                                   max_len)

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)

    served_step = res.server.compile_batched("tenant0", [
        {"params": params, "tokens": spec(res.tokens[i][:, :1]),
         "pos": spec(pos0), "caches": jax.tree_util.tree_map(spec, caches0)}
        for i in range(TENANTS)])
    n_kernels = served_step.as_text().count("tpu_custom_call")
    ma = served_step.memory_analysis()
    log(f"(b) served batched decode step ({TENANTS} x {BATCH}): {n_kernels} "
        f"tpu_custom_call, args {ma.argument_size_in_bytes / 1e9:.3f} GB, "
        f"temp {ma.temp_size_in_bytes / 1e9:.3f} GB")
    if n_kernels == 0:
        problems.append("no tpu_custom_call in the served decode step")

    # Reference: the plain jitted single-stream decode (greedy_decode's
    # prefill + decode_step), teacher-forced with the served tokens so that
    # one near-tie flip cannot derail the rest of the comparison.
    step = jax.jit(lambda p, t, ps, c: decode_step(p, cfg, t, ps, c)).lower(
        params, res.tokens[0][:, :1], pos0, caches0).compile()
    del caches0
    log(f"(b) reference decode step: "
        f"{step.as_text().count('tpu_custom_call')} tpu_custom_call "
        f"(information only)")

    exact = total = 0
    worst = 0.0
    for i in range(TENANTS):
        served = res.tokens[i]                           # (BATCH, GEN)
        logits, caches, pos = prefill_jit(params, cfg,
                                          {"tokens": res.prompts[i]}, max_len)
        gaps = []
        for s in range(GEN):
            last = logits[:, -1]
            if not bool(jnp.all(jnp.isfinite(last))):
                problems.append(f"tenant{i} step {s}: non-finite logits")
            top = last.max(axis=-1)
            chosen = jnp.take_along_axis(last, served[:, s:s + 1], -1)[:, 0]
            exact += int(jnp.sum(jnp.argmax(last, -1) == served[:, s]))
            gaps.append((top - chosen) / jnp.maximum(jnp.abs(top), 1.0))
            if s + 1 < GEN:
                logits, caches = step(params, served[:, s:s + 1], pos, caches)
                pos = pos + 1
        total += served.size
        worst = max(worst, float(jnp.max(jnp.stack(gaps))))
    log(f"(b) served vs reference: {exact}/{total} tokens identical, largest "
        f"relative logit gap {worst:.3e} (tolerance {LOGIT_TOL_REL:.3e})")
    if not worst <= LOGIT_TOL_REL:
        problems.append(f"served tokens off the reference argmax by "
                        f"{worst:.3e} > {LOGIT_TOL_REL:.3e}")
    if problems:
        raise AssertionError(f"(b) {problems}")
    log("(b) PASS")


# ------------------------------------------------------------- --chips 4

_COLLECTIVE = re.compile(r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
                         r"collective-permute)(-start)?\(")


def phase_sharded(n_devices: int = 4, n: int = MESH_N, nb: int = CHOL_NB,
                  seed: int = 0) -> None:
    from quickstart import cholesky_region

    from repro.core import ReplayExecutor, topo_waves
    from repro.launch.mesh import make_replay_mesh

    mesh = make_replay_mesh(n_devices)
    log(f"(mesh) tiled Cholesky n={n} nb={nb}, fused replay sharded over "
        f"{dict(mesh.shape)} vs one device")
    with jax.default_matmul_precision("highest"):
        a, tiles = spd_tiles(n, nb, seed)
        region = cholesky_region(nb)
        region(**tiles)                                  # record
        single = ReplayExecutor(region.tdg, mesh=None)
        sharded = ReplayExecutor(region.tdg, mesh=mesh)
        aot1 = single.aot_compile(tiles)
        aotn = sharded.aot_compile(tiles)
        check_plan(aotn.plan, "(mesh)")
        log(f"(mesh) pad lanes {aotn.plan.padded_lanes}, pad fraction "
            f"{aotn.plan.pad_fraction:.4f}")
        out1 = single.run(dict(tiles))
        outn = sharded.run(dict(tiles))
        l_ref = jnp.linalg.cholesky(a)
        errs = {"single_vs_ref": float(rel_err(assemble(out1, n, nb), l_ref)),
                "sharded_vs_ref": float(rel_err(assemble(outn, n, nb),
                                                l_ref))}
    tol = chol_tol(n)
    log(f"(mesh) normwise relative errors {errs}, tolerance {tol:.3e}")
    text = aotn.compiled.as_text()
    counts: dict[str, int] = {}
    for op, _ in _COLLECTIVE.findall(text):
        counts[op] = counts.get(op, 0) + 1
    log(f"(mesh) collectives in the sharded HLO: {counts}")
    for name, aot in (("single", aot1), ("sharded", aotn)):
        ma = aot.compiled.memory_analysis()
        log(f"(mesh) {name} per-device bytes: args "
            f"{ma.argument_size_in_bytes}, out {ma.output_size_in_bytes}, "
            f"temp {ma.temp_size_in_bytes}")
    if not counts:
        raise AssertionError("(mesh) sharded replay has no collectives: "
                             "the batch axis was not split")
    bad = {k: v for k, v in errs.items() if not v <= tol}
    if bad:
        raise AssertionError(f"(mesh) Cholesky outside tolerance: {bad}")
    mismatched = [k for k in out1
                  if not bool(jnp.array_equal(out1[k], outn[k]))]
    log(f"(mesh) sharded vs single-device replay: "
        f"{len(out1) - len(mismatched)}/{len(out1)} slots bit-identical")
    if mismatched:
        # Where the two first part: the earliest wave whose task last wrote
        # a differing slot, and how far apart the slots are.
        last_writer = {}
        for w, tids in enumerate(topo_waves(region.tdg)):
            for tid in tids:
                task = region.tdg.tasks[tid]
                for slot in task.outs:
                    last_writer[slot] = (w, task.name)
        first = min(last_writer[k] for k in mismatched)
        rel = {k: float(jnp.max(jnp.abs(out1[k] - outn[k]))
                        / jnp.max(jnp.abs(out1[k]))) for k in mismatched}
        worst = max(rel, key=rel.get)
        raise AssertionError(
            f"(mesh) {len(mismatched)} slots differ; earliest last writer "
            f"{first[1]} in wave {first[0]}; largest max-abs difference "
            f"relative to the slot's max-abs value {rel[worst]:.3e} "
            f"({worst}, written by {last_writer[worst][1]})")
    log("(mesh) PASS")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only sharded replay across four chips")
    args = ap.parse_args(argv)
    device = device_check(args.chips)

    from repro.launch import compile_cache

    log(f"compile cache: {compile_cache.enable()}")
    if args.chips == 4:
        phase_sharded(4)
    else:
        phase_replay()
        phase_serve()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
