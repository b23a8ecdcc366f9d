"""Serving driver: batched prefill + decode; single-stream, server or cluster.

Three modes:

* **Single-stream** (default): one prompt batch, prefill then an
  autoregressive decode loop. The decode step is a recurrent taskgraph
  region in the paper's sense: recorded (compiled) once, replayed per
  generated token with donated caches.

      PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \\
          --batch 4 --prompt-len 64 --gen 32

* **Multi-tenant server** (``--server``): N tenants each own a decode-step
  taskgraph region (same structure, same payload, private KV/SSM caches,
  shared params) and drive it concurrently through
  ``repro.serving.RegionServer``. Structurally identical decode requests
  coalesce into one batched fused replay per step; the run prints
  throughput plus the server's queue/batch/intern/latency metrics.

      PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \\
          --server --tenants 4 --gen 16

* **Distributed cluster** (``--cluster W``): the same N-tenant decode
  drive, but through ``repro.serving.ClusterFrontend`` — W worker
  *processes* each running a ``RegionServer`` behind the socket RPC layer.
  Model params are shipped once per worker as pinned buffers; per-step
  requests carry only tokens/pos/caches; tenants route sticky-by-structure
  so one worker serves all structurally identical decode regions from one
  warm executable. ``--cluster 0`` uses ``REPRO_CLUSTER_WORKERS``.

      PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \\
          --cluster 2 --tenants 4 --gen 8

  ``--workers host:port,...`` swaps the local spawner for **pre-started
  remote workers** (bootstrap each host with ``python -m
  repro.serving.worker --bind ... --registry
  repro.launch.serve:build_decode_registry --registry-kwargs '{...}'``);
  mix in the literal ``local`` to also spawn workers here. ``--token``
  (default ``$REPRO_RPC_TOKEN``) must match the workers' handshake token.

      PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \\
          --workers 10.0.0.5:7077,local --tenants 4 --gen 8
"""
from __future__ import annotations

import argparse
import dataclasses
import threading
import time
from typing import Any

import jax
import jax.numpy as jnp

from ..configs import ARCHS, get_config, reduced
from ..core.serialize import TaskFnRegistry
from ..models import init_params, prefill
from ..training import make_serve_step
from . import compile_cache


#: Weight init and prefill as one compiled program each (cfg and max_len
#: static). Op by op, a full-width model dispatches every stacked weight's
#: random draw, and every layer of the prompt pass, separately.
init_params_jit = jax.jit(init_params, static_argnums=0)
prefill_jit = jax.jit(prefill, static_argnums=(1, 3))


def build_decode_registry(arch: str = "qwen2.5-3b",
                          smoke: bool = True) -> TaskFnRegistry:
    """Payload symbol table for ``--cluster`` workers (and the frontend).

    A spawned worker cannot receive the decode-step closure over the wire;
    it re-links the TDG's ``"decode"`` symbol by importing this factory and
    rebuilding the step from the (deterministic) model config — the same
    contract as the paper's compiler-emitted TDG referencing outlined
    functions by name.
    """
    cfg = get_config(arch)
    if smoke:
        cfg = reduced(cfg)
    reg = TaskFnRegistry()
    reg.register("decode")(make_serve_step(cfg))
    return reg


def _run_single_stream(args, cfg, params) -> int:
    key = jax.random.PRNGKey(args.seed + 1)
    batch = {"tokens": jax.random.randint(
        key, (args.batch, args.prompt_len), 2, cfg.vocab_size)}
    if cfg.family == "encdec":
        batch["frames"] = jax.random.normal(
            key, (args.batch, cfg.encoder_seq, cfg.d_model), jnp.float32)

    max_len = args.prompt_len + args.gen
    t0 = time.time()
    logits, caches, pos = prefill_jit(params, cfg, batch, max_len)
    jax.block_until_ready(logits)
    t_prefill = time.time() - t0

    serve_step = jax.jit(make_serve_step(cfg), donate_argnums=(3,))
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    outs = [tok]
    t0 = time.time()
    for i in range(args.gen - 1):
        tok, caches = serve_step(params, tok[:, None], pos, caches)
        pos = pos + 1
        outs.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0
    gen = jnp.stack(outs, axis=1)
    tput = args.batch * (args.gen - 1) / max(t_decode, 1e-9)
    print(f"prefill: {t_prefill*1e3:.1f} ms for {args.batch}x{args.prompt_len}")
    print(f"decode:  {t_decode*1e3:.1f} ms for {args.gen-1} steps "
          f"({tput:.1f} tok/s)")
    print("sample token ids:", gen[0, :16].tolist())
    return 0


def _tenant_tiers(args) -> list[int]:
    """Per-tenant QoS tiers from ``--tiers`` ("1" or "0,1,...", cycled)."""
    if not args.tiers:
        return [0] * args.tenants
    cycle = [max(0, int(t)) for t in str(args.tiers).split(",") if t.strip()]
    return [cycle[i % len(cycle)] for i in range(args.tenants)]


def _print_tier_latency(tiers_summary) -> None:
    for tier in sorted(tiers_summary or {}, key=int):
        s = tiers_summary[tier]
        print(f"tier {tier}: n {s['count']}  p50 {s['p50_s']*1e3:.2f} ms  "
              f"p99 {s['p99_s']*1e3:.2f} ms")


@dataclasses.dataclass
class ServedDecode:
    """What :func:`serve_decode` returns: prompts in, tokens out, metrics."""

    prompts: list          # per tenant: (batch, prompt_len) int32
    tokens: list           # per tenant: (batch, gen) int32, first from prefill
    kernel_modes: list     # per tenant: the substrate the server pinned
    stats: dict            # RegionServer.stats() after close
    prefill_s: float
    decode_s: float
    server: Any            # the closed RegionServer (trace ring, pool)


#: A served step may include compiling the batched decode program: a
#: full-width model's takes tens of seconds on the chip.
_STEP_TIMEOUT_S = 600.0


def serve_decode(cfg, params, *, tenants: int, batch: int, prompt_len: int,
                 gen: int, seed: int = 0, max_batch: int = 0,
                 max_wait_ms: float = 5.0, request_level: bool = False,
                 tiers: list[int] | None = None,
                 tenant_rate: float = 0.0) -> ServedDecode:
    """Serve ``tenants`` decode streams through one ``RegionServer``.

    Each tenant prefills its own seeded prompt (private caches and
    positions; ``params`` is the same object for all, so a coalesced batch
    broadcasts rather than stacks it), registers a one-task decode-step
    region, and then issues ``gen - 1`` decode steps from its own thread.
    """
    from ..core import TDG
    from ..serving import RegionServer

    decode = make_serve_step(cfg)
    max_len = prompt_len + gen

    states = []
    t0 = time.time()
    for i in range(tenants):
        key = jax.random.PRNGKey(seed + 1 + i)
        batch_in = {"tokens": jax.random.randint(
            key, (batch, prompt_len), 2, cfg.vocab_size)}
        if cfg.family == "encdec":
            batch_in["frames"] = jax.random.normal(
                key, (batch, cfg.encoder_seq, cfg.d_model), jnp.float32)
        logits, caches, pos = prefill_jit(params, cfg, batch_in, max_len)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        states.append({"prompt": batch_in["tokens"], "tok": tok, "pos": pos,
                       "caches": caches, "out": [tok]})
    jax.block_until_ready([s["tok"] for s in states])
    t_prefill = time.time() - t0

    server = RegionServer(max_batch=max_batch or tenants,
                          max_wait_ms=max_wait_ms, name="decode-server",
                          continuous=False if request_level else None)
    tiers = tiers or [0] * tenants
    modes = []
    for i in range(tenants):
        # One decode-step region per tenant — structurally identical across
        # tenants (same payload object), so they intern to one executable.
        tdg = TDG(f"decode[{i}]")
        tdg.add_task(decode, ins=["params", "tokens", "pos", "caches"],
                     outs=["next", "caches"], name="decode")
        t = server.register_tenant(f"tenant{i}", tdg,
                                   outputs=("next", "caches"),
                                   tier=tiers[i], rate=tenant_rate or None)
        modes.append(t.kernel_mode)

    errors: list[BaseException] = []

    def tenant_loop(i: int) -> None:
        try:
            st = states[i]
            for _ in range(gen - 1):
                out = server.serve(f"tenant{i}", {
                    "params": params, "tokens": st["tok"][:, None],
                    "pos": st["pos"], "caches": st["caches"]},
                    timeout=_STEP_TIMEOUT_S)
                st["tok"] = out["next"]
                st["caches"] = out["caches"]
                st["pos"] = st["pos"] + 1
                st["out"].append(st["tok"])
        except BaseException as e:   # surface thread failures, don't exit 0
            errors.append(e)

    threads = [threading.Thread(target=tenant_loop, args=(i,))
               for i in range(tenants)]
    t0 = time.time()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    t_decode = time.time() - t0
    server.close()
    if errors:
        raise errors[0]
    return ServedDecode(
        prompts=[s["prompt"] for s in states],
        tokens=[jnp.stack(s["out"], axis=1) for s in states],
        kernel_modes=modes, stats=server.stats(), prefill_s=t_prefill,
        decode_s=t_decode, server=server)


def _run_server(args, cfg, params) -> int:
    res = serve_decode(cfg, params, tenants=args.tenants, batch=args.batch,
                       prompt_len=args.prompt_len, gen=args.gen,
                       seed=args.seed, max_batch=args.max_batch,
                       max_wait_ms=args.max_wait_ms,
                       request_level=args.request_level,
                       tiers=_tenant_tiers(args),
                       tenant_rate=args.tenant_rate)
    stats = res.stats
    m = stats["metrics"]
    toks = args.tenants * args.batch * (args.gen - 1)
    print(f"prefill: {res.prefill_s*1e3:.1f} ms for {args.tenants} tenants "
          f"x {args.batch}x{args.prompt_len}")
    print(f"decode:  {res.decode_s*1e3:.1f} ms for {args.gen-1} steps x "
          f"{args.tenants} tenants ({toks / max(res.decode_s, 1e-9):.1f} tok/s)")
    print(f"server:  {m['batches']} batches, occupancy mean "
          f"{m['batch_occupancy_mean']:.2f} max {m['batch_occupancy_max']}, "
          f"{m['batch_fallbacks']} fallbacks, queue peak "
          f"{m['queue_depth_peak']}")
    print(f"pool:    {stats['pool']}  intern: {stats['intern']}")
    print(f"latency: p50 {m['latency']['p50_s']*1e3:.2f} ms  "
          f"p99 {m['latency']['p99_s']*1e3:.2f} ms")
    _print_tier_latency(m.get("tiers"))
    print(f"trace:   {m['trace']}")
    if args.trace_out:
        res.server.dump_trace(args.trace_out)
        print(f"trace ring written to {args.trace_out}")
    for i in (0, args.tenants - 1):
        print(f"tenant{i} sample token ids:", res.tokens[i][0, :12].tolist())
    return 0


def _run_cluster(args, cfg, params) -> int:
    from ..core import TDG
    from ..serving import ClusterFrontend

    registry = build_decode_registry(args.arch, args.smoke)
    decode = registry.get("decode")
    max_len = args.prompt_len + args.gen

    states = []
    t0 = time.time()
    for i in range(args.tenants):
        key = jax.random.PRNGKey(args.seed + 1 + i)
        batch = {"tokens": jax.random.randint(
            key, (args.batch, args.prompt_len), 2, cfg.vocab_size)}
        if cfg.family == "encdec":
            batch["frames"] = jax.random.normal(
                key, (args.batch, cfg.encoder_seq, cfg.d_model), jnp.float32)
        logits, caches, pos = prefill_jit(params, cfg, batch, max_len)
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        states.append({"tok": tok, "pos": pos, "caches": caches, "out": [tok]})
    jax.block_until_ready([s["tok"] for s in states])
    t_prefill = time.time() - t0

    if args.workers:
        workers = [w.strip() for w in args.workers.split(",") if w.strip()]
    else:
        workers = args.cluster or None
    t0 = time.time()
    frontend = ClusterFrontend(
        workers=workers,
        registry="repro.launch.serve:build_decode_registry",
        registry_kwargs={"arch": args.arch, "smoke": args.smoke},
        max_batch=args.max_batch or args.tenants,
        max_wait_ms=args.max_wait_ms, token=args.token,
        continuous=False if args.request_level else None,
        name="decode-cluster")
    tiers = _tenant_tiers(args)
    for i in range(args.tenants):
        tdg = TDG(f"decode[{i}]")
        tdg.add_task(decode, ins=["params", "tokens", "pos", "caches"],
                     outs=["next", "caches"], name="decode")
        # params ship ONCE per worker (pinned); each step's request carries
        # only the varying decode state.
        frontend.register_tenant(f"tenant{i}", tdg, outputs=("next", "caches"),
                                 pinned={"params": params}, tier=tiers[i],
                                 rate=args.tenant_rate or None)
    t_spawn = time.time() - t0

    errors: list[BaseException] = []

    def tenant_loop(i: int) -> None:
        try:
            st = states[i]
            for _ in range(args.gen - 1):
                out = frontend.serve(f"tenant{i}", {
                    "tokens": st["tok"][:, None], "pos": st["pos"],
                    "caches": st["caches"]}, timeout=300)
                st["tok"] = jnp.asarray(out["next"])
                st["caches"] = out["caches"]
                st["pos"] = st["pos"] + 1
                st["out"].append(st["tok"])
        except BaseException as e:   # surface thread failures, don't exit 0
            errors.append(e)

    threads = [threading.Thread(target=tenant_loop, args=(i,))
               for i in range(args.tenants)]
    t0 = time.time()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    t_decode = time.time() - t0
    stats = frontend.stats()
    if args.trace_out:
        import json as _json
        with open(args.trace_out, "w") as f:
            _json.dump(frontend.trace(), f, indent=1)
        print(f"per-worker trace rings written to {args.trace_out}")
    frontend.close()
    if errors:
        raise errors[0]

    fr, agg = stats["frontend"], stats["aggregate"]
    toks = args.tenants * args.batch * (args.gen - 1)
    print(f"prefill: {t_prefill*1e3:.1f} ms for {args.tenants} tenants "
          f"x {args.batch}x{args.prompt_len}")
    print(f"cluster: {fr['workers']} workers ({fr['remote_workers']} remote) "
          f"ready+registered in {t_spawn*1e3:.0f} ms")
    print(f"decode:  {t_decode*1e3:.1f} ms for {args.gen-1} steps x "
          f"{args.tenants} tenants ({toks / max(t_decode, 1e-9):.1f} tok/s "
          f"over RPC)")
    print(f"fleet:   admitted {agg['admitted']}, {agg['batches']} batches, "
          f"coalesced {agg['coalesced_requests']}, aot_served "
          f"{agg['aot_served']}, hydrate failures "
          f"{agg['aot_hydrate_failures']}")
    print(f"routing: {stats['tenants']}")
    print(f"fleet intern: {agg['intern']}  pool: {agg['pool']}")
    print(f"frontend: deaths {fr['worker_deaths']}, requeues "
          f"{fr['requeues']}, artifacts shipped {fr['artifacts_shipped']}")
    for i in (0, args.tenants - 1):
        gen = jnp.stack(states[i]["out"], axis=1)
        print(f"tenant{i} sample token ids:", gen[0, :12].tolist())
    return 0


def _spawns_local_workers(args) -> bool:
    """Would this ``--cluster``/``--workers`` run spawn workers here?"""
    from ..serving.spawner import parse_worker_spec

    if not args.workers:
        return True
    return any(parse_worker_spec(w) is None
               for w in args.workers.split(",") if w.strip())


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCHS), default="qwen2.5-3b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--server", action="store_true",
                    help="multi-tenant RegionServer mode (see repro.serving)")
    ap.add_argument("--cluster", type=int, default=None, nargs="?", const=0,
                    help="distributed mode: worker process count "
                         "(0/omitted value = REPRO_CLUSTER_WORKERS)")
    ap.add_argument("--workers", default=None, metavar="SPEC,SPEC,...",
                    help="distributed mode with explicit worker specs: "
                         "comma-separated host:port of pre-started "
                         "`python -m repro.serving.worker` nodes, plus the "
                         "literal 'local' to also spawn here; implies "
                         "--cluster")
    ap.add_argument("--token", default=None,
                    help="RPC handshake auth token for --cluster/--workers "
                         "(default: $REPRO_RPC_TOKEN)")
    ap.add_argument("--tenants", type=int, default=4,
                    help="[--server/--cluster] concurrent decode tenants")
    ap.add_argument("--max-batch", type=int, default=0,
                    help="[--server/--cluster] coalescing ceiling (0 = #tenants)")
    ap.add_argument("--max-wait-ms", type=float, default=5.0,
                    help="[--server/--cluster] admission window for coalescing")
    ap.add_argument("--request-level", action="store_true",
                    help="[--server/--cluster] legacy run-to-completion "
                         "batching instead of continuous (iteration-level)")
    ap.add_argument("--tiers", default=None, metavar="T0,T1,...",
                    help="[--server/--cluster] per-tenant QoS tiers, cycled "
                         "over tenants (e.g. '0,1'); default all tier 0")
    ap.add_argument("--tenant-rate", type=float, default=0.0,
                    help="[--server/--cluster] per-tenant token-bucket rate "
                         "limit in req/s (0 = unlimited)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="[--server/--cluster] dump the execution-pattern "
                         "trace ring(s) to PATH as JSON after the run")
    args = ap.parse_args(argv)
    compile_cache.enable()

    cluster = args.cluster is not None or bool(args.workers)
    if cluster and _spawns_local_workers(args) \
            and jax.default_backend() == "tpu":
        raise SystemExit(
            "--cluster cannot spawn local workers on a TPU host: a chip "
            "belongs to one process at a time, and this frontend process "
            "already holds it, so every local worker would fail to start. "
            "Run one `python -m repro.serving.worker` per chip host and pass "
            "their addresses with --workers host:port,...")

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced(cfg)
    params = init_params_jit(cfg, jax.random.PRNGKey(args.seed))

    if cluster:
        return _run_cluster(args, cfg, params)
    if args.server:
        return _run_server(args, cfg, params)
    return _run_single_stream(args, cfg, params)


if __name__ == "__main__":
    raise SystemExit(main())
