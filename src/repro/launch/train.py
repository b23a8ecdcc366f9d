"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b --smoke \
        --steps 100 --batch 8 --seq 128

Wires together every substrate: config -> data pipeline -> model ->
optimizer (cosine or WSD) -> Taskgraph record/replay of the train step ->
async checkpointing -> fault-tolerant supervisor. ``--smoke`` uses the
reduced same-family config (CPU-runnable); omit it on real hardware.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from ..checkpoint import Checkpointer
from ..configs import ARCHS, get_config, reduced
from ..data import DataConfig, make_loader
from ..models import init_params, param_count
from ..optim import adamw, warmup_cosine, wsd
from ..runtime import RunState, StragglerPolicy, run_with_recovery
from ..sharding import partition as P_
from ..training import make_train_step
from . import compile_cache
from .mesh import make_small_mesh


def build(arch: str, smoke: bool, seq: int, batch: int, steps: int,
          lr: float, schedule: str):
    cfg = get_config(arch)
    if smoke:
        cfg = reduced(cfg, num_layers=4, d_model=128, d_ff=256,
                      vocab_size=512, scan_layers=False)
    cfg = dataclasses.replace(cfg, loss_chunk=0)
    if schedule == "wsd" or (schedule == "auto" and arch == "minicpm-2b"):
        lr_fn = wsd(lr, max(steps // 10, 1), int(steps * 0.7),
                    max(int(steps * 0.2), 1))
    else:
        lr_fn = warmup_cosine(lr, max(steps // 10, 1), steps)
    optimizer = adamw(lr_fn)
    return cfg, optimizer


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCHS), default="qwen2.5-3b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--schedule", choices=["auto", "cosine", "wsd"],
                    default="auto")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    compile_cache.enable()

    cfg, optimizer = build(args.arch, args.smoke, args.seq, args.batch,
                           args.steps, args.lr, args.schedule)
    print(f"arch={cfg.name} family={cfg.family}")

    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    print(f"params: {param_count(params):,}")
    opt_state = optimizer.init(params)

    step_fn_raw = jax.jit(make_train_step(cfg, optimizer),
                          donate_argnums=(0, 1))

    def step_fn(state: RunState, batch):
        b = {"tokens": jnp.asarray(batch["tokens"])}
        if cfg.family == "encdec":
            b["frames"] = jnp.zeros(
                (b["tokens"].shape[0], cfg.encoder_seq, cfg.d_model),
                cfg.compute_dtype)
        p, s, metrics = step_fn_raw(state.params, state.opt_state, b)
        return RunState(p, s, state.step), metrics

    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      global_batch=args.batch, seed=args.seed)
    ckpt = Checkpointer(args.ckpt_dir)
    losses = []

    def on_metrics(step, m):
        losses.append(float(m["loss"]))
        if step % 10 == 0 or step == 1:
            print(f"step {step:5d} loss {float(m['loss']):.4f} "
                  f"lr {float(m['lr']):.2e} gnorm {float(m['grad_norm']):.3f}",
                  flush=True)

    t0 = time.time()
    state, report = run_with_recovery(
        step_fn, RunState(params, opt_state, 0),
        data_iter_factory=lambda s: make_loader(dcfg, s),
        num_steps=args.steps, checkpointer=ckpt,
        checkpoint_every=args.ckpt_every, on_metrics=on_metrics,
        straggler_policy=StragglerPolicy())
    dt = time.time() - t0
    first = sum(losses[:5]) / max(len(losses[:5]), 1)
    last = sum(losses[-5:]) / max(len(losses[-5:]), 1)
    print(f"done: {report}  wall={dt:.1f}s  "
          f"loss {first:.3f} -> {last:.3f}")
    assert last < first, "loss did not improve"
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
