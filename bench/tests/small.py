"""Small cells that the benchmark's tests run on the CPU.

Nothing here touches a TPU: the tests call the harness below its look for a
chip, with configurations and mixes cut to sizes a test run can hold.
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
import types

ROOT = pathlib.Path(__file__).resolve().parents[2]

CPU_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
CHOL = "cholesky-n16384.tile512"
CHAT = "qwen2.5-3b-bf16.chat"


def load_run():
    spec = importlib.util.spec_from_file_location("bench_run_module",
                                                  ROOT / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def small_cholesky() -> tuple[dict, dict]:
    cfg = json.loads((ROOT / "bench/configs/cholesky-n16384-f32.json").read_text())
    mix = json.loads((ROOT / "bench/traffic/replay-tile512.json").read_text())
    cfg["n"], mix["nb"] = 256, 8
    return cfg, mix


def small_chat() -> tuple[dict, dict]:
    cfg = json.loads((ROOT / "bench/configs/qwen2.5-3b-bf16.json").read_text())
    mix = json.loads((ROOT / "bench/traffic/chat.json").read_text())
    cfg.update(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
               num_key_value_heads=2, intermediate_size=128, vocab_size=512)
    mix.update(slots=4, rate_rps=16, prompt_lengths=[8, 16, 24],
               output_median=6, output_sigma=0.5, output_min=3,
               output_max=10, max_len=40, check_batch=4, check_rows=64,
               check_tokens=24)
    return cfg, mix


def run_small(cell: str, cfg: dict, mix: dict, seed: int = 3,
              seconds: float = 1.0, trace: int = 0) -> tuple[dict, list]:
    """One run of ``cell`` at the given small sizes; (result, checks)."""
    from bench.lib import harness

    run = load_run()
    spec = harness.load_spec()
    args = types.SimpleNamespace(workload=cell, seed=seed, seconds=seconds,
                                 trace=trace)
    line, checks = run.execute(spec, args, CPU_DEVICE, PEAKS,
                               config=cfg, mix=mix)
    return json.loads(line), checks
