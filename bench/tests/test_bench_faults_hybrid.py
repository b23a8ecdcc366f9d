"""The long-document hybrid cell's comparison: a sound run passes; the
precision control and faults planted in the served path come out as not
correct.

A one-period (10-layer), 64-wide Granite 4.0-H on the CPU: Mamba-2 and
attention layers 9:1, a router over 72 experts of which 18 are held, top-10
as published (at top-2 a routing choice flipped by bf16 rounding moves a
logit as far as the precision control does), behind four slots, driven
through the harness below its look for a chip. The control and the faults
run at 128 wide with a 4,096-token vocabulary and compare 160 tokens.
"""
from __future__ import annotations

import json

import pytest

from bench.lib import harness
from bench.tests.small import ROOT, run_small

CELL = "granite-4.0-h-small-bf16.longdoc"


def small_hybrid() -> tuple[dict, dict]:
    cfg = json.loads(
        (ROOT / "bench/configs/granite-4.0-h-small-bf16.json").read_text())
    mix = json.loads((ROOT / "bench/traffic/longdoc.json").read_text())
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               mamba_n_heads=8, mamba_d_head=16, mamba_d_state=16,
               mamba_chunk_size=8, intermediate_size=32,
               shared_intermediate_size=64, vocab_size=512)
    mix.update(slots=4, rate_rps=16, prompt_lengths=[8, 16, 24],
               output_median=6, output_sigma=0.5, output_min=3,
               output_max=10, max_len=40, check_batch=4, check_rows=64,
               check_tokens=24)
    return cfg, mix


def larger(cfg: dict, mix: dict) -> tuple[dict, dict]:
    cfg.update(hidden_size=128, mamba_n_heads=16, vocab_size=4096)
    mix.update(check_batch=24, check_rows=160, check_tokens=160)
    return cfg, mix


def test_the_cell_reports_the_served_metrics():
    spec = harness.load_spec()
    reported = {m["name"] for m in harness.metrics_of(spec, CELL, "end_to_end")}
    assert reported == {"gen_tokens_per_s", "itl_p95_ms", "setup_s"}
    layers = {m["name"] for m in harness.metrics_of(spec, CELL, "per_layer")}
    assert layers == {"ssd_roofline.longdoc", "expert_roofline.longdoc",
                      "mamba_busy_share.longdoc", "state_mb.longdoc"}


def test_sound_run_is_correct_and_reports_its_metrics():
    cfg, mix = small_hybrid()
    res, checks = run_small(CELL, cfg, mix, seconds=1.5)
    assert res["correct"], checks
    assert res["attempted"] == round(mix["rate_rps"] * 1.5)
    assert res["failed"] == 0
    assert set(res["metrics"]) == {"gen_tokens_per_s", "itl_p95_ms",
                                   "setup_s"}


def test_state_counter_splits_ssd_conv_and_kv():
    driver = harness.load_plugin("drivers", "served_hybrid_lm")
    cfg, mix = small_hybrid()
    got = driver.state_bytes(driver.model_config(cfg), mix["max_len"])
    # 9 Mamba-2 layers: f32 SSD (heads, head dim, state) and bf16 conv
    # history (conv - 1, channels); 1 attention layer: bf16 K and V plus
    # int32 slot positions per cached token
    assert got == {"ssd": 9 * 8 * 16 * 16 * 4,
                   "conv": 9 * 3 * (8 * 16 + 2 * 16) * 2,
                   "kv": 40 * (2 * 2 * 16 * 2 + 4)}


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_precision_control_fails_the_limit(monkeypatch, seed):
    """The reference with its expert and projection matrices through fp8,
    put in the program's place, reads the gap of the token it puts first at
    each served position, and the harness's check finds it over the limit."""
    driver = harness.load_plugin("drivers", "served_hybrid_lm")
    reference_gaps = driver.reference_gaps

    def control_in_place(weights, cfg, mix, samples, control=False):
        out = reference_gaps(weights, cfg, mix, samples, control=True)
        out["served_logit_gap"] = out.pop("control_logit_gap")
        return out

    monkeypatch.setattr(driver, "reference_gaps", control_in_place)
    cfg, mix = larger(*small_hybrid())
    res, checks = run_small(CELL, cfg, mix, seed=seed, seconds=1.5)
    assert not res["correct"], checks
    gap = res["checks"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("fault", ["ssd_state_not_carried",
                                   "another_chips_experts",
                                   "half_batch_left_out"])
def test_fault_in_the_served_path_is_not_correct(monkeypatch, fault):
    import repro.training
    from repro.serving import RegionServer

    driver = harness.load_plugin("drivers", "served_hybrid_lm")
    cfg, mix = larger(*small_hybrid())
    halved = []
    if fault == "ssd_state_not_carried":
        make = repro.training.make_serve_step

        def make_broken(mcfg):
            step = make(mcfg)

            def broken(params, tokens, pos, caches):   # SSD state never moves
                nxt, new = step(params, tokens, pos, caches)
                new = [dict(n, ssm=dict(n["ssm"], ssd=c["ssm"]["ssd"]))
                       if "ssm" in n else n for n, c in zip(new, caches)]
                return nxt, new
            return broken

        monkeypatch.setattr(repro.training, "make_serve_step", make_broken)
    elif fault == "half_batch_left_out":
        fused = RegionServer._run_batched_fused

        def half(self, group):          # the later half gets member 0's step
            outs = fused(self, group)
            k = len(outs) // 2
            halved.append(k)
            return outs[:len(outs) - k] + [outs[0]] * k

        monkeypatch.setattr(RegionServer, "_run_batched_fused", half)
        mix["max_wait_ms"] = 50.0       # busy slots share steps under load too
    else:
        model_config = driver.model_config

        def next_chip(cfg):             # the held weights, routed as 18..35
            import dataclasses
            mcfg = model_config(cfg)
            return dataclasses.replace(mcfg, expert_offset=mcfg.held_experts)

        monkeypatch.setattr(driver, "model_config", next_chip)
    res, checks = run_small(CELL, cfg, mix, seed=11, seconds=1.5)
    assert not res["correct"], checks
    if fault == "half_batch_left_out":
        assert sum(halved) > 0
