"""The chat cell's comparison: a sound run passes; the precision control and
faults planted in the served path come out as not correct.

A two-layer, 64-wide Qwen2 on the CPU behind four slots, driven through the
harness below its look for a chip.
"""
from __future__ import annotations

import pytest

from bench.tests.small import CHAT, run_small, small_chat


def test_sound_run_is_correct_and_reports_its_metrics():
    cfg, mix = small_chat()
    res, checks = run_small(CHAT, cfg, mix, seconds=1.5)
    assert res["correct"], checks
    assert res["attempted"] == round(mix["rate_rps"] * 1.5)
    assert res["failed"] == 0
    assert set(res["metrics"]) == {"gen_tokens_per_s", "itl_p95_ms",
                                   "setup_s"}


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_precision_control_fails_the_limit(monkeypatch, seed):
    """The reference with every weight matrix through fp8, put in the
    program's place, reads the gap of the token it puts first at each served
    position, and the harness's check finds it over the limit."""
    from bench.lib import harness

    served_lm = harness.load_plugin("drivers", "served_lm")
    reference_gaps = served_lm.reference_gaps

    def control_in_place(weights, cfg, mix, samples, control=False):
        out = reference_gaps(weights, cfg, mix, samples, control=True)
        out["served_logit_gap"] = out.pop("control_logit_gap")
        return out

    monkeypatch.setattr(served_lm, "reference_gaps", control_in_place)
    cfg, mix = small_chat()
    cfg.update(hidden_size=128, num_hidden_layers=4, intermediate_size=256,
               vocab_size=4096)
    mix.update(check_batch=24, check_rows=160, check_tokens=160)
    res, checks = run_small(CHAT, cfg, mix, seed=seed, seconds=1.5)
    assert not res["correct"], checks
    gap = res["checks"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered",
                                   "half_batch_left_out"])
def test_fault_in_the_served_path_is_not_correct(monkeypatch, fault):
    import repro.training
    from repro.serving import RegionServer

    if fault == "state_unchanged":
        make = repro.training.make_serve_step

        def make_broken(cfg):
            step = make(cfg)

            def broken(params, tokens, pos, caches):   # caches never advance
                nxt, _ = step(params, tokens, pos, caches)
                return nxt, caches
            return broken

        monkeypatch.setattr(repro.training, "make_serve_step", make_broken)
    elif fault == "half_batch_left_out":
        fused = RegionServer._run_batched_fused

        def half(self, group):          # the later half gets member 0's step
            outs = fused(self, group)
            k = len(outs) // 2
            return outs[:len(outs) - k] + [outs[0]] * k

        monkeypatch.setattr(RegionServer, "_run_batched_fused", half)
    else:
        serve = RegionServer.serve
        calls = [0]

        def altered(self, name, buffers, timeout=60.0):
            out = serve(self, name, buffers, timeout=timeout)
            calls[0] += 1
            if calls[0] % 3 == 0:                     # a token changed
                out = dict(out, next=(out["next"] + 1) % 512)
            return out

        monkeypatch.setattr(RegionServer, "serve", altered)
    cfg, mix = small_chat()
    res, checks = run_small(CHAT, cfg, mix, seed=11, seconds=1.5)
    assert not res["correct"], checks
