"""The chat cell's comparison: a sound run passes; faults planted in the
served path come out as not correct.

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
                                   "ttft_p95_ms", "setup_s"}


@pytest.mark.parametrize("fault", ["state_unchanged", "token_altered"])
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
