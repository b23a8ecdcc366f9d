"""The hybrid cell's operation and byte counts against hand counts and the
program's parameter count, and its readers on a made-up trace."""
from __future__ import annotations

import dataclasses
import json

import pytest

from bench.lib import harness, work_hybrid
from bench.lib.trace import TraceSummary
from bench.tests.small import PEAKS, ROOT

GRANITE = json.loads(
    (ROOT / "bench/configs/granite-4.0-h-small-bf16.json").read_text())
CELL = "granite-4.0-h-small-bf16.longdoc"


def test_per_token_matmuls_are_twice_the_active_parameters():
    """Held experts all 72: a token meets its top-10, as the program's
    active parameter count has it (conv, A, D, dt bias and norms are the
    0.01% this count leaves out)."""
    from repro.configs.granite_4_0_h_small import CUT

    uncut = dict(GRANITE, num_local_experts=72)
    program = dataclasses.replace(CUT, experts_held=72)
    assert work_hybrid.matmul_flops_per_token(uncut) == pytest.approx(
        2 * program.active_param_count(), rel=2e-4)
    assert work_hybrid.routed_rows(GRANITE, 1) == pytest.approx(10 * 18 / 72)


@pytest.mark.parametrize("prompt,gen", [(1, 1), (256, 1), (256, 64), (7, 13)])
def test_request_flops_match_a_token_by_token_sum(prompt, gen):
    tok = (work_hybrid.matmul_flops_per_token(GRANITE, with_head=False)
           + work_hybrid.scan_flops_per_token(GRANITE))
    head = 2 * 100352 * 4096
    att = lambda p: 4 * 1 * 4096 * (p + 1)     # one attention layer, 32 x 128
    want = sum(tok + att(p) for p in range(prompt)) + head
    for g in range(1, gen):
        want += tok + head + att(prompt + g - 1)
    assert work_hybrid.request_flops(GRANITE, prompt, gen) == pytest.approx(want)


def test_ssd_and_expert_kernels_by_hand():
    flops, nbytes = work_hybrid.ssd_kernel_work(GRANITE, 256)
    n = 9 * 256                               # layers x tokens; one group
    assert flops == pytest.approx(
        n * (2 * 256 * 128 + 128 * 2 * (256 * 64 + 128 * 64)))
    assert nbytes == pytest.approx(
        n * 4 * (128 * (2 * 64 + 1) + 2 * 128 + 128 * 128 * 64 / 256))
    flops, nbytes = work_hybrid.expert_prefill_work(GRANITE, 4096)
    rows = 4096 * 2.5
    assert flops == pytest.approx(10 * rows * 6 * 4096 * 768)
    weights = 10 * 18 * 3 * 4096 * 768 * 2
    assert nbytes == pytest.approx(weights + 10 * rows * (2 * 4096 + 3 * 768) * 2)
    (f1, b1), (f2, b2) = work_hybrid.expert_decode_work(GRANITE, {1: 1, 16: 1})
    assert b1 == pytest.approx(weights * 2.5 / 18)
    assert b2 == pytest.approx(weights * (1 - (1 - 10 / 72) ** 16))
    assert f2 == pytest.approx(16 * f1)


def _ctx(trace, counters):
    return harness.MetricContext(
        cell=CELL, config=GRANITE, mix={}, counters=counters, window_s=30.0,
        trace=trace, peaks=PEAKS)


def test_readers_on_a_made_up_trace():
    ops = {"ssd_intra_chunk.3": 0.5, "ragged-dot-none.7": 2.0,
           "ragged-dot-metadata.2": 0.01, "fusion.1": 5.0}
    trace = TraceSummary(window_s=30.0, busy_s=10.0, op_seconds=ops,
                         op_calls={k: 10 for k in ops}, module_seconds={},
                         module_calls={}, idle_by_span={}, devices=1)
    counters = {"rows": 20000, "prompt_tokens": 16384, "decode_tokens": 1000,
                "step_occupancy": {1: 200, 4: 200}, "state_bytes_member": 10**8}
    read = {m: harness.load_plugin("metrics", m).read
            for m in ("ssd_roofline.longdoc", "expert_roofline.longdoc",
                      "mamba_busy_share.longdoc", "state_mb.longdoc")}
    ssd = read["ssd_roofline.longdoc"](_ctx(trace, counters))
    want = work_hybrid.least_seconds(
        [work_hybrid.ssd_kernel_work(GRANITE, 16384)], PEAKS) / 0.5
    assert ssd == pytest.approx(100 * want)
    assert 0 < read["expert_roofline.longdoc"](_ctx(trace, counters)) < 100
    assert read["mamba_busy_share.longdoc"](_ctx(trace, counters)) == \
        pytest.approx(5.0)
    assert read["state_mb.longdoc"](_ctx(trace, counters)) == 200.0
    for name, fn in read.items():             # a run without the program's
        if name != "state_mb.longdoc":        # kernels or without a trace
            assert fn(_ctx(None, counters)) is None
    assert read["state_mb.longdoc"](_ctx(None, {})) is None
