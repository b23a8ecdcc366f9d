"""Operation and byte counts against hand counts."""
from __future__ import annotations

import json

import pytest

from bench.lib import work
from bench.tests.small import ROOT

QWEN = json.loads((ROOT / "bench/configs/qwen2.5-3b-bf16.json").read_text())


def test_cholesky_is_n_cubed_over_three():
    assert work.cholesky_flops(16384) == pytest.approx(16384 ** 3 / 3)
    assert work.cholesky_flops(3) == pytest.approx(9.0)


def test_qwen_per_token_matmuls_are_twice_the_parameters():
    # 3,086,200,832 parameters, the tied table counted once (its 256-row
    # padding, biases and norms are the 0.02% this count leaves out)
    assert work.qwen2_matmul_flops_per_token(QWEN) == pytest.approx(
        2 * 3_086_200_832, rel=2e-4)
    per_layer = 2048 * (2048 + 2 * 256) + 2048 * 2048 + 3 * 2048 * 11008
    assert work.qwen2_matmul_flops_per_token(QWEN, with_head=False) == \
        2 * 36 * per_layer


@pytest.mark.parametrize("prompt,gen", [(1, 1), (256, 1), (256, 64), (7, 13)])
def test_request_flops_match_a_token_by_token_sum(prompt, gen):
    tok = work.qwen2_matmul_flops_per_token(QWEN, with_head=False)
    head = work.qwen2_matmul_flops_per_token(QWEN) - tok
    want = sum(tok + work.qwen2_attention_flops(QWEN, p) for p in range(prompt))
    want += head                               # logits of the first token
    for g in range(1, gen):                    # each decode step
        want += tok + head + work.qwen2_attention_flops(QWEN, prompt + g - 1)
    assert work.qwen2_request_flops(QWEN, prompt, gen) == pytest.approx(want)


def test_rmsnorm_bytes_read_and_write_each_row():
    assert work.rmsnorm_bytes(QWEN, 10) == 73 * 10 * 2048 * 2 * 2


def test_stacked_bytes_of_a_small_cholesky_region():
    """nb=4, 8x8 f32 tiles (256 bytes): count each fused class by hand."""
    import jax

    from bench.lib.seeds import jax_key
    from bench.regions import cholesky

    n, nb, tile = 32, 4, 8 * 8 * 4
    _, tiles = cholesky.make_input(n, nb, jax_key(1))
    region = cholesky.build(nb)
    with jax.default_matmul_precision("highest"):
        region(**tiles)
        aot = region.warmup(**tiles)
    assert region.tdg.num_tasks == cholesky.task_count(nb) == 20
    want = 0
    for c in aot.plan.classes:
        if c.fused and c.batcher in ("vmap", "map"):
            varying = sum(not s for s in c.shared)
            want += (varying * (c.size + c.padded) + c.size) * tile
    assert want > 0
    assert work.stacked_bytes(region.tdg, aot.plan, tiles) == want
