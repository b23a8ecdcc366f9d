"""The served hybrid program against the plain Granite 4.0-H reference.

A one-period (10-layer) Granite 4.0-H at 64 wide, on seeded weights in
float32: the program's prefill, then decoding through its caches (SSD and
conv state on the Mamba-2 layers, K/V on the attention layer), gives the
logits of the reference's full forward pass at every position. With the
kernels as references (``ref``) and as Pallas bodies on the interpreter.
"""
from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import harness
from bench.reference import granite_hybrid
from bench.tests.small import ROOT
from bench.tests.test_bench_faults_hybrid import small_hybrid


@pytest.mark.parametrize("mode", ["ref", "interpret"])
def test_prefill_then_decode_matches_the_reference(mode):
    from repro.kernels import registry
    from repro.models.model import decode_step, prefill

    cfg, _ = small_hybrid()
    cfg.update(weights_dtype="float32", compute_dtype="float32")
    mcfg = harness.load_plugin("drivers", "served_hybrid_lm").model_config(cfg)
    weights = granite_hybrid.make_weights(cfg, jax.random.PRNGKey(5),
                                          dtype=jnp.float32)
    prompt, steps = 16, 8
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(6), (1, prompt + steps), 0, cfg["vocab_size"]))
    with registry.kernel_mode_scope(mode):
        logits, caches, pos = prefill(weights, mcfg,
                                      {"tokens": jnp.asarray(tokens[:, :prompt])},
                                      prompt + steps)
        got = [logits[0, -1]]
        step = jax.jit(lambda t, p, c: decode_step(weights, mcfg, t, p, c))
        for t in range(prompt, prompt + steps - 1):
            logits, caches = step(jnp.asarray(tokens[:, t:t + 1]), pos, caches)
            pos = pos + 1
            got.append(logits[0, -1])
    got = np.stack(got)[:, :cfg["vocab_size"]]
    rows = np.array([(0, p) for p in range(prompt - 1, prompt + steps - 1)])
    want = np.asarray(granite_hybrid.logits_at(weights, cfg, tokens, rows))
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-4 * scale, (
        np.abs(got - want).max(), scale)


def test_the_cell_serves_the_programs_cut():
    """The cell's configuration file is the program's named cut of Granite
    4.0-H: the same layers, widths and held experts, with only the name and
    the serving choices (bf16 weights, no remat, no loss chunks) its own."""
    from repro.configs.granite_4_0_h_small import CUT

    cfg = json.loads(
        (ROOT / "bench/configs/granite-4.0-h-small-bf16.json").read_text())
    mcfg = harness.load_plugin("drivers", "served_hybrid_lm").model_config(cfg)
    assert mcfg.param_dtype == "bfloat16" and mcfg.remat == "none"
    assert dataclasses.replace(mcfg, name=CUT.name, param_dtype=CUT.param_dtype,
                               remat=CUT.remat,
                               loss_chunk=CUT.loss_chunk) == CUT
