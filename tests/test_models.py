"""Per-architecture smoke tests (reduced same-family configs) + model
behavior invariants (scan==unrolled, prefill/decode==full forward)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES, get_config, reduced, shape_applicable
from repro.models import (decode_step, forward, init_params, loss_fn,
                          param_count, prefill)

KEY = jax.random.PRNGKey(0)


def _batch(cfg, B=2, S=32):
    b = {"tokens": jax.random.randint(KEY, (B, S), 2, cfg.vocab_size)}
    if cfg.family == "encdec":
        b["frames"] = jax.random.normal(
            jax.random.fold_in(KEY, 7), (B, cfg.encoder_seq, cfg.d_model),
            jnp.float32)
    return b


@pytest.mark.parametrize("arch", ARCHS)
class TestArchSmoke:
    def test_train_step_shapes_and_finite(self, arch):
        cfg = reduced(get_config(arch))
        params = init_params(cfg, KEY)
        batch = _batch(cfg)
        loss, metrics = jax.jit(lambda p, b: loss_fn(p, cfg, b))(params, batch)
        assert np.isfinite(float(loss)), arch
        logits, aux = forward(params, cfg, batch)
        assert logits.shape == (2, 32, cfg.padded_vocab)
        assert np.isfinite(np.asarray(logits)).all()

    def test_grads_nonzero_and_finite(self, arch):
        cfg = reduced(get_config(arch))
        params = init_params(cfg, KEY)
        g = jax.grad(lambda p: loss_fn(p, cfg, _batch(cfg))[0])(params)
        leaves = jax.tree_util.tree_leaves(g)
        assert all(np.isfinite(np.asarray(x)).all() for x in leaves), arch
        total = sum(float(jnp.abs(x).sum()) for x in leaves)
        assert total > 0, arch

    def test_decode_matches_forward(self, arch):
        """Prefill+decode logits == teacher-forced forward logits."""
        cfg = reduced(get_config(arch))
        params = init_params(cfg, KEY)
        B, S = 2, 16
        batch = _batch(cfg, B, S)
        full_logits, _ = forward(params, cfg, batch)

        pre = {**batch, "tokens": batch["tokens"][:, :S // 2]}
        logits, caches, pos = prefill(params, cfg, pre, max_len=S + 4)
        np.testing.assert_allclose(
            np.asarray(logits[:, -1], np.float32),
            np.asarray(full_logits[:, S // 2 - 1], np.float32),
            atol=5e-2, rtol=5e-2)
        # decode the next two tokens teacher-forced and compare
        for t in range(S // 2, S // 2 + 2):
            lg, caches = decode_step(params, cfg, batch["tokens"][:, t:t + 1],
                                     pos, caches)
            pos = pos + 1
            np.testing.assert_allclose(
                np.asarray(lg[:, 0], np.float32),
                np.asarray(full_logits[:, t], np.float32),
                atol=5e-2, rtol=5e-2)


def test_scan_equals_unrolled():
    cfg = reduced(get_config("qwen2.5-3b"), num_layers=4)
    cfg_scan = dataclasses.replace(cfg, scan_layers=True)
    params = init_params(cfg, KEY)
    batch = _batch(cfg)
    l1, _ = loss_fn(params, cfg, batch)
    l2, _ = loss_fn(params, cfg_scan, batch)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)


def test_scan_grouped_heterogeneous_llama4():
    cfg = reduced(get_config("llama4-scout-17b-a16e"), num_layers=4,
                  attn_chunk=16)
    cfg = dataclasses.replace(cfg, global_attn_every=4)
    cfg_scan = dataclasses.replace(cfg, scan_layers=True)
    params = init_params(cfg, KEY)
    batch = _batch(cfg)
    l1, _ = loss_fn(params, cfg, batch)
    l2, _ = loss_fn(params, cfg_scan, batch)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)


def test_loss_chunking_invariant():
    cfg = reduced(get_config("glm4-9b"))
    cfg_chunked = dataclasses.replace(cfg, loss_chunk=8)
    params = init_params(cfg, KEY)
    batch = _batch(cfg, 2, 32)
    l1, _ = loss_fn(params, cfg, batch)
    l2, _ = loss_fn(params, cfg_chunked, batch)
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)


def test_remat_invariant():
    cfg = reduced(get_config("minitron-8b"))
    cfg_remat = dataclasses.replace(cfg, remat="full")
    params = init_params(cfg, KEY)
    batch = _batch(cfg)
    g1 = jax.grad(lambda p: loss_fn(p, cfg, batch)[0])(params)
    g2 = jax.grad(lambda p: loss_fn(p, cfg_remat, batch)[0])(params)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4),
        g1, g2)


def test_param_count_estimate_close():
    """configs.param_count() (analytic) vs actual initialized params."""
    for arch in ("qwen2.5-3b", "mamba2-370m", "qwen3-moe-30b-a3b"):
        cfg = reduced(get_config(arch))
        actual = param_count(init_params(cfg, KEY))
        est = cfg.param_count()
        assert abs(actual - est) / actual < 0.25, (arch, actual, est)


def test_shape_applicability_matrix():
    cells = 0
    for arch in ARCHS:
        cfg = get_config(arch)
        for s in SHAPES.values():
            ok, why = shape_applicable(cfg, s)
            if s.name == "long_500k":
                assert ok == (arch in ("mamba2-370m", "hymba-1.5b")), arch
            else:
                assert ok
            cells += ok
    assert cells == 35   # 11 archs x 4 shapes - 9 inapplicable long_500k


def test_sliding_window_cache_ring_buffer():
    """Hymba ring cache: decode with cache == full forward at long pos."""
    cfg = reduced(get_config("hymba-1.5b"), window=8)
    params = init_params(cfg, KEY)
    B, S = 1, 24
    batch = _batch(cfg, B, S)
    full_logits, _ = forward(params, cfg, batch)
    pre = {**batch, "tokens": batch["tokens"][:, :S - 2]}
    logits, caches, pos = prefill(params, cfg, pre, max_len=S + 2)
    for t in range(S - 2, S):
        lg, caches = decode_step(params, cfg, batch["tokens"][:, t:t + 1],
                                 pos, caches)
        pos = pos + 1
        np.testing.assert_allclose(np.asarray(lg[:, 0], np.float32),
                                   np.asarray(full_logits[:, t], np.float32),
                                   atol=5e-2, rtol=5e-2)
