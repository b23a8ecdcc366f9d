"""The Cholesky cell's comparison: a sound run passes; the precision
control and faults planted in the timed replay come out as not correct.

The cell at n=256, nb=8 on the CPU, driven through the harness below its
look for a chip.
"""
from __future__ import annotations

import jax
import pytest

from bench.tests.small import CHOL, run_small, small_cholesky


def test_sound_run_is_correct():
    cfg, mix = small_cholesky()
    res, checks = run_small(CHOL, cfg, mix)
    assert res["correct"], checks
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"region_ms", "setup_s"}
    assert list(res)[-1] == "checks"


def test_precision_control_fails_the_limit():
    """The reference's factor with bf16x3 trailing updates, in the region's
    place, reads above the limit on every seed."""
    from bench.lib.seeds import jax_key
    from bench.regions import cholesky

    cfg, mix = small_cholesky()
    limit = cfg["limits"]["l_rel_err"]
    for seed in (1, 2, 3):
        a, _ = cholesky.make_input(cfg["n"], mix["nb"], jax_key(seed))
        ref = cholesky.reference(a)
        ctl = cholesky.compare(cholesky.control_factor(a, mix["nb"]), ref)
        assert ctl["l_rel_err"] > limit, (seed, ctl)


def _replay_fault(monkeypatch, fault):
    from repro.core import record

    orig = record.TaskGraphRegion.replay

    def replay(self, **buffers):
        return fault(orig(self, **buffers), buffers)

    monkeypatch.setattr(record.TaskGraphRegion, "replay", replay)


@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered"])
def test_fault_in_the_replay_is_not_correct(monkeypatch, fault):
    def unchanged(out, buffers):      # returns its input tiles as the factor
        return {k: buffers["A" + k[1:]] for k in out}

    def altered(out, buffers):        # one tile off by 1e-3 where produced
        out = dict(out)
        out["L3_1"] = out["L3_1"] * (1 + 1e-3)
        return out

    _replay_fault(monkeypatch, {"state_unchanged": unchanged,
                                "answer_altered": altered}[fault])
    cfg, mix = small_cholesky()
    res, checks = run_small(CHOL, cfg, mix, seed=7)
    assert not res["correct"], checks
    assert jax.numpy.isfinite(checks[0].value) or checks[0].value is not None
