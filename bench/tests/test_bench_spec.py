"""BENCHMARK.json is well formed, and every name in it is found as a file."""
from __future__ import annotations

import json
import re

import pytest

from bench.lib import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
SPEC = harness.load_spec()


def test_top_level_keys_and_limits():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"][1] == "bench/run.py"
    assert 1 <= SPEC["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in SPEC[k]]
    assert all(NAME.match(n) for n in names), names
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in SPEC[k]}) == len(SPEC[k])
    metric_names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(set(metric_names)) == len(metric_names)
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_end_to_end_bounds_and_setup():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
        assert m["better"] in ("lower", "higher")


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_every_name_of_a_cell_is_found(cell):
    w, cfg_entry = harness.find_cell(SPEC, cell)
    assert w["chips"] in (1, 4)
    cfg = harness.load_config(cfg_entry)
    mix = harness.load_mix(w["traffic"])
    assert harness.load_plugin("drivers", cfg["kind"]).run
    assert harness.load_plugin("generators", mix["kind"]).make
    if "region" in cfg:
        assert harness.load_plugin("regions", cfg["region"]).build
    reported = {m["name"] for m in harness.metrics_of(SPEC, cell, "end_to_end")}
    assert "setup_s" in reported and len(reported) >= 2
    layers = harness.metrics_of(SPEC, cell, "per_layer")
    assert layers
    for m in layers:
        assert harness.load_plugin("metrics", m["name"]).read
        assert m["moves"] in reported
    assert set(cfg["limits"])


#: The end-to-end metrics each cell reports; a later served cell joins the
#: chat cell's served metrics.
CELL_METRICS = {
    "cholesky-n16384.tile512": {"region_ms", "setup_s"},
    "qwen2.5-3b-bf16.chat": {"gen_tokens_per_s", "itl_p95_ms", "setup_s"},
}


@pytest.mark.parametrize("cell", sorted(CELL_METRICS))
def test_each_cell_reports_its_end_to_end_metrics(cell):
    reported = {m["name"] for m in harness.metrics_of(SPEC, cell, "end_to_end")}
    assert reported == CELL_METRICS[cell]
    for m in SPEC["per_layer"]:
        if cell in m.get("workloads", [cell]):
            assert m["moves"] in reported, m["name"]


def test_a_new_name_resolves_without_editing_a_file(tmp_path):
    """A later PR adds a metric, a mix and a generator as new files only."""
    (tmp_path / "metrics").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "generators").mkdir()
    (tmp_path / "metrics" / "queue_ms.serve.py").write_text(
        "def read(ctx):\n    return ctx.counters.get('queue_ms')\n")
    (tmp_path / "traffic" / "bursty.json").write_text('{"kind": "bursts"}')
    (tmp_path / "generators" / "bursts.py").write_text(
        "def make(config, mix, seed, seconds):\n    return []\n")
    mix = harness.load_mix("bursty", bench=tmp_path)
    gen = harness.load_plugin("generators", mix["kind"], bench=tmp_path)
    reader = harness.load_plugin("metrics", "queue_ms.serve", bench=tmp_path)
    ctx = harness.MetricContext(cell="c", config={}, mix=mix,
                                counters={"queue_ms": 4.5}, window_s=1.0,
                                trace=None, peaks={})
    assert gen.make({}, mix, 1, 1.0) == [] and reader.read(ctx) == 4.5
    with pytest.raises(LookupError):
        harness.load_plugin("metrics", "absent_metric", bench=tmp_path)


def test_unknown_device_has_no_peaks():
    from bench.lib.peaks import UnknownDevice, peaks_for

    assert peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(UnknownDevice):
        peaks_for("cpu")
