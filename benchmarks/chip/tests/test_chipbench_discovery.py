"""BENCHMARK.json and the files it names: every name resolves, and a new
configuration, traffic mix or metric is found by name from new files."""

import _paths
from _paths import ROOT

import json
import re
import shutil

import pytest

from benchmarks.chip import spec, traffic

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.benchmark(ROOT)


def test_every_cell_resolves_by_name(bench):
    e2e = {m["name"] for m in bench["end_to_end"]}
    for cell in bench["workloads"]:
        assert NAME.match(cell["name"]) and cell["chips"] in (1, 4)
        cfg = spec.config(cell["config"])
        assert cfg["name"] == cell["config"]
        mix = spec.traffic(cell["traffic"])
        assert traffic.count(mix, bench["run_seconds"]) >= 1
        reported = {m["name"] for m in spec.metrics(bench, cell["name"],
                                                    "end_to_end")}
        assert "setup_s" in reported and len(reported) >= 2
        layer = spec.metrics(bench, cell["name"], "per_layer")
        assert layer
        for m in layer:
            assert m["moves"] in reported, (cell["name"], m["name"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert callable(spec.reader(m["name"]))
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
        assert m["name"] in e2e


def test_configuration_files_state_their_source_and_cuts(bench):
    for c in bench["configs"]:
        cfg = spec.load_json(ROOT / c["file"])
        assert cfg["source"] == c["source"]
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert cfg["gap_limit"] is not None and cfg["gap_limit"] > 0


def test_new_files_are_found_by_name(tmp_path):
    base = tmp_path / "chip"
    shutil.copytree(spec.HERE, base, ignore=shutil.ignore_patterns(
        "tests", "testdata", "__pycache__"))
    cfg = dict(spec.config("qwen3-0.6b"), name="qwen3-0.6b-32k")
    (base / "configs" / "qwen3-0.6b-32k.json").write_text(json.dumps(cfg))
    mix = dict(spec.traffic("chat-poisson"), rate_per_s=1.0,
               why="a new mix")
    (base / "traffic" / "chat-slow.json").write_text(json.dumps(mix))
    (base / "metrics" / "first_token_count.py").write_text(
        "def read(run):\n    return len(run.record.requests)\n")
    assert spec.config("qwen3-0.6b-32k", base)["name"] == "qwen3-0.6b-32k"
    assert spec.traffic("chat-slow", base)["rate_per_s"] == 1.0
    read = spec.reader("first_token_count.slow", base)
    assert read(type("R", (), {"record": type("X", (), {
        "requests": [1, 2]})()})()) == 2
    with pytest.raises(KeyError):
        spec.reader("no_such_metric", base)


def test_unknown_device_kind_is_an_error():
    assert spec.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(spec.UnknownDevice, match="TPU v9"):
        spec.peaks("TPU v9")
    with pytest.raises(spec.UnknownDevice):
        spec.peaks("cpu")


def test_unknown_workload_is_an_error(bench):
    with pytest.raises(KeyError, match="no workload"):
        spec.workload(bench, "nope")
