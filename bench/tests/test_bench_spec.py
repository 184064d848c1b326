"""BENCHMARK.json against the benchmark's contract, and every file it
names present."""

import importlib.util
import json
import re

import pytest

from bench import harness

SPEC = json.load(open(harness.ROOT / "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_top_level_keys():
    assert list(SPEC) == ["command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51 and isinstance(SPEC["run_seconds"], int)
    assert len(json.dumps(SPEC)) < 64 * 1024


def test_names_are_unique_and_well_formed():
    names = [c["name"] for c in SPEC["configs"]]
    names += [w["name"] for w in SPEC["workloads"]]
    names += [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    for w in SPEC["workloads"]:
        names += [w["config"], w["traffic"]]
    for c in SPEC["configs"]:
        names += c["reduced"]
    assert all(NAME.match(n) for n in names)


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_config_files(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    data = json.load(open(harness.ROOT / cfg["file"]))
    assert data["name"] == cfg["name"] and data["source"] == cfg["source"]
    width = re.compile(r"(_dim|_rank|hidden_size|intermediate_size|head|experts_per_tok)")
    assert not [k for k in cfg["reduced"] if width.search(k)]
    for key in cfg["reduced"]:
        assert key in data and key in data["published"]
    assert any(w["config"] == cfg["name"] for w in SPEC["workloads"])


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_cells_load_by_name(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    c = harness.load_cell(cell["name"])
    assert c.traffic["slots"] >= 1 and c.limits
    assert set(c.limits) <= {"max_logit_gap", "mean_logit_gap"}
    assert all(v > 0 for v in c.limits.values())
    reported = {m["moves"] for m in c.per_layer}
    assert reported <= {m["name"] for m in c.end_to_end}
    assert "setup_s" in {m["name"] for m in c.end_to_end} and len(c.end_to_end) >= 2
    assert c.per_layer


def test_metrics():
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.25
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


@pytest.mark.parametrize("metric", SPEC["per_layer"], ids=lambda m: m["name"])
def test_every_per_layer_metric_has_a_reader(metric):
    path = harness.BENCH / "metrics" / f"{metric['name']}.py"
    spec = importlib.util.spec_from_file_location("reader", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert callable(mod.read)


def test_peaks_by_device_kind():
    assert harness.peaks_for("TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(harness.Refused):
        harness.peaks_for("TPU v9 imaginary")


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_every_configuration_has_an_architecture(cfg):
    from bench import arch

    data = json.load(open(harness.ROOT / cfg["file"]))
    assert data["model_type"] in arch.names()
    assert arch.for_config(data).program_config(data).name == cfg["name"]
