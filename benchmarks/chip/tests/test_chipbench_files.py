"""Every configuration, traffic mix, cell and metric named in
BENCHMARK.json has its files, and every name and unit keeps to the
allowed characters."""
from __future__ import annotations

import importlib
import os
import re

import pytest

from chip import harness

BENCH = harness.load_json(harness.ROOT, "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def _stems(sub: str, ext: str) -> list:
    """Names of the files ``<sub>/*<ext>`` under the benchmark: a
    configuration, traffic mix or metric need not be in a cell yet."""
    return sorted(f[:-len(ext)] for f in os.listdir(os.path.join(
        harness.HERE, sub)) if f.endswith(ext) and not f.startswith("_"))


def _line(s: str) -> bool:
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks/chip"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert BENCH["command"] == ["python3", "benchmarks/chip/run.py"]


def test_names_units_and_lines():
    names = [c["name"] for c in BENCH["configs"]] + CELLS + \
        [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for w in BENCH["workloads"]:
        assert NAME.match(w["traffic"]) and _line(w["why"])
        assert w["chips"] in (1, 4)
    for c in BENCH["configs"]:
        assert _line(c["source"]) and _line(c["why"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in BENCH["per_layer"]:
        assert _line(m["layer"])


def test_bounds():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("name", _stems("configs", ".json"))
def test_config_files(name):
    cfg = harness.load_json(harness.HERE, "configs", name + ".json")
    assert cfg["name"] == name and NAME.match(name)
    entry = next((c for c in BENCH["configs"] if c["name"] == name), None)
    if entry is not None:
        assert entry["file"] == f"benchmarks/chip/configs/{name}.json"
        assert cfg["source"] == entry["source"]
        assert cfg["reduced"] == entry["reduced"]
    importlib.import_module(f"chip.reference.{cfg['reference']}")
    for key in ("d_model", "max_len", "n_layers", "n_heads", "d_ff", "m",
                "b", "n_items"):
        assert isinstance(cfg[key], int) and cfg[key] > 0
    assert cfg["d_model"] % cfg["m"] == 0
    assert cfg["d_model"] % cfg["n_heads"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files(cell):
    files = harness.load_cell(cell)
    importlib.import_module(f"chip.drivers.{files['traffic']['driver']}")
    assert files["limits"] and all(v > 0 for v in files["limits"].values())
    # every cell reports setup_s, another end-to-end metric and a
    # per-layer metric
    e2e = {m["name"] for m in files["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert files["per_layer"]


@pytest.mark.parametrize("name", _stems("traffic", ".json"))
def test_traffic_files_name_a_driver(name):
    tr = harness.load_json(harness.HERE, "traffic", name + ".json")
    assert NAME.match(name)
    mod = importlib.import_module(f"chip.drivers.{tr['driver']}")
    assert callable(mod.Cell)


@pytest.mark.parametrize("name", _stems("metrics", ".py"))
def test_metric_reader_finds_nothing_in_an_empty_run(name):
    # a reader with nothing to read returns nothing, never a 0
    run = harness.Run(name="empty", config=harness.load_json(
        harness.HERE, "configs", "sasrec-jpq-booking.json"),
        traffic={"k": 100}, chips=1, peaks=harness.peaks("TPU v5 lite"),
        seconds=1.0)
    assert NAME.match(name)
    assert importlib.import_module(f"chip.metrics.{name}").read(run) is None


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_per_layer_metrics(metric):
    m = next(x for x in BENCH["per_layer"] if x["name"] == metric)
    mod = importlib.import_module(f"chip.metrics.{metric}")
    assert callable(mod.read)
    e2e = {x["name"]: x for x in BENCH["end_to_end"]}
    assert m["moves"] in e2e
    for cell in m["workloads"]:
        assert cell in CELLS
        assert cell in e2e[m["moves"]].get("workloads", CELLS)
    assert m["source"] in ("device_trace", "program_span",
                           "program_counter", "host_clock")


def test_files_under_paths_are_named_from_names():
    top = os.path.join(harness.ROOT, "benchmarks", "chip")
    for dirpath, _, files in os.walk(top):
        if "__pycache__" in dirpath:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(dirpath, f), harness.ROOT)
            assert re.match(r"^[A-Za-z0-9_./\-]+$", rel), rel
