"""BENCHMARK.json against the contract's format, and the harness's promise
that a cell, a configuration or a metric is files and entries, not code."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "chipbench")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as fh:
        return json.load(fh)


def test_keys_and_names(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"), entry["name"]))
    assert len(names) == len(set(names)), "a name appears twice"
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        assert UNIT.match(metric["unit"]), metric["unit"]
        assert metric["better"] in ("lower", "higher")
        assert metric["source"] in SOURCES
    for metric in manifest["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    assert any(m["name"] == "setup_s" for m in manifest["end_to_end"])


def test_cells_name_files_that_exist(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    pairs = set()
    for cell in manifest["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
        assert NAME.match(cell["traffic"])
        assert (cell["config"], cell["traffic"]) not in pairs
        pairs.add((cell["config"], cell["traffic"]))
        config = configs[cell["config"]]
        assert os.path.isfile(os.path.join(ROOT, config["file"]))
        stated = _json("configs", cell["config"] + ".json")
        assert stated["source"] == config["source"]
        assert stated["reduced"] == config["reduced"]
        traffic = _json("traffic", cell["traffic"] + ".json")
        assert os.path.isfile(os.path.join(BENCH, "drivers",
                                           traffic["driver"] + ".py"))
        assert os.path.isfile(os.path.join(BENCH, "references",
                                           stated["reference"] + ".py"))
    assert {c["config"] for c in manifest["workloads"]} == set(configs)
    four = [c for c in manifest["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(manifest["workloads"]) // 4)


def test_per_layer_metrics_have_readers_and_arrows(manifest):
    cells = [c["name"] for c in manifest["workloads"]]
    end = {m["name"]: m.get("workloads", cells) for m in manifest["end_to_end"]}
    assert len({m["layer"] for m in manifest["per_layer"]}) >= 3
    for metric in manifest["per_layer"]:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           metric["name"] + ".py"))
        assert "\n" not in metric["layer"] and metric["layer"].strip()
        assert metric["moves"] in end
        for cell in metric.get("workloads", cells):
            assert cell in end[metric["moves"]], (metric["name"], cell)
    for cell in cells:  # every cell reports something of each kind
        assert any(cell in m.get("workloads", cells)
                   for m in manifest["per_layer"])


def test_no_code_knows_a_cell_or_a_configuration(manifest):
    """Names of cells and configurations appear only in data: ``configs/``,
    ``traffic/`` and (as file names) ``references/``."""
    words = {c["name"] for c in manifest["workloads"] + manifest["configs"]}
    words |= {c["traffic"] for c in manifest["workloads"]}
    offenders = []
    for base, dirs, files in os.walk(BENCH):
        dirs[:] = [d for d in dirs if d not in ("configs", "traffic",
                                                "references", "__pycache__")]
        for name in files:
            if not name.endswith((".py", ".json")):
                continue
            with open(os.path.join(base, name)) as fh:
                text = fh.read()
            offenders += [(name, w) for w in words
                          if re.search(rf"(?<![\w.]){re.escape(w)}(?![\w.])",
                                       text)]
    assert not offenders, offenders
