"""BENCHMARK.json against the benchmark's contract, and every name in it
against the files that serve it."""
import json
import math
import os
import re

import pytest

from bench.tests.conftest import ROOT

MANIFEST = os.path.join(ROOT, "BENCHMARK.json")
with open(MANIFEST) as _f:
    MAN = json.load(_f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES_E2E = {"host_clock", "device_trace"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "config": {"name", "source", "file", "reduced", "why"},
    "workload": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}
CELLS = [w["name"] for w in MAN["workloads"]]
METRICS = MAN["end_to_end"] + MAN["per_layer"]


def _line(text, limit=200):
    return (isinstance(text, str) and 1 <= len(text) <= limit
            and "\n" not in text and "\t" not in text)


def _reports(metric, cell):
    return cell in metric.get("workloads", CELLS)


def test_top_level():
    assert set(MAN) == KEYS["top"]
    assert os.path.getsize(MANIFEST) <= 64 * 1024
    assert isinstance(MAN["run_seconds"], int)
    assert 1 <= MAN["run_seconds"] <= 51
    assert 1 <= len(MAN["paths"]) <= 16
    for p in MAN["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))


def test_command_stays_in_paths():
    cmd = MAN["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    for word in cmd[1:]:
        assert not word.startswith("/") and ".." not in word
        if "/" in word or word.endswith(".py"):
            assert any(word.startswith(p.rstrip("/") + "/")
                       for p in MAN["paths"]), word
            assert os.path.exists(os.path.join(ROOT, word))


def test_names_unique():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in MAN[group]]
        assert len(names) == len(set(names))
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("conf", MAN["configs"], ids=lambda c: c["name"])
def test_config(conf):
    assert set(conf) == KEYS["config"]
    assert NAME.match(conf["name"]) and _line(conf["source"])
    assert _line(conf["why"])
    assert conf["file"].startswith(tuple(p.rstrip("/") + "/"
                                         for p in MAN["paths"]))
    assert os.path.exists(os.path.join(ROOT, conf["file"]))
    assert len(conf["reduced"]) <= 16
    assert all(NAME.match(k) for k in conf["reduced"])
    assert any(w["config"] == conf["name"] for w in MAN["workloads"])
    files = [c["file"] for c in MAN["configs"]]
    assert files.count(conf["file"]) == 1
    stem = os.path.splitext(conf["file"])[0]
    assert os.path.exists(os.path.join(ROOT, stem + ".py")), \
        "a configuration's plain reference sits beside its file"


@pytest.mark.parametrize("cell", MAN["workloads"], ids=lambda w: w["name"])
def test_cell(cell):
    assert set(cell) == KEYS["workload"]
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["config"] in {c["name"] for c in MAN["configs"]}
    assert cell["chips"] in (1, 4) and _line(cell["why"])
    assert os.path.exists(os.path.join(ROOT, "bench", "traffic",
                                       cell["traffic"] + ".json"))
    e2e = [m["name"] for m in MAN["end_to_end"] if _reports(m, cell["name"])]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(_reports(m, cell["name"]) for m in MAN["per_layer"])


def test_four_chip_cells_at_most_half():
    four = sum(w["chips"] == 4 for w in MAN["workloads"])
    assert four <= max(1, math.floor(0.5 * len(MAN["workloads"])))


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric(metric):
    per_layer = metric in MAN["per_layer"]
    keys = KEYS["per_layer" if per_layer else "end_to_end"]
    assert set(metric) - {"workloads"} == keys
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in (SOURCES if per_layer else SOURCES_E2E)
    assert all(c in CELLS for c in metric.get("workloads", []))
    reader = os.path.join(ROOT, "bench", "metrics", metric["name"] + ".py")
    assert os.path.exists(reader), "each metric has a reader of its own"
    if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
        assert metric["unit"] == "%"
    if per_layer:
        assert _line(metric["layer"])
        moved = {m["name"]: m for m in MAN["end_to_end"]}[metric["moves"]]
        for cell in metric.get("workloads", CELLS):
            assert _reports(moved, cell), (metric["name"], cell)
    else:
        assert 0 < metric["bound"] <= 0.25


def test_layers_named_alike():
    """Metrics of one layer give the same layer name."""
    layers = {m["layer"] for m in MAN["per_layer"]}
    assert all(_line(layer) for layer in layers)
    assert len({layer.lower() for layer in layers}) == len(layers)
