"""``BENCHMARK.json`` against the contract's limits and against the files
it names: every cell has its file, its configuration and its traffic
kind; every per-layer metric has its reader, named and labelled alike."""

import json
import os
import re

import pytest

from benchmarks.chip import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest()


def one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_shape(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["benchmarks/chip"]
    assert manifest["command"] == ["python3", "benchmarks/chip/run.py"]
    assert all(one_line(w) for w in manifest["command"])
    assert os.path.getsize(os.path.join(harness.ROOT,
                                        "BENCHMARK.json")) <= 64 * 1024
    seconds = manifest["run_seconds"]
    assert isinstance(seconds, int) and 1 <= seconds <= 51
    # a full check of the largest benchmark (24 cells) fits the driver's day
    assert (2 + 14 * 24) * (seconds + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs(manifest):
    names = [c["name"] for c in manifest["configs"]]
    assert len(names) == len(set(names)) and 1 <= len(names) <= 24
    used = {w["config"] for w in manifest["workloads"]}
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert one_line(c["source"]) and one_line(c["why"])
        assert PATH.match(c["file"])
        assert c["file"].startswith(manifest["paths"][0] + "/")
        assert len(c["reduced"]) <= 16
        body = harness.load_json(os.path.join(harness.ROOT, c["file"]))
        assert body["name"] == c["name"] and body["source"] == c["source"]
        for key in c["reduced"]:
            assert NAME.match(key) and key in body["reduced_reason"]
            # a cut is of scale, never of a shape
            assert key not in {"num_feature", "num_bins", "max_depth",
                               "objective"}


def test_workloads(manifest):
    cells = manifest["workloads"]
    names = [w["name"] for w in cells]
    assert len(names) == len(set(names)) and 2 <= len(names) <= 24
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(pairs) == len(set(pairs))
    four = [w for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and one_line(w["why"])
        cell, config = harness.load_cell(manifest, w["name"])
        kind = harness.load_kind(cell["kind"])
        for fn in ("setup", "window", "check", "end_to_end"):
            assert callable(getattr(kind, fn))
        if w["chips"] > 1:
            assert config["mesh"] == {"data": w["chips"]}


def test_metrics(manifest):
    every = manifest["end_to_end"] + manifest["per_layer"]
    names = [m["name"] for m in every]
    assert len(names) == len(set(names))
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in every:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
    readers = {mod.NAME: mod for mod in harness.layer_metric_modules()}
    assert len(readers) == len(harness.layer_metric_modules())
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        mod = readers[m["name"]]
        assert (mod.UNIT, mod.LAYER, mod.MOVES) == (m["unit"], m["layer"],
                                                    m["moves"])
        assert one_line(m["layer"]) and m["moves"] in e2e
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    # every cell reports setup_s, another end-to-end metric and a per-layer
    # metric; a per-layer metric only where the metric it moves is
    for name in cells:
        mine = {m["name"] for m in
                harness.cell_metrics(manifest, name, "end_to_end")}
        assert "setup_s" in mine and len(mine) >= 2
        layers = harness.cell_metrics(manifest, name, "per_layer")
        assert layers and all(m["moves"] in mine for m in layers)
        kind = harness.load_cell(manifest, name)[0]["kind"]
        assert all(kind in readers[m["name"]].KINDS for m in layers)


def test_every_data_file_is_named_from_the_allowed_characters():
    for folder, _, files in os.walk(harness.HERE):
        if "__pycache__" in folder:
            continue
        for f in files:
            rel = os.path.relpath(os.path.join(folder, f), harness.ROOT)
            assert PATH.match(rel), rel
            if f.endswith(".json"):
                with open(os.path.join(folder, f)) as fh:
                    json.load(fh)
