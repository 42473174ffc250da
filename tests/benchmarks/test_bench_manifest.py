"""BENCHMARK.json against the builder's contract and against the files
it names."""

import json
import os
import re

import pytest

from benchmarks import manifest as mf

M = mf.Manifest()
DOC = M.doc
NAME, UNIT = mf.NAME_RE, mf.UNIT_RE
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
ALL_METRICS = DOC["end_to_end"] + DOC["per_layer"]
CELLS = [w["name"] for w in DOC["workloads"]]


def cells_of(metric):
    return metric.get("workloads", CELLS)


def test_top_level_keys_and_sizes():
    assert set(DOC) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(mf.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024
    assert 1 <= len(DOC["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in DOC["paths"])
    assert 1 <= len(DOC["command"]) <= 32
    for word in DOC["command"]:
        assert 1 <= len(word) <= 200 and not word.startswith("/") \
            and ".." not in word
    assert any(DOC["command"][-1].startswith(p + "/")
               for p in DOC["paths"])
    assert isinstance(DOC["run_seconds"], int) \
        and 1 <= DOC["run_seconds"] <= 51
    assert 1 <= len(DOC["configs"]) <= 24
    assert 1 <= len(DOC["workloads"]) <= 24
    assert 1 <= len(DOC["end_to_end"]) <= 16
    assert 1 <= len(DOC["per_layer"]) <= 128


def test_full_check_fits_the_drivers_time():
    runs = 2 + 14 * 24
    assert runs * (DOC["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("entry", DOC["configs"], ids=lambda c: c["name"])
def test_config_entry(entry):
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(entry["name"])
    assert any(entry["file"].startswith(p + "/") for p in DOC["paths"])
    for key in ("source", "why"):
        assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] \
            and "\t" not in entry[key]
    assert len(entry["reduced"]) <= 16
    for key in entry["reduced"]:
        assert NAME.match(key)
        assert not re.search(r"(_dim|_rank|hidden|intermediate|head)",
                             key), "a width may never be reduced"
    cfg = M.config(entry["name"])
    assert cfg["reduced"] == entry["reduced"]
    assert M.config(entry["name"], rehearsal=True)["model"].keys() \
        == cfg["model"].keys()
    assert any(w["config"] == entry["name"] for w in DOC["workloads"])
    assert os.path.exists(os.path.join(
        mf.ROOT, "benchmarks", "references", entry["name"] + ".py"))


def test_config_files_are_distinct():
    files = [c["file"] for c in DOC["configs"]]
    assert len(set(files)) == len(files)


@pytest.mark.parametrize("cell", DOC["workloads"], ids=lambda w: w["name"])
def test_cell_entry_and_its_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key])
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    M.config_entry(cell["config"])
    mix = M.traffic(cell["traffic"])
    assert os.path.exists(os.path.join(
        mf.ROOT, "benchmarks", "runners", mix["kind"] + ".py"))
    assert "rehearsal" in mix
    reported = [m["name"] for m in DOC["end_to_end"]
                if cell["name"] in cells_of(m)]
    assert "setup_s" in reported and len(reported) >= 2
    assert any(cell["name"] in cells_of(m) for m in DOC["per_layer"])


def test_names_are_unique_and_pairs_appear_once():
    for group in (DOC["configs"], DOC["workloads"], ALL_METRICS):
        names = [x["name"] for x in group]
        assert len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in DOC["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_four_chip_cells_at_most_a_quarter():
    four = sum(1 for w in DOC["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(DOC["workloads"]) // 4)


@pytest.mark.parametrize("metric", DOC["end_to_end"],
                         ids=lambda m: m["name"])
def test_end_to_end_metric(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                           "bound", "source"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.1
    assert set(cells_of(metric)) <= set(CELLS)


def test_setup_s_is_reported_by_every_cell():
    setup = [m for m in DOC["end_to_end"] if m["name"] == "setup_s"]
    assert len(setup) == 1 and "workloads" not in setup[0]


@pytest.mark.parametrize("metric", DOC["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_and_its_file(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                           "source", "layer", "moves"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in mf.SOURCES
    assert 1 <= len(metric["layer"]) <= 200 and "\n" not in metric["layer"]
    moved = [m for m in DOC["end_to_end"] if m["name"] == metric["moves"]]
    assert len(moved) == 1, "moves names one end-to-end metric"
    # the moved metric is reported in every cell where this one is
    assert set(cells_of(metric)) <= set(cells_of(moved[0]))
    spec = M.metric_file(metric["name"])
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec[key] == metric[key], key
    # which cells report it is BENCHMARK.json's alone to say, so that a
    # later PR's new cell needs no edit of the metric's file
    assert "workloads" not in spec
    assert callable(mf.resolve(spec["reader"]))


def test_layers_are_the_ones_perf_md_lists():
    with open(os.path.join(mf.ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in {m["layer"] for m in DOC["per_layer"]}:
        assert f"`{layer}`" in perf, f"PERF.md section 3 lacks {layer!r}"


def test_every_file_under_paths_is_named_from_allowed_characters():
    for p in DOC["paths"]:
        for base, dirs, files in os.walk(os.path.join(mf.ROOT, p)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), mf.ROOT)
                assert PATH.match(rel), rel


def test_json_round_trips():
    json.loads(json.dumps(DOC))
