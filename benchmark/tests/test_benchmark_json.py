"""BENCHMARK.json against the contract's limits, and against the files
it names."""

import json
import os
import re

import pytest

from benchmark.run import _load_reader

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter",
           "host_clock"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark"]
    assert bench["command"] == ["python3", "benchmark/run.py"]
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    rs = bench["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    # a full check with the full 24 cells must fit into 43200 seconds
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_names_units_and_lines(bench):
    names = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[group]:
            assert NAME.match(e["name"]), e["name"]
            assert (group, e["name"]) not in names
            names.add((group, e["name"]))
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for e in bench["end_to_end"]:
        assert set(e) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.1
    for e in bench["per_layer"]:
        assert set(e) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert _line(e["layer"])
        if e["name"].endswith("_roofline") or "mfu" in e["name"]:
            assert e["unit"] == "%"
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert _line(c["why"]) and _line(c["source"])
        assert len(c["reduced"]) <= 16
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert _line(w["why"]) and w["chips"] in (1, 4)
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])


def test_every_named_file_exists(bench):
    for c in bench["configs"]:
        assert c["file"].startswith("benchmark/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        # found by name, never by an import a runner spells out
        for kind, name in (("reference", cfg["correct"]["reference"]),
                           ("models", cfg["family"])):
            assert os.path.exists(os.path.join(
                ROOT, "benchmark", kind, name + ".py")), (kind, name)
    configs = {c["name"] for c in bench["configs"]}
    pairs = set()
    for w in bench["workloads"]:
        assert w["config"] in configs
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        path = os.path.join(ROOT, "benchmark", "traffic",
                            w["traffic"] + ".json")
        with open(path) as f:
            mix = json.load(f)
        for kind, name in (("runners", mix["runner"]),
                           ("generators", mix["generator"])):
            assert os.path.exists(os.path.join(
                ROOT, "benchmark", kind, name + ".py")), (kind, name)
    assert configs == {w["config"] for w in bench["workloads"]}
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)


def test_runners_find_family_generator_and_reference_by_name():
    """A later PR adds a family, a generator or a reference as a file:
    no runner may import one by a name it spells out."""
    for runner in os.listdir(os.path.join(ROOT, "benchmark", "runners")):
        if not runner.endswith(".py"):
            continue
        with open(os.path.join(ROOT, "benchmark", "runners", runner)) as f:
            text = f.read()
        for kind in ("models", "generators", "reference"):
            assert f"benchmark.{kind}" not in text, (runner, kind)
            assert f"from benchmark import {kind}" not in text


def test_runners_read_only_the_benchmarks_own_keys():
    """Every family names its published keys its own way (`n_layer`,
    `num_hidden_layers`): only the family's file reads those, or a later
    configuration of another family would have to edit a runner."""
    own = {"family", "correct", "server", "rows_per_step"}
    for runner in os.listdir(os.path.join(ROOT, "benchmark", "runners")):
        if not runner.endswith(".py"):
            continue
        with open(os.path.join(ROOT, "benchmark", "runners", runner)) as f:
            keys = set(re.findall(r'\bc\["(\w+)"\]', f.read()))
        assert keys <= own, (runner, keys - own)


def _cells_of(metric, bench):
    return set(metric.get("workloads",
                          [w["name"] for w in bench["workloads"]]))


def test_every_cell_reports_enough(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "workloads" not in e2e["setup_s"]
    assert e2e["setup_s"]["bound"] <= 0.1
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert _cells_of(m, bench) <= cells
    for cell in cells:
        others = [m for m in bench["end_to_end"]
                  if m["name"] != "setup_s" and cell in _cells_of(m, bench)]
        layer = [m for m in bench["per_layer"]
                 if cell in _cells_of(m, bench)]
        assert others and layer, cell


def test_every_layer_metric_has_a_reader_that_agrees(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        mod = _load_reader(m["name"])
        assert callable(mod.read)
        for key in ("layer", "unit", "better", "source", "moves"):
            assert mod.META[key] == m[key], (m["name"], key)
        # the metric it moves is reported wherever this one is
        assert _cells_of(m, bench) <= _cells_of(e2e[m["moves"]], bench)


def test_files_under_paths_are_named_from_allowed_characters(bench):
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for path in bench["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                assert ok.match(rel) and len(rel) <= 200, rel
