"""BENCHMARK.json against its format and limits, and every name in it
resolving to the file that serves it."""

from __future__ import annotations

import importlib
import json
import re
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(_dim|_rank|hidden|intermediate|latent|state|"
                   r"projection|head|expansion|per_token|n_inputs|words)")


def _metrics():
    return SPEC["end_to_end"] + SPEC["per_layer"]


def test_top_level_keys_and_paths():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["benchmarks/chip"]
    assert 1 <= SPEC["run_seconds"] <= 51
    assert isinstance(SPEC["run_seconds"], int)
    assert SPEC["command"][1].startswith(SPEC["paths"][0] + "/")
    assert len(json.dumps(SPEC)) < 64 * 1024


@pytest.mark.parametrize("name", [x["name"] for x in
                                  SPEC["configs"] + SPEC["workloads"]
                                  + SPEC["end_to_end"] + SPEC["per_layer"]]
                         + [w["traffic"] for w in SPEC["workloads"]])
def test_names_use_allowed_characters(name):
    assert NAME.match(name), name


@pytest.mark.parametrize("metric", _metrics(), ids=lambda m: m["name"])
def test_metric_fields(metric):
    assert UNIT.match(metric["unit"]), metric["unit"]
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    if metric in SPEC["end_to_end"]:
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.25
    else:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        if metric["name"].endswith("_roofline") or "mfu" in metric["name"]:
            assert metric["unit"] == "%"


def test_names_are_unique():
    for group in (SPEC["configs"], SPEC["workloads"], _metrics()):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    pairs = [(w["config"], w["traffic"]) for w in SPEC["workloads"]]
    assert len(pairs) == len(set(pairs))


def _reports(cell: str, metric: dict) -> bool:
    return cell in metric.get("workloads", [cell])


@pytest.mark.parametrize("metric", SPEC["per_layer"],
                         ids=lambda m: m["name"])
def test_moves_is_reported_by_every_cell_that_lists_the_metric(metric):
    moved = {m["name"]: m for m in SPEC["end_to_end"]}[metric["moves"]]
    cells = metric.get("workloads", [w["name"] for w in SPEC["workloads"]])
    for cell in cells:
        assert _reports(cell, moved), (metric["name"], cell)


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_cell_reports_setup_another_end_to_end_and_a_layer(cell):
    name = cell["name"]
    e2e = [m["name"] for m in SPEC["end_to_end"] if _reports(name, m)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(_reports(name, m) for m in SPEC["per_layer"])
    assert cell["chips"] == 1
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert 1 <= len(cell["why"]) <= 200


def test_every_configuration_has_a_cell_and_a_file():
    used = {w["config"] for w in SPEC["workloads"]}
    files = set()
    for conf in SPEC["configs"]:
        assert conf["name"] in used
        assert conf["file"].startswith(SPEC["paths"][0] + "/")
        assert conf["file"] not in files
        files.add(conf["file"])
        cfg = json.loads((ROOT / conf["file"]).read_text())
        assert cfg["name"] == conf["name"]
        assert cfg["reduced"] == conf["reduced"]
        for key in conf["reduced"]:
            assert not WIDTH.search(key), key


@pytest.mark.parametrize("cell", SPEC["workloads"], ids=lambda w: w["name"])
def test_cell_files_resolve_by_name(cell):
    from chip import traffic

    mix = traffic.load_mix(cell["traffic"])
    importlib.import_module(f"chip.{mix['loop']}")
    work = importlib.import_module(f"chip.work.{mix['kernel']}")
    assert work.TRACE_NAMES
    for m in _metrics():
        if _reports(cell["name"], m):
            reader = importlib.import_module(
                f"chip.metrics.{m['name'].split('.')[0]}")
            assert callable(reader.read)


def test_files_under_paths_are_named_from_name_characters():
    for path in HERE.rglob("*"):
        if "__pycache__" in path.parts or path.is_dir():
            continue
        rel = path.relative_to(ROOT).as_posix()
        assert re.match(r"^[A-Za-z0-9_./-]+$", rel), rel
