"""BENCHMARK.json resolves to files of the benchmark and keeps the shape
its runs rely on: every cell finds its configuration, traffic mix and a
reader for each metric it reports."""
from __future__ import annotations

import json
import os
import re

import pytest

from _chip_tiny import CHIP, ROOT

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def applies(metric, cell):
    return "workloads" not in metric or cell in metric["workloads"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert BENCH["paths"] == ["benchmarks/chip"]


def test_names_units_and_lengths():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(set(names)) == len(names)
    for n in names:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for x in BENCH["configs"] + BENCH["workloads"]:
        assert 1 <= len(x["why"]) <= 200 and "\n" not in x["why"], x["name"]


def test_configs_are_files_of_the_benchmark():
    for c in BENCH["configs"]:
        assert c["file"].startswith("benchmarks/chip/configs/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("cell", CELLS)
def test_each_cell_resolves(cell):
    w = next(x for x in BENCH["workloads"] if x["name"] == cell)
    assert w["chips"] in (1, 4)
    assert os.path.isfile(os.path.join(CHIP, "traffic",
                                       w["traffic"] + ".json"))
    e2e = [m["name"] for m in BENCH["end_to_end"] if applies(m, cell)]
    layer = [m for m in BENCH["per_layer"] if applies(m, cell)]
    assert "setup_s" in e2e and len(e2e) >= 2 and layer
    for m in layer:
        assert m["moves"] in e2e
    for name in e2e + [m["name"] for m in layer]:
        assert os.path.isfile(os.path.join(CHIP, "metrics", name + ".py"))


def test_bounds_and_sources():
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert "bound" not in m
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 2)
