"""``BENCHMARK.json`` and the files it names: every name resolves, every rule holds.

A later change adds a cell, configuration, traffic mix or metric as new
files plus entries; this test finds each of them by name, as the harness
does.
"""

from __future__ import annotations

import json
import re
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import checks  # noqa: E402
import layout  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


def _one_line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)


def test_names_units_and_entries():
    seen = set()
    for group, keys in (("configs", {"name", "source", "file", "reduced", "why"}),
                        ("workloads", {"name", "config", "traffic", "chips", "why"})):
        for entry in SPEC[group]:
            assert set(entry) == keys, entry
            assert NAME.match(entry["name"]) and _one_line(entry["why"])
            assert (group, entry["name"]) not in seen
            seen.add((group, entry["name"]))
    metric_names = set()
    for group in ("end_to_end", "per_layer"):
        for m in SPEC[group]:
            assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
            assert m["better"] in ("lower", "higher")
            assert m["name"] not in metric_names
            metric_names.add(m["name"])
            if group == "end_to_end":
                assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
                assert m["source"] in ("host_clock", "device_trace")
                assert 0.01 <= m["bound"] <= 0.25
            else:
                assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                                  "workloads"}
                assert m["source"] in ("device_trace", "program_span", "program_counter",
                                       "host_clock")
                assert _one_line(m["layer"])
                assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}
            for w in m.get("workloads", []):
                assert w in CELLS
    assert "setup_s" in metric_names


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    cell = layout.load_cell(name)
    assert cell.chips in (1, 4) and cell.config["chips"] == cell.chips
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in reported
        assert callable(layout.metric_module(m["name"]).read)
    job = layout.job_module(cell)
    for attr in ("COLUMNS", "VALUE_DIM", "make_batch", "map_fn", "group_ids", "reference",
                 "control"):
        assert hasattr(job, attr), attr
    traffic = layout.traffic_module(cell)
    traffic.check(cell.traffic)
    checks.limits_of(cell.config)
    engine = cell.config["engine"]
    assert engine["num_slots"] * cell.config["rows_per_shard"] == \
        cell.config["job"]["rows_per_batch"]


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file(entry):
    path = ROOT / entry["file"]
    assert path.is_file() and entry["file"].startswith("bench/")
    config = json.loads(path.read_text())
    assert config["name"] == entry["name"]
    assert set(entry["reduced"]) == set(config["reduced"])
    assert _one_line(entry["source"])


def test_files_are_named_from_name_characters():
    for path in BENCH.rglob("*"):
        if "__pycache__" in path.parts or "scratch" in path.parts:
            continue
        rel = path.relative_to(ROOT).as_posix()
        assert re.match(r"^[A-Za-z0-9_./-]+$", rel), rel


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        layout.peaks("TPU v99")
    assert layout.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
