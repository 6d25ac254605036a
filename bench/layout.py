"""Where the benchmark's parts live, found by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, job or
per-layer metric is a file of its own, so a later change adds a cell by
adding files and entries and edits nothing that exists:

    BENCHMARK.json                  cells, metrics and bounds
    bench/configs/<config>.json     one deployment: source, job, engine settings,
                                    sizes, the limits of the correctness check
    bench/jobs/<job>.py             data generator, map function, plain reference
                                    and lower-precision control of one job kind;
                                    ``outputs`` where its rows are named by key
                                    (``bench/checks.py``)
    bench/traffic/<mix>.json        one traffic mix: ``kind`` and its parameters
    bench/traffic/<kind>.py         the generator that reads mixes of that kind
    bench/metrics/<metric>.py       ``read(run)`` for one per-layer metric
    bench/peaks.json                published peaks by ``device_kind``
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with its configuration, traffic and metrics."""

    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: list
    per_layer: list


def load_module(path: Path) -> ModuleType:
    """Import one file of the benchmark by its path."""
    if not path.is_file():
        raise FileNotFoundError(f"benchmark module {path} does not exist")
    spec = importlib.util.spec_from_file_location(f"bench_{path.parent.name}_{path.stem}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _read_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _metrics_for(metrics: list, cell: str, reported: set) -> list:
    """The metrics a cell reports: those that list it, or, unlisted, move
    an end-to-end metric it reports."""
    out = []
    for m in metrics:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif m["moves"] in reported:
            out.append(m)
    return out


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``, with its files read."""
    bench = _read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _read_json(root / configs[w["config"]]["file"])
    traffic = _read_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    reported = {m["name"] for m in e2e}
    per_layer = _metrics_for(bench["per_layer"], name, reported)
    return Cell(name=name, chips=int(w["chips"]), config_name=w["config"],
                config=config, traffic_name=w["traffic"], traffic=traffic,
                end_to_end=e2e, per_layer=per_layer)


def job_module(cell: Cell) -> ModuleType:
    return load_module(BENCH_DIR / "jobs" / f"{cell.config['job']['kind']}.py")


def traffic_module(cell: Cell) -> ModuleType:
    return load_module(BENCH_DIR / "traffic" / f"{cell.traffic['kind']}.py")


def metric_module(name: str) -> ModuleType:
    return load_module(BENCH_DIR / "metrics" / f"{name}.py")


def peaks(device_kind: str, path: Optional[Path] = None) -> dict:
    """Published peaks of ``device_kind``; a device not in the table is an error."""
    table = _read_json(path or BENCH_DIR / "peaks.json")
    if device_kind not in table["devices"]:
        raise KeyError(f"no published peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json; have {sorted(table['devices'])}")
    return table["devices"][device_kind]
