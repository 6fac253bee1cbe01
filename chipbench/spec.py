"""Find a cell's pieces by name: its entry in ``BENCHMARK.json``, its
configuration, traffic mix and rate files, and its metrics."""

from __future__ import annotations

import dataclasses
import json
import pathlib

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # configs/<config>.json
    traffic: dict           # traffic/<mix>.json
    rate: dict              # cells/<cell>.json
    end_to_end: list        # BENCHMARK.json metrics this cell reports
    per_layer: list


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT,
              here: pathlib.Path = HERE) -> Cell:
    """The cell ``name``.  Raises KeyError for a cell that
    ``BENCHMARK.json`` does not list."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    entry = {w["name"]: w for w in bench["workloads"]}.get(name)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    config = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    return Cell(
        name=name, chips=int(entry["chips"]),
        config=json.loads((root / config["file"]).read_text()),
        traffic=json.loads(
            (here / "traffic" / f"{entry['traffic']}.json").read_text()),
        rate=json.loads((here / "cells" / f"{name}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])
