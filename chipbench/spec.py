"""Find a cell's pieces by name: its entry in ``BENCHMARK.json``, its
configuration, traffic mix and rate files, its architecture module and
its metrics."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys

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
    arch: object            # archs/<arch>.py, as the configuration names


def arch(config: dict, here: pathlib.Path = HERE):
    """The module ``archs/<name>.py`` of the architecture that
    ``config`` names (``"arch"``, else ``"dense"``).  Raises KeyError for
    a name with no such file."""
    name = config.get("arch", "dense")
    path = here / "archs" / f"{name}.py"
    if not (str(name).isidentifier() and path.is_file()):
        have = sorted(p.stem for p in (here / "archs").glob("*.py")
                      if p.stem != "__init__")
        raise KeyError(f"no architecture {name!r}: looked for {path}; "
                       f"archs/ holds {have}")
    modname = f"chipbench.archs.{name}"
    prev = sys.modules.get(modname)
    if prev is not None and pathlib.Path(prev.__file__).resolve() == \
            path.resolve():
        return prev
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    # registered while it runs, for dataclasses look their module up;
    # kept only where no module of that name was there before
    sys.modules[modname] = mod
    try:
        spec.loader.exec_module(mod)
    except BaseException:
        del sys.modules[modname]
        raise
    finally:
        if prev is not None:
            sys.modules[modname] = prev
    return mod


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT,
              here: pathlib.Path = HERE) -> Cell:
    """The cell ``name``.  Raises KeyError for a cell that
    ``BENCHMARK.json`` does not list, or whose configuration names an
    architecture that ``archs/`` does not hold."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    entry = {w["name"]: w for w in bench["workloads"]}.get(name)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    config = {c["name"]: c for c in bench["configs"]}[entry["config"]]
    cfgfile = json.loads((root / config["file"]).read_text())
    return Cell(
        name=name, chips=int(entry["chips"]), config=cfgfile,
        traffic=json.loads(
            (here / "traffic" / f"{entry['traffic']}.json").read_text()),
        rate=json.loads((here / "cells" / f"{name}.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)],
        arch=arch(cfgfile, here))
