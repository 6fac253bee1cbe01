"""End-to-end numbers from the open loop's record, and the context that
the per-layer readers in ``metrics/`` get."""

from __future__ import annotations

import dataclasses
import importlib.util

import numpy as np

from chipbench.spec import HERE


def tokens_per_s(states, win) -> float:
    """Tokens that came out in the window, of every request, over the
    window's length."""
    n = sum(1 for s in states for t in s.times if win.start <= t < win.end)
    return n / (win.end - win.start)


def ttfts(states, deadline: float) -> tuple[list[float], int]:
    """(seconds from due to first token, of every request due in the
    window; requests with no first token by ``deadline``)."""
    out, missing = [], 0
    for s in states:
        if not s.in_window:
            continue
        if s.times and s.times[0] <= deadline:
            out.append(s.times[0] - s.due)
        else:
            missing += 1
            out.append(deadline - s.due)    # a lower bound: it missed
    return out, missing


def itls(states, win) -> list[float]:
    """Every gap between consecutive tokens of one request whose later
    token came out in the window."""
    out = []
    for s in states:
        t = s.times
        out.extend(b - a for a, b in zip(t, t[1:])
                   if win.start <= b < win.end)
    return out


def p95(xs) -> float:
    return float(np.percentile(np.asarray(xs, float), 95))


@dataclasses.dataclass
class RunData:
    """What a per-layer reader may read."""
    dims: object            # model.Dims
    budget: int             # packed lanes per step
    digits: int             # residue digits of the profile
    peak: dict              # the device's row of device.PEAKS
    win: object             # loop.Window: the part before the profiler
    states: list            # loop.ReqState
    steps: list             # loop.StepRecord
    spans: object           # loop.Spans
    trace: object | None    # xtrace.Trace of the traced run

    def window_steps(self):
        return [s for s in self.steps
                if self.win.start <= s.end < self.win.end]


def reader(name: str):
    """The reader of metric ``name``: ``metrics/<name>.py``, else the
    file of the part before the first dot, which reads the same quantity
    in every kind of cell."""
    for stem in (name, name.split(".")[0]):
        path = HERE / "metrics" / f"{stem}.py"
        if path.exists():
            spec = importlib.util.spec_from_file_location(
                f"chipbench.metrics.{stem.replace('.', '_')}", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            return mod.read
    raise KeyError(f"no reader for metric {name!r} under metrics/")
