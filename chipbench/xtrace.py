"""From a profiler trace to numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
lists of events; everything after that is arithmetic on those lists, so
the tests can feed it events by hand.  Times are nanoseconds on the
trace's one clock, which the device planes and the host's
``TraceAnnotation`` spans share.
"""

from __future__ import annotations

import dataclasses
import glob
import os

SPAN = "bench."           # prefix of the benchmark's own host spans
WINDOW = SPAN + "window"  # the span that bounds the traced window


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float            # ns
    dur: float              # ns
    device: str = ""

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    ops: list               # device ops ("XLA Ops" lines), all devices
    modules: list           # device program runs ("XLA Modules" lines)
    spans: list             # the benchmark's host spans
    devices: list           # names of the device planes

    @property
    def window(self) -> tuple[float, float]:
        w = [s for s in self.spans if s.name == WINDOW]
        if not w:
            raise ValueError(f"the trace has no {WINDOW!r} span")
        return w[0].start, w[0].end


def load(directory: str) -> Trace:
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {directory}, "
                         f"found {len(paths)}")
    return from_profile(ProfileData.from_file(paths[0]))


def from_profile(pd) -> Trace:
    ops, modules, spans, devices = [], [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            devices.append(plane.name)
            for line in plane.lines:
                if line.name not in ("XLA Ops", "XLA Modules"):
                    continue
                into = ops if line.name == "XLA Ops" else modules
                for e in line.events:
                    into.append(Event(e.name, e.start_ns, e.duration_ns,
                                      plane.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN):
                        spans.append(Event(e.name, e.start_ns,
                                           e.duration_ns))
    return Trace(ops, modules, spans, devices)


def clip(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of the events' intervals that lie in [lo, hi]."""
    out = []
    for e in events:
        a, b = max(e.start, lo), min(e.end, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals) -> list[tuple[float, float]]:
    """Merge intervals into disjoint, sorted ones."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(tr: Trace) -> float:
    """Time in the window during which an op ran, averaged over the
    devices that ran any."""
    lo, hi = tr.window
    per = {}
    for e in tr.ops:
        per.setdefault(e.device, []).append(e)
    if not per:
        return 0.0
    return sum(sum(b - a for a, b in union(clip(evs, lo, hi)))
               for evs in per.values()) / len(per)


def idle_gaps(tr: Trace) -> list[tuple[float, float]]:
    """Intervals of the window in which no op ran on the first device."""
    lo, hi = tr.window
    dev = tr.devices[0] if tr.devices else ""
    busy = union(clip([e for e in tr.ops if e.device == dev], lo, hi))
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def attribute_gaps(tr: Trace) -> dict[str, float]:
    """Idle device time (ns) by what the host was doing: the innermost
    benchmark span around each idle nanosecond's gap midpoint."""
    inner = [s for s in tr.spans if s.name != WINDOW]
    out: dict[str, float] = {}
    for a, b in idle_gaps(tr):
        mid = 0.5 * (a + b)
        around = [s for s in inner if s.start <= mid <= s.end]
        name = (min(around, key=lambda s: s.dur).name[len(SPAN):]
                if around else "outside_steps")
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def op_time(tr: Trace, match) -> tuple[float, int]:
    """(summed ns, count) of the window's device ops whose instruction
    name matches (not an op that only takes one of them as operand)."""
    lo, hi = tr.window
    evs = [e for e in tr.ops
           if match(short_name(e.name).split(" = ")[0])
           and lo <= e.start <= hi]
    return sum(e.dur for e in evs), len(evs)


def module_time(tr: Trace, match) -> tuple[float, int]:
    """(summed ns, runs) of the window's program runs whose name matches,
    on the first device."""
    lo, hi = tr.window
    dev = tr.devices[0] if tr.devices else ""
    evs = [e for e in tr.modules
           if e.device == dev and match(e.name) and lo <= e.start <= hi]
    return sum(e.dur for e in evs), len(evs)


def short_name(name: str) -> str:
    """``%fusion.117 = bf16[2048,16,3,64]{...} fusion(...)`` ->
    ``fusion.117 = bf16[2048,16,3,64]``: the HLO instruction and its
    result type, without layouts and operands."""
    return name.lstrip("%").split("{", 1)[0].strip()


def leaf_ops(ops) -> list:
    """The ops that enclose no other op of their device (a loop's body
    ops, not the loop)."""
    out = []
    by_dev: dict[str, list] = {}
    for e in ops:
        by_dev.setdefault(e.device, []).append(e)
    for evs in by_dev.values():
        evs = sorted(evs, key=lambda e: (e.start, -e.dur))
        for e, nxt in zip(evs, evs[1:] + [None]):
            if nxt is None or nxt.start >= e.end:
                out.append(e)
    return out


def top_ops(tr: Trace, n: int = 10) -> list[list]:
    """The ``n`` leaf ops that took most device time in the window, in
    seconds, averaged over devices."""
    lo, hi = tr.window
    tot: dict[str, float] = {}
    for e in leaf_ops(tr.ops):
        if lo <= e.start <= hi:
            k = short_name(e.name)
            tot[k] = tot.get(k, 0.0) + e.dur
    k = max(1, len({e.device for e in tr.ops}))
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / k * 1e-9] for name, ns in best]
