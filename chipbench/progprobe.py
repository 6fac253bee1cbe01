#!/usr/bin/env python3
"""Runs of a cell with the engine's own tracer on, read by the readers of
the program's records, in one process.

    python3 chipbench/progprobe.py --workload <cell> --seconds <s> \
        --seeds 1,2 --modes off,on,traced

``run.py`` leaves the engine's tracer off and hands its readers no
``program``.  This tool runs ``run.run_cell`` with both, from outside:
the tracer on from the engine's build, ``annotate`` while the profiler
runs, the ``repro.`` spans of the trace, and the labels of the packed
step's executable.  For each seed it makes one run per mode, in the
order given (``off``: tracer off, untraced; ``on``: tracer on,
untraced; ``traced``: tracer on, with the profiler).

Prints one JSON line per run: the cell's metrics; the mean step on the
benchmark's own ``step`` spans and its stalls (steps over
``progtrace.STALL`` times their median), counted alike with the tracer
on and off; with the tracer on, the program's readers, the host ms of
each child span per step, the split of TTFT per request, and the
``[stall]`` lines on stderr; traced, the device ms per step of each
label and the idle gaps by program span.  Every run's ``setup_s`` counts
from the process's start, so only the first one's is a set-up time.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import statistics
import sys
import types
import weakref
from unittest import mock

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

from chipbench import loop, measure, progtrace, run, xtrace  # noqa: E402

READERS = ("queue_wait_p95_ms.chat", "prefill_drain_p95_ms.chat",
           "step_host_ms.chat", "attn_gather_ms.chat",
           "rns_mlp_ms.saturated", "layer_scan_ms.saturated")
LABELS = progtrace.SCOPES + (progtrace.SCAN, progtrace.BARE,
                             progtrace.NO_METADATA)


def stalls(spans, lo: float, hi: float) -> tuple[int, float | None]:
    """(steps over ``STALL`` times the median, ms of the longest), on the
    benchmark's ``step`` spans that ended in [lo, hi)."""
    ds = [b - a for n, a, b in spans.records if n == "step" and lo <= b < hi]
    if not ds:
        return 0, None
    med = statistics.median(ds)
    return sum(d > progtrace.STALL * med for d in ds), 1e3 * max(ds)


def child_ms(tracer, lo: float, hi: float) -> dict[str, float]:
    """Mean host ms per step of each child span of ``step``."""
    steps = progtrace.step_spans(tracer, lo, hi)
    tot: dict[str, float] = {}
    for _, kids in steps:
        for k in kids:
            tot[k.name] = tot.get(k.name, 0.0) + (k.end - k.start)
    return {k: 1e3 * v / len(steps) for k, v in tot.items()}


def ttft_split(tracer, states, lo: float, hi: float) -> dict | None:
    """Medians in ms, over the requests due in [lo, hi) that have a first
    token: generator lag (submit − due), queue wait (first admit −
    submit), prefill drain (first token − first admit), each request's
    sum of the three, and TTFT (its first token out of ``step()`` − due);
    and the longest time between the loop's clock read before
    ``engine.submit`` and the engine's own ``submit`` event, which is no
    work at all unless the process was held."""
    # request ids count up in the order the loop submits its states
    rids = sorted(rid for rid, evs in tracer.events.items()
                  if evs and evs[0][0] == "submit")
    sent = [s for s in states if s.submitted > 0]
    rids = rids[len(rids) - len(sent):]     # after the warm-up request
    rows, held = [], 0.0
    for rid, st in zip(rids, sent):
        first: dict[str, float] = {}
        for kind, at in tracer.events[rid]:
            first.setdefault(kind, at)
        held = max(held, first["submit"] - st.submitted)
        if not (lo <= st.due < hi and st.times) or "first_token" not in first:
            continue
        lag, wait = st.submitted - st.due, first["admit"] - first["submit"]
        drain = first["first_token"] - first["admit"]
        rows.append((lag, wait, drain, lag + wait + drain,
                     st.times[0] - st.due))
    if not rows:
        return None
    names = ("gen_lag", "queue_wait", "prefill_drain", "sum", "ttft")
    out = {k: 1e3 * statistics.median(c) for k, c in zip(names, zip(*rows))}
    out.update(n=len(rows), submit_held_max=1e3 * held)
    return out


def device_split(data) -> dict:
    """Device ms per run of the packed step by label, and the leaf ops
    of the scan and of no metadata that take the most of it."""
    got = progtrace.step_ops(data)
    if got is None:
        return {}
    runs, ops = got
    by_op: dict[tuple, float] = {}
    for label, e in ops:
        if label in (None, progtrace.SCAN, progtrace.NO_METADATA):
            k = (str(label), xtrace.short_name(e.name))
            by_op[k] = by_op.get(k, 0.0) + e.dur * 1e-6 / runs
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:16]
    return {"label_ms": {k: progtrace.scoped_ms(data, {k}) for k in LABELS},
            "unlabelled_ops": sum(1 for label, _ in ops if label is None),
            "top_ops_ms": [[label, name, ms] for (label, name), ms in top]}


def gaps_s(tr) -> dict[str, float]:
    got = xtrace.attribute_gaps(tr)
    return {k: v * 1e-9 for k, v in sorted(got.items(), key=lambda kv: -kv[1])}


def probe(workload: str, seed: int, seconds: float, mode: str,
          **kw) -> dict:
    """One run of ``workload`` in ``mode``; ``kw`` goes to ``run_cell``."""
    from repro.serve.trace import Tracer

    cap: dict = {}
    traced = mode == "traced"

    def hook(engine):
        if mode != "off":
            engine.tracer = Tracer(on=True)
        cap["tracer"] = engine.tracer
        if traced:          # released before the check frees the engine
            cap["engine"], cap["mixed"] = weakref.ref(engine), engine._mixed

    class Spans(loop.Spans):
        """The benchmark's spans; the engine's tracer annotates while
        they do, that is while the profiler runs."""

        def __init__(self):
            super().__init__()
            cap["spans"] = self

        @property
        def annotate(self):
            return self.__dict__.get("_annotate", False)

        @annotate.setter
        def annotate(self, on):
            self._annotate = on
            cap["tracer"].annotate = on

    drive_, load_, rundata_ = loop.drive, xtrace.load, measure.RunData

    def drive(*a, **k):
        out = drive_(*a, **k)
        cap["end"] = loop.clock()
        cap["tracer"].on = False
        cap["win"], cap["states"], _ = out
        return out

    def load(directory):
        tr = load_(directory)
        # from_profile keeps the host events under xtrace.SPAN, as read
        # when it runs: the same walk, for the program's prefix
        with mock.patch.object(xtrace, "SPAN", progtrace.PREFIX):
            cap["pspans"] = load_(directory).spans
        return tr

    def rundata(**fields):
        data = rundata_(**fields)
        engine = cap.pop("engine")()
        labels = progtrace.step_labels(cap.pop("mixed"), engine.params,
                                       engine.cache, fields["budget"])
        data.program = progtrace.Program(cap["tracer"], cap["end"],
                                         cap.get("pspans", []), labels)
        cap["data"] = data
        return data

    with contextlib.ExitStack() as stack:
        for obj, name, new in ((loop, "Spans", Spans), (loop, "drive", drive),
                               (xtrace, "load", load),
                               (measure, "RunData", rundata)):
            stack.enter_context(mock.patch.object(obj, name, new))
        res = run.run_cell(workload, seed, seconds, traced, hook=hook, **kw)
    cap.pop("mixed", None)

    data, win, tracer = cap.get("data"), cap["win"], cap["tracer"]
    lo, hi = win.start, (data.win.end if data is not None else win.end)
    total, n = cap["spans"].total("step", lo, hi)
    out = {"mode": mode, "workload": workload, "seed": seed,
           "correct": res["correct"],
           "metrics": {k: v["value"] for k, v in res["metrics"].items()},
           "step_wall_ms": 1e3 * total / n if n else None}
    out["stalls"], out["step_max_ms"] = stalls(cap["spans"], lo, hi)
    if mode == "off":
        return out
    lines = progtrace.stall_lines(tracer, lo, hi)
    for line in lines:
        print(line, file=sys.stderr, flush=True)
    if data is None:
        data = types.SimpleNamespace(
            win=loop.Window(lo, hi), trace=None,
            program=progtrace.Program(tracer, cap["end"]))
    out.update(program_stalls=len(lines), child_ms=child_ms(tracer, lo, hi),
               ttft_split_ms=ttft_split(tracer, cap["states"], lo, hi),
               readers={k: measure.reader(k)(data) for k in READERS})
    if traced and data.trace is not None:
        out.update(device_split(data), labels=len(data.program.labels),
                   idle_gaps=gaps_s(data.trace),
                   idle_gaps_program=gaps_s(progtrace.program_trace(
                       data.trace, data.program.spans)))
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--modes", default="off,on")
    args = ap.parse_args()
    modes = args.modes.split(",")
    if not set(modes) <= {"off", "on", "traced"}:
        ap.error(f"--modes: off, on or traced, not {args.modes!r}")
    for seed in [int(s) for s in args.seeds.split(",")]:
        for mode in modes:
            print(json.dumps(probe(args.workload, seed, args.seconds, mode)),
                  flush=True)


if __name__ == "__main__":
    main()
