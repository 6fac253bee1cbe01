"""The program's own records of a run, and the numbers read from them.

Three sources, all made by the program (``repro.serve.trace`` and the
named scopes of ``models/``), none by the benchmark's wrappers:

* the engine's ``Tracer``: spans and per-request events on
  ``time.perf_counter``, the clock of ``loop.py``;
* its spans in a profiler trace, as ``repro.<name>`` host events (the
  tracer annotates them while ``annotate`` is set), on the device
  trace's clock;
* a label for each instruction of the packed step's executable, read
  from its HLO text, whose ``metadata={op_name=...}`` carries the named
  scopes: the op events of a TPU trace carry only the instruction.

A reader finds them as ``run.program`` (a :class:`Program`).  A run
without it, as on a program that has no tracer or no scopes, gives
``None`` from every reader here; none of them raises.  Nothing here
changes what ``xtrace`` reads: its spans stay the benchmark's own.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
import statistics

from chipbench import xtrace
from chipbench.device import COMPILE_EVENT

PREFIX = "repro."         # prefix of the program's spans in a trace
#: the scopes of ``models/`` around the packed step's layers
SCOPES = ("embed", "norm", "qkv", "kv_write", "attn_gather", "attn",
          "attn_out", "mlp", "logits")
# labels of the instructions under none of them
SCAN = "scan"             # in the layer scan's loop: carry and weight slices
BARE = "bare"             # named by the program outside the loop
NO_METADATA = "no_metadata"   # added by the compiler, named by nothing
STEP_MODULE = "_mixed"    # the packed step's program, as in step_device_ms
STALL = 4.0               # a step over this many times the median stalled


@dataclasses.dataclass
class Program:
    tracer: object          # repro.serve.trace.Tracer of the run
    end: float              # when the open loop stopped, on perf_counter
    spans: list = dataclasses.field(default_factory=list)   # xtrace.Event
    labels: dict = dataclasses.field(default_factory=dict)  # instr -> label


def of(run) -> Program | None:
    """The run's program records, or None where the run has none."""
    prog = getattr(run, "program", None)
    return prog if getattr(prog, "tracer", None) is not None else None


# ------------------------------------------------------------- sources ---
def program_trace(tr, spans):
    """``tr`` with the program's ``repro.`` spans in place of the
    benchmark's, the window kept, each renamed into the benchmark's
    prefix: ``xtrace.attribute_gaps`` of it gives each idle gap to the
    innermost program span around it, else ``outside_steps``."""
    own = [dataclasses.replace(s, name=xtrace.SPAN + s.name[len(PREFIX):])
           for s in spans if s.name.startswith(PREFIX)]
    return dataclasses.replace(
        tr, spans=[s for s in tr.spans if s.name == xtrace.WINDOW] + own)


_INSTR = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')


def labels_from_hlo(text: str) -> dict[str, str]:
    """Instruction name -> label, for every instruction of an HLO
    module's text: the innermost of ``SCOPES`` in its ``op_name``; else
    ``SCAN`` inside a loop, ``BARE`` outside one, ``NO_METADATA`` where
    it has no ``op_name``."""
    out = {}
    for line in text.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        op = _OP_NAME.search(line)
        if op is None:
            out[m.group(1)] = NO_METADATA
            continue
        path = op.group(1).split("/")
        names = [p for p in path if p in SCOPES]
        out[m.group(1)] = (names[-1] if names
                           else SCAN if "while" in path else BARE)
    return out


def step_labels(mixed, params, cache, budget: int) -> dict[str, str]:
    """``labels_from_hlo`` of the executable that ran as the packed step
    (``mixed`` is the engine's jitted ``_mixed``, after the run): JAX
    hands it back from its in-memory cache for the same arguments.
    Empty where lowering them compiled anew, which would not be the
    executable that ran."""
    import jax
    import numpy as np

    compiled = []

    def seen(event, secs, **_):
        if event == COMPILE_EVENT:
            compiled.append(secs)

    z = np.zeros((budget,), np.int32)
    zb = np.zeros((budget,), bool)
    jax.monitoring.register_event_duration_secs_listener(seen)
    try:
        text = mixed.lower(params, z, z, z, zb, zb, cache).compile().as_text()
    finally:
        jax.monitoring.unregister_event_duration_listener(seen)
    return {} if compiled else labels_from_hlo(text)


# ------------------------------------------------------- request events ---
def requests(run) -> list[tuple[float, float | None, float | None]]:
    """(submit, first admit, first first_token) of every request submitted
    in the run's untraced window; None where the event never came."""
    prog = of(run)
    if prog is None:
        return []
    out = []
    for evs in prog.tracer.events.values():
        first = {}
        for kind, t in evs:
            first.setdefault(kind, t)
        s = first.get("submit")
        if s is not None and run.win.start <= s < run.win.end:
            out.append((s, first.get("admit"), first.get("first_token")))
    return out


# --------------------------------------------------------- host spans ---
def step_spans(tracer, lo: float, hi: float) -> list[tuple[int, list]]:
    """(index, [child spans]) of every ``step`` span that ended in
    [lo, hi), children in order (``gc`` passes among them)."""
    kids: dict[int, list] = {}
    for sp in tracer.spans:
        if sp.parent is not None:
            kids.setdefault(sp.parent, []).append(sp)
    return [(i, kids.get(i, [])) for i, sp in enumerate(tracer.spans)
            if sp.name == "step" and sp.parent is None
            and sp.end is not None and lo <= sp.end < hi]


def stall_lines(tracer, lo: float, hi: float):
    """One ``[stall]`` line for each step of [lo, hi) whose ``step`` span
    is over ``STALL`` times the median: each child's ms, and the ms of
    collector passes anywhere inside the step."""
    steps = step_spans(tracer, lo, hi)
    if not steps:
        return []
    dur = {i: tracer.spans[i].end - tracer.spans[i].start for i, _ in steps}
    med = statistics.median(dur.values())
    out = []
    for i, kids in steps:
        if dur[i] <= STALL * med:
            continue
        sp = tracer.spans[i]
        parts = {}
        for k in kids:
            if k.name != "gc":
                parts[k.name] = parts.get(k.name, 0.0) + (k.end - k.start)
        gc_s = sum(g.end - g.start for g in tracer.spans
                   if g.name == "gc" and g.step == sp.step
                   and sp.start <= g.start <= sp.end)
        body = " ".join(f"{k}={1e3 * v:.3f}" for k, v in parts.items())
        out.append(f"[stall] step={sp.step} ms={1e3 * dur[i]:.3f} "
                   f"median_ms={1e3 * med:.3f} {body} gc={1e3 * gc_s:.3f}")
    return out


# ------------------------------------------------------- device scopes ---
def step_ops(run) -> tuple[int, list] | None:
    """(runs, [(label, op)]) of the packed step's runs in the traced
    window on the first device: each leaf op with its instruction's
    label, None where the labels lack the instruction.  None where the
    run has no labels, no trace, or no run of the step."""
    prog, tr = of(run), run.trace
    if prog is None or tr is None or not prog.labels:
        return None
    lo, hi = tr.window
    dev = tr.devices[0] if tr.devices else ""
    runs = sorted((e.start, e.end) for e in tr.modules
                  if e.device == dev and STEP_MODULE in e.name
                  and lo <= e.start <= hi)
    if not runs:
        return None
    starts = [a for a, _ in runs]
    ops = []
    for e in xtrace.leaf_ops([e for e in tr.ops if e.device == dev]):
        k = bisect.bisect_right(starts, e.start) - 1
        if k >= 0 and e.start < runs[k][1]:
            name = xtrace.short_name(e.name).split(" = ")[0]
            ops.append((prog.labels.get(name), e))
    return len(runs), ops


def scoped_ms(run, labels) -> float | None:
    """Device ms per run of the packed step of its leaf ops whose label
    is in ``labels``, over the traced window.  None where the labels are
    not those of the executable that ran (an op of the step has none),
    or where no op of the step carries one of ``SCOPES``: a program
    without scopes is not read as all scan."""
    got = step_ops(run)
    if got is None:
        return None
    runs, ops = got
    if any(label is None for label, _ in ops) or not any(
            label in SCOPES for label, _ in ops):
        return None
    return sum(e.dur for label, e in ops if label in labels) * 1e-6 / runs
