"""In-program spans and per-request events of the serve engine.

A :class:`Tracer` keeps its records in memory, on ``time.perf_counter``.
It is off by default: then ``span()`` hands back one shared null context
and ``event()`` returns at once, so the engine's span sites cost a
method call each.  Turn it on for a measurement and read the records
afterwards::

    engine.tracer = Tracer(on=True)
    ...  # submit() / step()
    engine.tracer.on = False
    steps = [s for s in engine.tracer.spans if s.name == "step"]

While it is on:

* ``span(name)`` records a :class:`Span` with its parent (the index of
  the innermost span open around it) and its step index (given to the
  root span, inherited by its children);
* ``event(rid, kind)`` records ``(kind, time)`` under the request id;
* Python's garbage collector passes are recorded as ``gc`` spans, so a
  stall from collection shows as one;
* with ``annotate`` set (by whoever runs ``jax.profiler``), every span
  is also a ``jax.profiler.TraceAnnotation("repro.<name>")`` and lands
  on the profiler's host plane, on the device trace's clock.

Records grow while the tracer is on; turn it off (which also removes the
garbage-collector hook) when the measurement is done.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import time

clock = time.perf_counter
PREFIX = "repro."        # prefix of the annotations in a profiler trace

_NULL = contextlib.nullcontext()


@dataclasses.dataclass(slots=True)
class Span:
    name: str
    start: float             # seconds on ``clock``
    end: float | None        # None while the span is open
    parent: int | None       # index in ``Tracer.spans`` of the enclosing span
    step: int | None         # engine step the span belongs to


class Tracer:
    """Spans and request events; see the module docstring."""

    def __init__(self, on: bool = False):
        self.spans: list[Span] = []
        self.events: dict[int, list[tuple[str, float]]] = {}
        self.annotate = False
        self._open: list[int] = []
        self._gc_start: float | None = None
        self._on = False
        self.on = on

    @property
    def on(self) -> bool:
        return self._on

    @on.setter
    def on(self, value: bool) -> None:
        value = bool(value)
        if value and not self._on:
            gc.callbacks.append(self._gc)
        elif self._on and not value:
            gc.callbacks.remove(self._gc)
            self._gc_start = None
        self._on = value

    def span(self, name: str, step: int | None = None):
        """Context that records ``name`` from entry to exit (a no-op while
        the tracer is off).  ``step`` defaults to the enclosing span's."""
        if not self._on:
            return _NULL
        return _Open(self, name, step)

    def event(self, rid: int, kind: str, t: float | None = None) -> None:
        """Record ``kind`` for request ``rid`` at ``t`` (default: now)."""
        if self._on:
            self.events.setdefault(rid, []).append(
                (kind, clock() if t is None else t))

    def _push(self, name: str, step: int | None) -> Span:
        parent = self._open[-1] if self._open else None
        if step is None and parent is not None:
            step = self.spans[parent].step
        sp = Span(name, clock(), None, parent, step)
        # the index is taken after the append: a collection pass that
        # starts while ``sp`` is made appends its ``gc`` span first
        self.spans.append(sp)
        self._open.append(len(self.spans) - 1)
        return sp

    def _gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_start = clock()
        elif self._gc_start is not None:
            parent = self._open[-1] if self._open else None
            step = self.spans[parent].step if parent is not None else None
            self.spans.append(
                Span("gc", self._gc_start, clock(), parent, step))
            self._gc_start = None


class _Open:
    """One open span of an on tracer."""

    __slots__ = ("tracer", "name", "step", "record", "ann")

    def __init__(self, tracer: Tracer, name: str, step: int | None):
        self.tracer, self.name, self.step = tracer, name, step
        self.ann = None

    def __enter__(self):
        tr = self.tracer
        if tr.annotate:
            import jax

            self.ann = jax.profiler.TraceAnnotation(PREFIX + self.name)
            self.ann.__enter__()
        self.record = tr._push(self.name, self.step)
        return self

    def __exit__(self, *exc):
        tr = self.tracer
        self.record.end = clock()
        tr._open.pop()
        if self.ann is not None:
            self.ann.__exit__(*exc)
        return False
