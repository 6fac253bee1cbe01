"""The open loop: submit each request when it is due, step the engine
while it has work, and record when every token came out.

Each request is timed from when it was due, so a stall that delays later
submissions counts against them.  The benchmark wraps the engine
instance's scheduler calls and its packed step in spans of its own
(``Spans``); with a profiler running they also go into the trace as
``bench.<name>`` annotations.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

clock = time.perf_counter


@dataclasses.dataclass
class ReqState:
    due: float                      # absolute, on ``clock``
    in_window: bool
    prompt: object
    max_new: int
    submitted: float = 0.0
    times: list = dataclasses.field(default_factory=list)  # per token
    tokens: object = None           # served tokens, once finished


@dataclasses.dataclass
class StepRecord:
    start: float
    end: float
    lanes: int                      # prefill tokens + decode lanes
    context: int                    # keys attended, summed over lanes


class Spans:
    """Host spans around calls into the program's layers."""

    def __init__(self):
        self.records: list[tuple[str, float, float]] = []
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation("bench." + name)
            ann.__enter__()
        t = clock()
        try:
            yield
        finally:
            self.records.append((name, t, clock()))
            if ann is not None:
                ann.__exit__(None, None, None)

    def wrap(self, name: str, fn):
        def wrapped(*args, **kwargs):
            with self.span(name):
                return fn(*args, **kwargs)
        return wrapped

    def total(self, name: str, lo: float, hi: float) -> tuple[float, int]:
        """(seconds, count) of the spans ``name`` that ended in [lo, hi)."""
        ds = [b - a for n, a, b in self.records if n == name and lo <= b < hi]
        return sum(ds), len(ds)


@contextlib.contextmanager
def instrument(engine, spans: Spans, kv_module):
    """Wrap the engine's scheduler calls, table upload and packed step in
    spans while the context lasts.  Yields the per-step lane plan,
    refreshed by each ``plan_mixed``."""
    plan = {"lanes": 0, "context": 0}
    sched = engine.sched
    schedule, plan_mixed = sched.schedule, sched.plan_mixed

    def planned(*args, **kwargs):
        segs = plan_mixed(*args, **kwargs)
        plan["lanes"] = sum(s.n for s in segs)
        plan["context"] = sum(int(p) + 1 for s in segs for p in s.positions)
        return segs

    set_tables = kv_module.set_tables
    sched.schedule = spans.wrap("schedule", schedule)
    sched.plan_mixed = spans.wrap("plan", planned)
    engine._mixed = spans.wrap("mixed_dispatch", engine._mixed)
    kv_module.set_tables = spans.wrap("set_tables", set_tables)
    try:
        yield plan
    finally:
        kv_module.set_tables = set_tables


@dataclasses.dataclass
class Window:
    start: float
    end: float


def drive(engine, requests, t0: float, window_s: tuple[float, float], *,
          spans: Spans, plan: dict, tails: bool, postroll_s: float,
          on_tick=None):
    """Run the open loop.  ``requests`` are ``traffic.Request``s, due
    after ``t0``; ``window_s`` is (start, length) in seconds after ``t0``.

    Stops at the window's close, or with ``tails`` once every request due
    in the window has its first token (at most ``postroll_s`` later).
    Returns (Window, [ReqState], [StepRecord])."""
    win = Window(t0 + window_s[0], t0 + window_s[0] + window_s[1])
    states = [ReqState(t0 + r.due, r.in_window, r.prompt, r.max_new)
              for r in requests]
    live: dict[int, ReqState] = {}
    steps: list[StepRecord] = []
    nxt = 0
    while True:
        now = clock()
        if on_tick is not None:
            on_tick(now)
        while nxt < len(states) and states[nxt].due <= now:
            st = states[nxt]
            st.submitted = clock()
            live[engine.submit(st.prompt, st.max_new)] = st
            nxt += 1
        if now >= win.end and (not tails or now >= win.end + postroll_s
                               or all(s.times for s in states
                                      if s.in_window)):
            break
        if not engine.sched.has_work:
            wake = states[nxt].due if nxt < len(states) else now + 1e-3
            time.sleep(max(0.0, min(wake, win.end) - clock()))
            continue
        ts = clock()
        with spans.span("step"):
            stats = engine.step()
        t = clock()
        steps.append(StepRecord(ts, t, plan["lanes"], plan["context"]))
        for seq in engine.sched.running.values():
            _emitted(live[seq.rid], len(seq.emitted), t)
        for rid in stats["finished"]:
            st = live.pop(rid)
            st.tokens = engine.results.pop(rid)
            engine.latencies.pop(rid, None)
            engine.ttfts.pop(rid, None)
            _emitted(st, len(st.tokens), t)
    return win, states, steps


def _emitted(st: ReqState, n: int, t: float) -> None:
    if n > len(st.times):
        st.times.extend([t] * (n - len(st.times)))
