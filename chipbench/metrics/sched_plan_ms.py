"""Scheduler: host ms per step spent in ``schedule`` and ``plan_mixed``,
from the benchmark's spans around those calls, over the window's steps."""


def read(run):
    lo, hi = run.win.start, run.win.end
    s1, _ = run.spans.total("schedule", lo, hi)
    s2, n = run.spans.total("plan", lo, hi)
    return 1e3 * (s1 + s2) / n if n else None
