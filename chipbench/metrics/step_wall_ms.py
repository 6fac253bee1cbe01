"""Engine host loop: mean host ms of one ``engine.step()`` in the window,
from the benchmark's span around the call (the step ends in a blocking
readback of the step's tokens)."""


def read(run):
    t, n = run.spans.total("step", run.win.start, run.win.end)
    return 1e3 * t / n if n else None
