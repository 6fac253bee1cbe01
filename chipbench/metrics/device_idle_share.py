"""Device: share of the traced window in which no op ran on the device,
in %."""

from chipbench import xtrace


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace.window
    return 100.0 * (1.0 - xtrace.busy_ns(run.trace) / (hi - lo))
