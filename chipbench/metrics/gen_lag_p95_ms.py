"""Load generator: 95th percentile of how late requests were submitted
(submit time minus due time), in ms, over the requests due in the
window's untraced part.  The loop submits between steps, so a long step
shows here."""

from chipbench import measure


def read(run):
    lags = [s.submitted - s.due for s in run.states
            if run.win.start <= s.due < run.win.end]
    return 1e3 * measure.p95(lags) if lags else None
