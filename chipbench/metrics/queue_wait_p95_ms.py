"""Scheduler: 95th percentile, in ms, of how long a request waited for a
slot (its first ``admit`` minus its ``submit``, from the engine's own
request events), over the requests submitted in the window's untraced
part.  One never admitted counts at its wait up to the loop's end."""

from chipbench import measure, progtrace


def read(run):
    reqs = progtrace.requests(run)
    if not reqs:
        return None
    end = progtrace.of(run).end
    return 1e3 * measure.p95([(a if a is not None else end) - s
                              for s, a, _ in reqs])
