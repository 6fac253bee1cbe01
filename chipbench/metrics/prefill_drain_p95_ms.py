"""Engine host loop: 95th percentile, in ms, of how long an admitted
request's prompt took to drain through the packed lanes (its
``first_token`` minus its first ``admit``, from the engine's own request
events), over the admitted requests submitted in the window's untraced
part.  One still without a first token counts at its wait up to the
loop's end."""

from chipbench import measure, progtrace


def read(run):
    reqs = [r for r in progtrace.requests(run) if r[1] is not None]
    if not reqs:
        return None
    end = progtrace.of(run).end
    return 1e3 * measure.p95([(f if f is not None else end) - a
                              for _, a, f in reqs])
