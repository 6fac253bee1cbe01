"""Model step: device ms per run of the packed step spent under the
``attn_gather`` scope (the page gathers of K and V and their per-lane
index), from the trace."""

from chipbench import progtrace


def read(run):
    return progtrace.scoped_ms(run, {"attn_gather"})
