"""Model step: device ms per run of the packed step spent in ops that
the layer scan's loop itself makes, under none of the model's scopes
(slices and updates of the page pool it carries and of the stacked
weights), from the trace.  Ops the compiler adds with no ``op_name``
are not counted.  None where no op of the step carries a scope, so that
a program without scopes is not read as all scan."""

from chipbench import progtrace


def read(run):
    return progtrace.scoped_ms(run, {progtrace.SCAN})
