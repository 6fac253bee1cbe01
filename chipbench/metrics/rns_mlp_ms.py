"""Model step: device ms per run of the packed step spent under the
``mlp`` scope (the resident residue chain: fused digit dots, their
padding, the gate), from the trace."""

from chipbench import progtrace


def read(run):
    return progtrace.scoped_ms(run, {"mlp"})
