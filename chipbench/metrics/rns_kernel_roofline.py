"""Residue kernels: the fused residue dots' least time on the chip over
their device time in the trace, in %.

The least time of a launch is the larger of its int8 digit-matmul
operations over the int8 peak and its operand and result bytes over HBM
bandwidth (``flops.fused_dot_cost``, at the packed step's lanes).  The
launches of one packed step are the architecture's (``fused_launches`` of
``archs/<arch>.py``: for a dense model wi, wg and wo of every layer); the
traced launches are matched by kernel name and counted in whole steps."""

from chipbench import flops, xtrace

KERNEL = "fused_dot"


def read(run):
    if run.trace is None:
        return None
    ns, n = xtrace.op_time(run.trace, lambda name: KERNEL in name)
    per_step = run.arch.fused_launches(run.dims, run.budget, run.digits)
    if not n or ns <= 0:
        return None
    least = sum(flops.least_time(o, b, run.peak)[0] for o, b in per_step)
    return 100.0 * least * (n / len(per_step)) / (ns * 1e-9)
