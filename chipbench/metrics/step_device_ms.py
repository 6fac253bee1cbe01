"""Model step: device ms of one run of the packed step's program
(``_mixed`` over ``models/model.py:mixed_step``), from the trace."""

from chipbench import xtrace


def read(run):
    if run.trace is None:
        return None
    ns, n = xtrace.module_time(run.trace, lambda m: "_mixed" in m)
    return ns * 1e-6 / n if n else None
