"""Scheduler: share of the packed step's lanes that carry a prompt token
or a decode token, over every step of the window, in %."""


def read(run):
    steps = run.window_steps()
    if not steps:
        return None
    return 100.0 * sum(s.lanes for s in steps) / (len(steps) * run.budget)
