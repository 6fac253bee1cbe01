"""Engine host loop: mean host ms per step outside the device wait, from
the engine's own spans: each ``step`` span less its ``readback`` child
(which waits for the device and copies the lanes' tokens back), over
the steps that ended in the window's untraced part."""

from chipbench import progtrace


def read(run):
    prog = progtrace.of(run)
    if prog is None:
        return None
    steps = progtrace.step_spans(prog.tracer, run.win.start, run.win.end)
    if not steps:
        return None
    spans = prog.tracer.spans
    own = [spans[i].end - spans[i].start
           - sum(k.end - k.start for k in kids if k.name == "readback")
           for i, kids in steps]
    return 1e3 * sum(own) / len(own)
