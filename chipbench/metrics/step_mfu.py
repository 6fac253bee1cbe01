"""Model step: model FLOPs of the tokens the window's steps processed,
over the window's seconds, over the chip's bf16 peak, in %.  The FLOPs
are the architecture's count (``model_flops`` of ``archs/<arch>.py``: for
a dense model 2 per matmul weight per token plus attention at each
token's context), whatever numeric path runs them."""


def read(run):
    steps = run.window_steps()
    if not steps:
        return None
    f = run.arch.model_flops(run.dims, sum(s.lanes for s in steps),
                             sum(s.context for s in steps))
    secs = run.win.end - run.win.start
    return 100.0 * f / (secs * run.peak["bf16_flops"])
