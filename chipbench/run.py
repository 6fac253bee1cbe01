#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the TPU this process holds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

One process, in order: the device gate (no TPU, or fewer chips than the
cell asks for: exit non-zero, no result); JAX's compilation cache in the
checkout; the weights from ``--seed`` on the device in one jitted call;
``ContinuousEngine`` as the configuration file states; one warm-up
request through the packed step; the open loop's pre-roll; the measured
window of ``--seconds``; with ``--trace 1`` a profiler trace of a few
seconds in the window's middle.  Once the window has closed and peak
memory is read, the engine is freed and a sample of the finished
requests goes through the plain reference (``check.py``).

The last line of stdout is the JSON result; the last lines of stderr
are the numbers compared, each beside its limit.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up is timed from here

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

from chipbench import (  # noqa: E402
    check, device, loop, measure, spec, traffic, xtrace)
from chipbench.model import Dims, make_weights, program_config  # noqa: E402

TRACE_SECONDS = 4.0     # length of the traced end of the window
CONTROL_BITS = 4        # the control's MLP operand bits (int4 for int8)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


class Tracer:
    """Starts the profiler at ``start``, with the benchmark's spans
    annotated into the trace from then on.  ``stop`` is called once the
    window has closed: stopping writes the trace out, which stalls the
    host for seconds."""

    def __init__(self, spans, start: float):
        self.spans, self.start = spans, start
        self.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        self.state, self.started, self.ann = 0, None, None

    def __call__(self, now: float) -> None:
        import jax

        if self.state == 0 and now >= self.start:
            self.started = loop.clock()
            jax.profiler.start_trace(self.dir)
            self.ann = jax.profiler.TraceAnnotation(xtrace.WINDOW)
            self.ann.__enter__()
            self.spans.annotate = True
            self.state = 1

    def stop(self) -> None:
        import jax

        if self.state == 1:
            self.spans.annotate = False
            self.ann.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = 2

    def read(self):
        try:
            return xtrace.load(self.dir) if self.state == 2 else None
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def warm_up(engine, rng, dm: Dims, budget: int) -> None:
    """One request through every program the window uses: two prompt
    chunks, then decode steps."""
    engine.submit(rng.integers(0, dm.vocab, budget + 1).astype("int32"), 3)
    while engine.sched.has_work:
        engine.step()
    engine.results.clear()
    engine.latencies.clear()
    engine.ttfts.clear()


def build(cell, seed: int, hook=None):
    """Weights, the engine as the configuration states, and one warm-up
    request through it."""
    import jax
    import numpy as np

    from repro.serve import engine as engine_mod

    dm = Dims.of(cell.config)
    cfg, scfg = program_config(cell.config)
    params = jax.block_until_ready(make_weights(dm, seed))
    engine = engine_mod.ContinuousEngine(params, cfg, scfg)
    if hook is not None:
        hook(engine)
    warm_up(engine, np.random.default_rng(seed), dm, scfg.token_budget)
    return dm, cfg, scfg, params, engine


def end_to_end(name: str, states, win, setup_s: float, deadline: float):
    if name == "setup_s":
        return setup_s
    if name == "tokens_per_s":
        return measure.tokens_per_s(states, win)
    if name == "ttft_p95_ms":
        return 1e3 * measure.p95(measure.ttfts(states, deadline)[0])
    if name == "itl_p95_ms":
        return 1e3 * measure.p95(measure.itls(states, win))
    raise KeyError(f"no end-to-end metric {name!r} in chipbench/run.py")


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             control_bits: int | None = None, devices=None,
             gate=device.check_device, hook=None) -> dict:
    """One run of one cell; returns the result object.  ``gate`` and
    ``hook`` (called with the built engine) let the tests drive the
    rest of a run without a chip, with the timed path broken."""
    cell = spec.load_cell(workload)
    import jax

    devices = devices or jax.devices()
    dev = gate(cell.chips, devices)
    log(f"[device] platform={dev['platform']} kind={dev['kind']} "
        f"count={dev['count']}")
    log(f"[cache] {device.enable_compile_cache(ROOT)}")
    clog = device.CompileLog()

    dm, cfg, scfg, params, engine = build(cell, seed, hook)
    log(f"[setup] engine built and warm; {clog.since((0.0, 0, 0, 0))}")

    mix, rate = cell.traffic, float(cell.rate["rate_per_s"])
    reqs = traffic.build(mix, rate, seconds, seed, dm.vocab)
    too_long = [r for r in reqs if len(r.prompt) + r.max_new > scfg.max_cache]
    if too_long:
        raise ValueError(f"{len(too_long)} requests exceed max_cache")
    pre = float(mix["preroll_s"])
    t0 = loop.clock() + 0.01
    win_start = t0 + pre
    mark = {}
    spans = loop.Spans()
    tracer = None
    if trace:
        tracer = Tracer(spans, win_start + max(0.0, seconds - TRACE_SECONDS))

    def on_tick(now):
        if "window" not in mark and now >= win_start:
            mark["window"] = clog.mark()
        if tracer is not None:
            tracer(now)

    # a traced run reports no tails, so it ends at the window's close
    tails = bool(mix.get("tails", False)) and not trace
    post = float(mix.get("postroll_s", 0.0))
    from repro.serve import kv_cache

    with loop.instrument(engine, spans, kv_cache) as plan:
        win, states, steps = loop.drive(
            engine, reqs, t0, (pre, float(seconds)), spans=spans,
            plan=plan, tails=tails, postroll_s=post, on_tick=on_tick)
    end = loop.clock()
    if tracer is not None:
        tracer.stop()
    setup_s = win.start - T_START
    in_window = clog.since(mark.get("window", clog.mark()))
    log(f"[window] steps={len(steps)} {in_window} (compiles in the "
        "window should be 0)")
    deadline = win.end + post if tails else win.end
    lags = [s.submitted - s.due for s in states if s.in_window]
    log(f"[generator] requests in window={len(lags)} lag_p95_ms="
        f"{1e3 * measure.p95(lags) if lags else 0.0!r}")
    if not tails:
        ttft = measure.ttfts(states, win.end)[0]
        log(f"[tails] not judged here: ttft_p95_ms={1e3 * measure.p95(ttft)!r}"
            f" itl_p95_ms={1e3 * measure.p95(measure.itls(states, win))!r}")

    peak_bytes = device.memory_peak_bytes(devices)
    dev["memory_peak_bytes"] = peak_bytes
    metrics = {}
    breakdown = None
    if trace:
        tr = tracer.read()
        # host-clock readers see the window up to the profiler's start
        untraced = loop.Window(win.start, tracer.started or win.end)
        data = measure.RunData(
            dims=dm, budget=scfg.token_budget,
            digits=_digits(cfg.rns.profile), peak=device.peaks(dev["kind"]),
            win=untraced, states=states, steps=steps, spans=spans, trace=tr)
        for m in cell.per_layer:
            v = measure.reader(m["name"])(data)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        if tr is not None:
            lo, hi = tr.window
            dev["busy_s"] = xtrace.busy_ns(tr) * 1e-9
            dev["window_s"] = (hi - lo) * 1e-9
            gaps = sorted(xtrace.attribute_gaps(tr).items(),
                          key=lambda kv: -kv[1])[:10]
            breakdown = {"device_ops": xtrace.top_ops(tr),
                         "idle_gaps": [[k, v * 1e-9] for k, v in gaps]}
    else:
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": end_to_end(
                m["name"], states, win, setup_s, deadline), "unit": m["unit"]}

    finished = {i: (s.prompt, s.tokens) for i, s in enumerate(states)
                if s.tokens is not None}
    del engine
    gc.collect()
    limit = float(cell.config["check"]["max_logit_gap"])
    rids = check.sample(finished, seed,
                        int(cell.config["check"]["sample_tokens"]))
    t_check = loop.clock()
    gap, ctrl, n = check.widest_gaps(params, dm, finished, rids,
                                     scfg.max_cache, bits=control_bits)
    log(f"[check] requests={len(rids)} served_tokens={n} "
        f"seconds={loop.clock() - t_check:.3f} run_s={end - T_START:.3f}")
    correct = n > 0 and gap <= limit
    checks = {"max_logit_gap": {"value": gap, "limit": limit}}
    if ctrl is not None:
        checks["control_max_logit_gap"] = {"value": ctrl, "limit": limit}
    failed = measure.ttfts(states, deadline)[1] if tails else 0
    result = {"correct": bool(correct),
              "attempted": sum(1 for s in states if s.in_window),
              "failed": failed, "metrics": metrics, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def _digits(profile: str) -> int:
    from repro.core.moduli import get_profile

    return get_profile(profile).n_digits


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    for name, c in result["checks"].items():
        log(f"check {name}={c['value']!r} limit={c['limit']!r}")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
