#!/usr/bin/env python3
"""Find a cell's capacity: offer its traffic at several fixed rates, one
after another on one engine, and print what each rate achieved.

    python3 chipbench/sweep.py --workload <cell> --seed <n> \
        --seconds <s> --rates 2,3,4,5

Not part of a benchmark run: the benchmark offers each cell one rate,
fixed in ``cells/<cell>.json``; this finds that rate once.  A rate is
sustained while the queue at the window's close stays short and the
completed requests keep pace with the offered ones.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

from chipbench import device, loop, measure, run, spec, traffic  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    cell = spec.load_cell(args.workload)
    dev = device.check_device(cell.chips)
    from repro.serve import kv_cache

    device.enable_compile_cache(ROOT)
    dm, cfg, scfg, params, engine = run.build(cell, args.seed)
    mix = dict(cell.traffic, postroll_s=0, tails=False)
    spans = loop.Spans()
    for rate in [float(r) for r in args.rates.split(",")]:
        reqs = traffic.build(mix, rate, args.seconds, args.seed, dm.vocab)
        t0 = loop.clock()
        with loop.instrument(engine, spans, kv_cache) as plan:
            win, states, steps = loop.drive(
                engine, reqs, t0, (float(mix["preroll_s"]), args.seconds),
                spans=spans, plan=plan, tails=False, postroll_s=0.0)
        due = [s for s in states if s.in_window]
        done = [s for s in due if s.tokens is not None]
        first = [s.times[0] - s.due for s in due if s.times]
        wsteps = [s for s in steps if win.start <= s.end < win.end]
        row = {
            "rate": rate, "offered": len(due), "completed": len(done),
            "no_first_token": len(due) - len(first),
            "waiting_at_close": len(engine.sched.waiting),
            "tokens_per_s": measure.tokens_per_s(states, win),
            "ttft_p95_ms": 1e3 * measure.p95(first) if first else None,
            "itl_p95_ms": 1e3 * measure.p95(measure.itls(states, win)),
            "lane_fill": sum(s.lanes for s in wsteps)
            / max(1, len(wsteps) * scfg.token_budget),
            "step_ms": 1e3 * (win.end - win.start) / max(1, len(wsteps)),
        }
        print(json.dumps(row), flush=True)
        engine.sched.waiting.clear()        # drop the queue, finish the rest
        while engine.sched.has_work:
            engine.step()
        engine.results.clear()
        engine.latencies.clear()
        engine.ttfts.clear()
    print(json.dumps({"device": dev}))


if __name__ == "__main__":
    main()
