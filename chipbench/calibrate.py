#!/usr/bin/env python3
"""Readings that a cell's limit is set from, in one process.

    python3 chipbench/calibrate.py --workload <cell> --seconds <s> \
        --seeds 1,2,3

For each seed, one whole run at the cell's own load (a short window is
enough to finish the mix's longest requests), checked as every run is;
the int4 control is read on the same prompts and served tokens.  Prints
one JSON line per seed: the program's widest gap (the lower reading),
the control's (the upper reading) and the run's metrics.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parent.parent
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

from chipbench import run  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    for seed in [int(s) for s in args.seeds.split(",")]:
        r = run.run_cell(args.workload, seed, args.seconds, False,
                         control_bits=run.CONTROL_BITS)
        print(json.dumps({
            "seed": seed, "correct": r["correct"],
            "program_gap": r["checks"]["max_logit_gap"]["value"],
            "control_gap": r["checks"]["control_max_logit_gap"]["value"],
            "metrics": {k: v["value"] for k, v in r["metrics"].items()},
            "memory_peak_bytes": r["device"]["memory_peak_bytes"]}),
            flush=True)


if __name__ == "__main__":
    main()
