# One function per paper claim. Print ``name,us_per_call,derived`` CSV.
# ``--json PATH`` additionally writes the rows as a BENCH_core.json
# artifact (normalize-ops-per-matmul amortization, tracked per commit);
# ``--serve-json PATH`` runs the mixed-length synthetic-traffic benchmark
# (benchmarks/bench_serve.py) and writes BENCH_serve.json — tokens/sec,
# p50/p99 latency, page utilization for continuous vs bucketed serving.
# ``--dist-json PATH`` runs the digit-sharded benchmark
# (benchmarks/bench_dist.py; subprocesses with 1 and 8 virtual devices)
# and writes BENCH_dist.json — residue-chain latency and serve tokens/sec
# per device count.
# ``--kernels-json PATH`` runs the fused-kernel benchmark
# (benchmarks/bench_kernels.py) and writes BENCH_kernels.json — HBM bytes
# moved and wall-clock, fused vs unfused chain, plus the recompile and
# autotune smoke rows; ``--skip-kernels`` suppresses it.
# ``--audit-json PATH`` runs ALL the static auditors over the smoke
# serve config and writes the combined reports as BENCH_audit.json —
# the exactness proof (repro.analysis.ledger_audit: headroom tables,
# per-site fallback tallies), the kernel legality/VMEM sweep
# (repro.analysis.kernel_audit: every family x autotune config, plus
# the engine's own traced launches), and the jit compile-churn proof
# (repro.analysis.trace_audit) — tracked per commit by the CI
# static-analysis job.
from __future__ import annotations

import argparse
import json
import os


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write core rows as JSON (e.g. BENCH_core.json)")
    ap.add_argument("--serve-json", default=None, metavar="PATH",
                    help="run the serve traffic benchmark, write its rows "
                         "as JSON (e.g. BENCH_serve.json)")
    ap.add_argument("--dist-json", default=None, metavar="PATH",
                    help="run the digit-sharded 1-vs-8-virtual-device "
                         "benchmark, write its rows as JSON "
                         "(e.g. BENCH_dist.json)")
    ap.add_argument("--kernels-json", default=None, metavar="PATH",
                    help="run the fused-kernel benchmark, write its rows "
                         "as JSON (e.g. BENCH_kernels.json)")
    ap.add_argument("--audit-json", default=None, metavar="PATH",
                    help="run the static auditors (exactness + kernel "
                         "legality/VMEM + trace churn) on the smoke serve "
                         "config, write the combined reports "
                         "(e.g. BENCH_audit.json)")
    ap.add_argument("--skip-core", action="store_true",
                    help="skip the core benches (serve-only run)")
    ap.add_argument("--skip-kernels", action="store_true",
                    help="skip the fused-kernel benches")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    rows = []
    serve_rows = []
    dist_rows = []
    kernel_rows = []
    sink = rows

    def report(name: str, us: float, derived: str = ""):
        sink.append({"name": name, "us_per_call": us, "derived": derived})
        print(f"{name},{us:.1f},{derived}", flush=True)

    print("name,us_per_call,derived")
    if not args.skip_core:
        from benchmarks import bench_core

        bench_core.run_all(report)

    if args.serve_json:
        from benchmarks import bench_serve

        sink = serve_rows
        bench_serve.run_all(report)
        sink = rows

    if args.dist_json:
        from benchmarks import bench_dist

        sink = dist_rows
        bench_dist.run_all(report)
        sink = rows

    if args.kernels_json and not args.skip_kernels:
        from benchmarks import bench_kernels

        sink = kernel_rows
        bench_kernels.run_all(report)
        sink = rows

    audit_blob = None
    if args.audit_json:
        import dataclasses

        import jax

        from repro.analysis.kernel_audit import (audit_all,
                                                 audit_engine_kernels)
        from repro.analysis.ledger_audit import audit_serve
        from repro.analysis.trace_audit import audit_traces
        from repro.configs.base import get_config
        from repro.core.rns_matmul import RnsDotConfig
        from repro.models import model as M
        from repro.serve.engine import ContinuousEngine, ServeConfig

        cfg = dataclasses.replace(
            get_config("smollm-135m", smoke=True),
            rns=RnsDotConfig(profile="rns9", qx=8, qw=8), rns_targets="mlp")
        params = M.init_model(jax.random.PRNGKey(0), cfg)[0]
        scfg = ServeConfig(max_cache=24, page_size=8, max_seqs=2)
        audit_report = audit_serve(params, cfg, scfg)
        h = audit_report.min_headroom
        derived = "PROVED" if audit_report.ok else "FAILED"
        if h is not None:
            derived += f" min_headroom={h:+.1f}b"
        report("exactness_audit", 0.0, derived)

        # kernel legality sweep: every family x autotune config, plus
        # the launches a built smoke engine actually traces
        kernel_report = audit_all(profiles=(cfg.rns.profile,))
        eng = ContinuousEngine(params, cfg, scfg)
        engine_kernels = audit_engine_kernels(eng)
        k_ok = kernel_report.ok and engine_kernels.ok
        report("kernel_audit", 0.0,
               ("PROVED" if k_ok else "FAILED")
               + f" configs={len(kernel_report.entries)}"
               + f" engine_phases={len(engine_kernels.entries)}")

        # jit compile-churn proof over the generated traffic family
        trace_report = audit_traces(eng)
        report("trace_audit", 0.0,
               ("PROVED" if trace_report.ok else "FAILED")
               + f" phases={len(trace_report.phases)}"
               + f" variants={trace_report.n_variants}")
        audit_blob = {
            "exactness": json.loads(audit_report.to_json()),
            "kernels": kernel_report.to_dict(),
            "engine_kernels": engine_kernels.to_dict(),
            "trace": trace_report.to_dict(),
        }

    # roofline summary from the newest dry-run artifacts
    for tag, d in (("baseline", "artifacts/dryrun"),
                   ("optimized", "artifacts/dryrun_opt")):
        if not os.path.isdir(d):
            continue
        from benchmarks import roofline

        recs = roofline.load_all(d)
        done = [r for r in recs if "skipped" not in r and not r.get("rns")]
        if done:
            worst = min(done, key=lambda r: r["roofline_frac"])
            best = max(done, key=lambda r: r["roofline_frac"])
            report(f"roofline_cells_{tag}", float(len(done)),
                   f"worst={worst['arch']}/{worst['shape']}/{worst['mesh']}"
                   f"@{worst['roofline_frac']:.4f} "
                   f"best={best['arch']}/{best['shape']}/{best['mesh']}"
                   f"@{best['roofline_frac']:.4f}")

    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=2)
        print(f"wrote {args.json}", flush=True)
    if args.serve_json:
        with open(args.serve_json, "w") as f:
            json.dump(serve_rows, f, indent=2)
        print(f"wrote {args.serve_json}", flush=True)
    if args.dist_json:
        with open(args.dist_json, "w") as f:
            json.dump(dist_rows, f, indent=2)
        print(f"wrote {args.dist_json}", flush=True)
    if args.kernels_json and not args.skip_kernels:
        with open(args.kernels_json, "w") as f:
            json.dump(kernel_rows, f, indent=2)
        print(f"wrote {args.kernels_json}", flush=True)
    if args.audit_json and audit_blob is not None:
        with open(args.audit_json, "w") as f:
            json.dump(audit_blob, f, indent=2)
        print(f"wrote {args.audit_json}", flush=True)


if __name__ == "__main__":
    main()
