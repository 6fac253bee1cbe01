"""Serving launcher (smoke-scale on CPU; same engines at fleet scale).

Bucketed (equal-length batch, legacy):

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
        --batch 8 --prompt-len 32 --new 16

Continuous batching over the paged KV cache (mixed-length traffic):

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
        --continuous --requests 12 --prompt-lens 7,33,120 --new 16

Residue-domain MLP datapath with resident (encode-once) weights:

    PYTHONPATH=src python -m repro.launch.serve --continuous --rns rns9 \
        --resident-weights --per-layer-profiles --requests 4 --new 8

Chunked prefill (packed mixed-phase steps, no prefill/decode barrier):

    PYTHONPATH=src python -m repro.launch.serve --continuous \
        --chunked-prefill --token-budget 64 --requests 8 --new 8

Sliding-window attention with cyclic KV page reuse (long streams in a
page pool far smaller than the stream):

    PYTHONPATH=src python -m repro.launch.serve --continuous \
        --window-tokens 32 --requests 2 --prompt-lens 10 --new 96
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.configs.base import get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as M
from repro.serve.engine import ContinuousEngine, Engine, ServeConfig


def _digit_mesh(args):
    if not args.digit_shard:
        return None
    from repro.launch.mesh import make_digit_mesh

    mesh = make_digit_mesh()            # all local devices on "model"
    print(f"digit sharding over {mesh.shape['model']} device(s) "
          "(residue channels; see docs/distributed.md)")
    return mesh


def _bucketed(args, cfg, params):
    engine = Engine(params, cfg, ServeConfig(
        max_cache=args.prompt_len + args.new + 8, max_new_tokens=args.new,
        rns_backend=args.rns_backend, mesh=_digit_mesh(args),
        resident_weights=args.resident_weights,
        per_layer_profiles=args.per_layer_profiles))
    rng = np.random.default_rng(0)
    prompts = rng.integers(0, cfg.vocab, (args.batch, args.prompt_len))
    frontend = None
    if cfg.frontend:
        frontend = rng.standard_normal(
            (args.batch, cfg.n_frontend_tokens, cfg.d_model)).astype(np.float32)

    t0 = time.perf_counter()
    out = engine.generate(prompts.astype(np.int32), frontend=frontend)
    dt = time.perf_counter() - t0
    n_tok = out.shape[0] * out.shape[1]
    print(f"generated {out.shape} tokens in {dt:.2f}s "
          f"({n_tok/dt:.1f} tok/s incl. compile)")
    t0 = time.perf_counter()
    out = engine.generate(prompts.astype(np.int32), frontend=frontend)
    dt = time.perf_counter() - t0
    print(f"warm: {n_tok/dt:.1f} tok/s")
    print("sample:", out[0][:16])


def _continuous(args, cfg, params):
    lens = [int(x) for x in args.prompt_lens.split(",")]
    max_cache = max(lens) + args.new + 8
    engine = ContinuousEngine(params, cfg, ServeConfig(
        max_cache=max_cache, max_new_tokens=args.new,
        page_size=args.page_size, max_seqs=args.max_seqs,
        n_pages=args.n_pages, rns_backend=args.rns_backend,
        prefix_cache=args.prefix_cache, spec_decode=args.spec_decode,
        spec_k=args.spec_k, mesh=_digit_mesh(args),
        resident_weights=args.resident_weights,
        per_layer_profiles=args.per_layer_profiles,
        chunked_prefill=args.chunked_prefill,
        token_budget=args.token_budget, chunk_size=args.chunk_size,
        window_tokens=args.window_tokens))
    if args.resident_weights:
        from repro.models.resident import resident_profiles

        profs = sorted(set(resident_profiles(engine.params).values()))
        print(f"resident weights: encoded once at build "
              f"(profiles {profs or ['-']})")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, (lens[i % len(lens)],)).astype(
        np.int32) for i in range(args.requests)]
    res, stats = engine.run(prompts)
    print(f"served {stats['n_requests']} mixed-length requests "
          f"(lens {sorted(set(lens))}) in {stats['n_steps']} steps / "
          f"{stats['wall_s']:.2f}s -> {stats['tokens_per_s']:.1f} tok/s")
    print(f"latency p50={stats['latency_p50_s']:.3f}s "
          f"p99={stats['latency_p99_s']:.3f}s  "
          f"page util (mean)={stats['mean_page_utilization']:.2f}  "
          f"preemptions={stats['n_preemptions']}")
    if args.spec_decode:
        print(f"speculative: tokens/step={stats['tokens_per_step']:.2f} "
              f"acceptance={stats['acceptance_rate']:.2f} "
              f"(window {engine.spec_window})")
    if args.prefix_cache:
        print(f"prefix cache: hit_tokens={stats['cache_hit_tokens']} "
              f"pages_shared={stats['pages_shared']} "
              f"pages_allocated={stats['pages_allocated']} "
              f"cow_splits={stats['cow_splits']}")
    if args.window_tokens:
        print(f"sliding window: {args.window_tokens} tokens retained per "
              f"row, pages_window_evicted={stats['pages_window_evicted']}")
    if args.chunked_prefill:
        mixed = sum(1 for s in stats["steps"]
                    if s["prefill_tokens"] > 0 and s["decode_tokens"] > 0)
        print(f"chunked prefill: budget={engine.scfg.token_budget} lanes "
              f"ttft p50={stats['ttft_p50_s']:.3f}s "
              f"p95={stats['ttft_p95_s']:.3f}s  mixed steps={mixed}")
        print(f"compiles: mixed={engine._mixed._cache_size()} "
              f"(per-mix recompiles: 0)")
    else:
        decode_jit = engine._verify if args.spec_decode else engine._decode
        print(f"compiles: prefill={engine._prefill._cache_size()} "
              f"{'verify' if args.spec_decode else 'decode'}="
              f"{decode_jit._cache_size()} (per-length recompiles: 0)")
    print("sample:", res[0][:16])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new", type=int, default=16)
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching over the paged KV cache")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--prompt-lens", default="7,33,120",
                    help="comma list; requests cycle through these lengths")
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--max-seqs", type=int, default=8)
    ap.add_argument("--n-pages", type=int, default=None)
    ap.add_argument("--prefix-cache", action="store_true",
                    help="copy-on-write prefix caching: sequences sharing "
                         "a prompt prefix share physical KV pages "
                         "(continuous engine only)")
    ap.add_argument("--spec-decode", action="store_true",
                    help="self-speculative (n-gram prompt-lookup) decoding "
                         "through one jitted [R, k+1] verify step "
                         "(continuous engine only; tokens stay identical "
                         "to vanilla decode)")
    ap.add_argument("--spec-k", type=int, default=3,
                    help="draft tokens per speculative step")
    ap.add_argument("--chunked-prefill", action="store_true",
                    help="packed mixed-phase batching: prefill chunks and "
                         "decode rows share ONE jitted step over a fixed "
                         "token budget (continuous engine only; tokens "
                         "stay identical to whole-prompt prefill)")
    ap.add_argument("--token-budget", type=int, default=64,
                    help="packed lanes per mixed step (--chunked-prefill)")
    ap.add_argument("--chunk-size", type=int, default=None,
                    help="max prefill tokens per row per mixed step; must "
                         "be a multiple of --page-size")
    ap.add_argument("--window-tokens", type=int, default=None,
                    help="sliding-window attention: each row attends at "
                         "most this many trailing tokens and the scheduler "
                         "recycles KV pages behind the window (continuous "
                         "engine only; bounded page-pool occupancy for "
                         "arbitrarily long streams)")
    ap.add_argument("--rns", metavar="PROFILE", default=None,
                    help="run the MLP datapath in residues on PROFILE "
                         "(e.g. rns9); required for --rns-backend/"
                         "--resident-weights to have any effect")
    ap.add_argument("--rns-backend", default=None,
                    help="RNS execution backend override for either engine "
                         "(reference|pallas|pallas_fused|...; pallas_fused "
                         "runs the fused encode->matmul->normalize kernels)")
    ap.add_argument("--resident-weights", action="store_true",
                    help="encode RNS MLP weights once at engine build "
                         "(resident residue-domain weights: zero per-step "
                         "weight conversions, token-identical output)")
    ap.add_argument("--per-layer-profiles", action="store_true",
                    help="with --resident-weights: narrow layers encode "
                         "on fewer/smaller moduli (ledger-proved exact)")
    ap.add_argument("--digit-shard", action="store_true",
                    help="shard RNS residue channels over all local "
                         "devices (either engine; needs an RNS arch "
                         "whose digit count divides the device count)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=True)
    if args.rns:
        from repro.core.rns_matmul import RnsDotConfig

        cfg = dataclasses.replace(
            cfg, rns=RnsDotConfig(profile=args.rns, qx=8, qw=8),
            rns_targets="mlp")
    params, _ = M.init_model(jax.random.PRNGKey(0), cfg)
    if args.continuous:
        _continuous(args, cfg, params)
    else:
        _bucketed(args, cfg, params)


if __name__ == "__main__":
    main()
