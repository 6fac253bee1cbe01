#!/usr/bin/env python3
"""Smoke test of the serve path on a TPU at smollm-135m's published widths.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # digit-sharded RNS serve, 4 chips

Everything runs in this one process (a chip belongs to one process).
The model is ``get_config("smollm-135m")`` at full width (30 layers,
d_model 576, GQA 9/3, d_ff 1536, vocab 49152) with seeded random
weights.  Phases on one chip, in order:

  device   platform, kind and count as JAX reports them; anything but a
           TPU exits non-zero before any other phase runs
  kernels  the 7 Pallas launches of the residue datapath, compiled, at
           the model's MLP widths, each bitwise against its ``ref.py``
  float    ``ContinuousEngine`` with chunked prefill serves 8 seeded
           prompts of 32-768 tokens, 32 new tokens each; every request
           finishes, and the whole-prompt logits are finite
  rns      the same traffic with the MLPs in residues (rns9, resident
           weights, fused Pallas kernels): token-identical to the
           reference backend, no fallback, no weight conversion

With ``--four-chips`` only the digit-sharded RNS serve runs: rns12 over
a 4-wide digit mesh, compared token for token with the same profile on
one chip of the same host.

Compile seconds, tokens/s and peak device bytes printed on the way are
smoke figures: one cold and one warm pass of a small closed batch, not a
benchmark.  The last line of stdout is the JSON result.  A failed phase
raises, and the script exits non-zero without printing it.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
ARCH = "smollm-135m"
PROMPT_LENS = (32, 64, 128, 256, 384, 512, 640, 768)
NEW_TOKENS = 32
SEED = 0
KERNEL_ROWS = 128       # activation rows of the kernel phase: one M tile
QBITS = 8               # activation and weight fixed-point bits
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def check_device(need: int) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    print(f"[device] platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{d.platform!r}); no phase was run")
    if len(devs) < need:
        raise SystemExit(f"chip_smoke: needs {need} chips, JAX sees "
                         f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


class CompileLog:
    """Backend compile seconds (persistent-cache reads included) and
    cache hits/misses, from JAX's own monitoring events."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self):
        return self.seconds, self.hits, self.misses

    def since(self, mark) -> str:
        return (f"compile_s={self.seconds - mark[0]:.3f} "
                f"cache_hits={self.hits - mark[1]} "
                f"cache_misses={self.misses - mark[2]}")


def peak_bytes() -> str:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'n/a')}"


def kernel_phase(cfg, log) -> None:
    """Each of the 7 launches, compiled, bitwise against its ref.py."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.moduli import get_profile
    from repro.core.rns import encode_int32
    from repro.kernels.rns_convert.ops import rns_convert
    from repro.kernels.rns_convert.ref import rns_convert_ref
    from repro.kernels.rns_fused import ops as fused, ref as fused_ref
    from repro.kernels.rns_matmul.ops import rns_matmul
    from repro.kernels.rns_matmul.ref import rns_matmul_ref
    from repro.kernels.rns_normalize.ops import rns_normalize
    from repro.kernels.rns_normalize.ref import rns_normalize_ref

    p = get_profile("rns9")
    M, D, N = KERNEL_ROWS, cfg.d_model, cfg.d_ff
    qmax = 2 ** (QBITS - 1) - 1
    rng = np.random.default_rng(SEED)
    x = jnp.asarray(rng.standard_normal((M, D)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((M, N)), jnp.float32)
    rows = jnp.asarray(rng.uniform(20.0, 60.0, (M, 1)), jnp.float32)
    a = encode_int32(p, rng.integers(-qmax, qmax + 1, (M, D))).astype(
        jnp.int8)
    b = encode_int32(p, rng.integers(-qmax, qmax + 1, (D, N))).astype(
        jnp.int8)
    moduli = np.asarray(p.moduli, np.int32)
    # rns_matmul_ref reads the moduli's values, so it runs op by op
    acc = rns_matmul_ref(moduli, a, b)                  # [K, M, N] int32
    s40 = jnp.float32(40.0)
    kw = {"bits": QBITS}
    # name: (compiled kernel, its ref.py oracle, operands)
    launches = {
        "rns_matmul": (
            lambda a, b: rns_matmul(p, a, b, interpret=False),
            lambda a, b: acc, (a, b)),
        "rns_fused_encode_matmul": (
            lambda x, s, b: fused.rns_fused_encode_matmul(
                p, x, s, b, interpret=False, **kw),
            lambda x, s, b: fused_ref.rns_fused_encode_matmul_ref(
                p, x, s, b, **kw), (x, rows, b)),
        "rns_fused_matmul_normalize": (
            lambda a, b: fused.rns_fused_matmul_normalize(
                p, a, b, interpret=False),
            functools.partial(fused_ref.rns_fused_matmul_normalize_ref, p),
            (a, b)),
        "rns_fused_dot": (
            lambda x, s, b: fused.rns_fused_dot(p, x, s, b, interpret=False,
                                                **kw),
            lambda x, s, b: fused_ref.rns_fused_dot_ref(p, x, s, b, **kw),
            (x, rows, b)),
        "rns_normalize": (
            lambda r: rns_normalize(p.name, r, interpret=False),
            lambda r: rns_normalize_ref(r.reshape(p.n_digits, -1),
                                        profile=p.name).reshape(M, N),
            (acc,)),
        "rns_convert[scalar scale]": (
            lambda y, s: rns_convert(p.name, y, s, interpret=False, **kw),
            lambda y, s: rns_convert_ref(y, s, profile=p.name, **kw),
            (y, s40)),
        "rns_convert[row scales]": (
            lambda y, s: rns_convert(p.name, y, s, interpret=False, **kw),
            lambda y, s: rns_convert_ref(y, s, profile=p.name, **kw),
            (y, rows)),
    }
    for name, (run, ref, args) in launches.items():
        m = log.mark()
        got = np.asarray(jax.block_until_ready(run(*args)))
        want = np.asarray(jax.jit(ref)(*args))
        same = got.dtype == want.dtype and np.array_equal(got, want)
        print(f"[kernels] {name} shape={got.shape} dtype={got.dtype} "
              f"bitwise={'yes' if same else 'NO'} {log.since(m)} (smoke)",
              flush=True)
        if not same:
            bad = int(np.sum(got != want)) if got.shape == want.shape else -1
            raise AssertionError(f"{name}: compiled kernel differs from "
                                 f"its ref.py in {bad} elements")


def prompts_for(cfg):
    import numpy as np

    rng = np.random.default_rng(SEED)
    return [rng.integers(0, cfg.vocab, (n,)).astype(np.int32)
            for n in PROMPT_LENS]


def serve_config(**kw):
    from repro.serve.engine import ServeConfig

    return ServeConfig(max_cache=1024, page_size=16, max_seqs=8,
                       max_new_tokens=NEW_TOKENS, chunked_prefill=True, **kw)


def serve(label, params, cfg, scfg, prompts, log):
    """Serve ``prompts`` twice (cold, then warm) on one engine.

    Returns (per-prompt token lists, the warm run's stats).  Every
    request must finish with all its tokens, and the warm pass must
    repeat the cold pass exactly."""
    from repro.serve.engine import ContinuousEngine

    m = log.mark()
    t0 = time.perf_counter()
    eng = ContinuousEngine(params, cfg, scfg)
    cold, _ = eng.run(prompts)
    cold_s = time.perf_counter() - t0
    compiled = log.since(m)
    warm, stats = eng.run(prompts)
    outs = [[int(t) for t in cold[r]] for r in sorted(cold)]
    again = [[int(t) for t in warm[r]] for r in sorted(warm)]
    if len(outs) != len(prompts) or any(
            len(o) != NEW_TOKENS or not all(0 <= t < cfg.vocab for t in o)
            for o in outs):
        raise AssertionError(f"{label}: not every request finished with "
                             f"{NEW_TOKENS} in-vocab tokens")
    if again != outs:
        raise AssertionError(f"{label}: the warm pass served different "
                             "tokens than the cold pass")
    print(f"[{label}] served {len(outs)} requests x {NEW_TOKENS} tokens; "
          f"{compiled} cold_s={cold_s:.3f} warm_tok_s="
          f"{stats['tokens_per_s']:.3f} warm_steps={stats['n_steps']} "
          f"{peak_bytes()} (smoke)", flush=True)
    return outs, stats


def check_rns_ops(label, stats, *, fused: bool) -> None:
    ops = stats["steps"][0]["rns_ops"]
    print(f"[{label}] rns_ops converts={ops.converts} matmuls={ops.matmuls} "
          f"normalizes={ops.normalizes} fused={ops.fused} "
          f"fallbacks={ops.fallbacks} weight_converts={ops.weight_converts}",
          flush=True)
    if ops.fallbacks or ops.weight_converts or (fused and not ops.fused):
        raise AssertionError(f"{label}: expected fused kernels, no fallback "
                             f"and no weight conversion; got {ops}")


def float_phase(cfg, params, prompts, log) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import model as M

    outs, _ = serve("float", params, cfg, serve_config(), prompts, log)
    m = log.mark()
    tokens = np.zeros((len(prompts), max(PROMPT_LENS)), np.int32)
    for i, pr in enumerate(prompts):
        tokens[i, :len(pr)] = pr
    lengths = jnp.asarray([len(pr) for pr in prompts], jnp.int32)
    logits = jax.jit(lambda p, t, n: M.prefill_ragged(
        p, cfg, {"tokens": t}, n)[0])(params, jnp.asarray(tokens), lengths)
    finite = bool(jnp.all(jnp.isfinite(logits)))
    agree = int(np.sum(np.asarray(jnp.argmax(logits, -1))
                       == np.asarray([o[0] for o in outs])))
    print(f"[float] whole-prompt logits {tuple(logits.shape)} finite="
          f"{finite}; first token equal to the engine's for {agree}/"
          f"{len(prompts)} prompts; {log.since(m)}", flush=True)
    if not finite:
        raise AssertionError("float: whole-prompt logits are not finite")


def rns_phase(cfg, params, prompts, log, profile="rns9", mesh=None) -> None:
    """Fused-kernel RNS serve against the reference backend (one chip),
    or, with ``mesh``, digit-sharded against one chip."""
    from repro.core.rns_matmul import RnsDotConfig

    rcfg = dataclasses.replace(
        cfg, rns=RnsDotConfig(profile, qx=QBITS, qw=QBITS),
        rns_targets="mlp")
    fused = functools.partial(serve_config, rns_backend="pallas_fused",
                              resident_weights=True)
    label = f"rns {profile} pallas_fused"
    got, stats = serve(label, params, rcfg, fused(), prompts, log)
    check_rns_ops(label, stats, fused=True)
    if mesh is None:
        want_label = f"rns {profile} reference"
        want, stats = serve(want_label, params, rcfg, serve_config(
            rns_backend="reference", resident_weights=True), prompts, log)
    else:
        want_label = f"rns {profile} digit-sharded x{mesh.devices.size}"
        want, stats = serve(want_label, params, rcfg, fused(mesh=mesh),
                            prompts, log)
    check_rns_ops(want_label, stats, fused=False)
    same = sum(g == w for g, w in zip(got, want))
    print(f"[rns] {label} vs {want_label}: {same}/{len(got)} requests "
          "token-identical", flush=True)
    if same != len(got):
        raise AssertionError(f"{label} and {want_label} served different "
                             "tokens")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the digit-sharded RNS serve (rns12 "
                         "over 4 chips) against the same profile on one "
                         "chip")
    args = ap.parse_args()
    device = check_device(4 if args.four_chips else 1)

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    print(f"[cache] {enable_compile_cache()}", flush=True)
    import jax

    from repro.configs.base import get_config
    from repro.core import dispatch
    from repro.models import model as M

    if dispatch.default_interpret():
        raise AssertionError("Pallas interpret mode is on for a TPU")
    log = CompileLog()
    cfg = get_config(ARCH)
    params, _ = M.init_model(jax.random.PRNGKey(SEED), cfg)
    prompts = prompts_for(cfg)
    if args.four_chips:
        from repro.launch.mesh import make_digit_mesh

        rns_phase(cfg, params, prompts, log, profile="rns12",
                  mesh=make_digit_mesh(4))
    else:
        kernel_phase(cfg, log)
        float_phase(cfg, params, prompts, log)
        rns_phase(cfg, params, prompts, log)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
