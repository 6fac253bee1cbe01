"""Benchmarks for the paper's architectural claims (one per claim).

The paper has no task-accuracy tables; its claims are arithmetic-
architectural.  Each bench below quantifies one claim; wall times are CPU
proxies (the TPU numbers are structural: op counts / slice counts).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dispatch
from repro.core import fractional as fr
from repro.core import mrc, rns
from repro.core.moduli import PROFILES, get_profile, required_digits
from repro.core.rns_matmul import RnsDotConfig, rns_dot, rns_matmul_res


def _t(f, *args, n=5):
    f(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(n):
        out = f(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6  # us


def bench_pac_ops(report):
    """Claim 2+6: PAC ops cost O(K) (linear in precision); binary multiply
    partial products are quadratic."""
    rng = np.random.default_rng(0)
    x = rng.integers(-2**20, 2**20, 4096).astype(np.int32)
    for name in ("rns5", "rns9", "rns12", "rns18"):
        p = get_profile(name)
        rx = rns.encode_int32(p, x)
        mul = jax.jit(lambda a, b: rns.rns_mul(p, a, b))
        us = _t(mul, rx, rx)
        q = int(p.range_bits)
        binary_pp = (q // 8 + 1) ** 2
        report(f"pac_mul_{name}", us,
               f"digits={p.n_digits} bits={p.range_bits:.0f} "
               f"binary_8x8_partial_products={binary_pp}")


def bench_deferred_norm(report):
    """Claim 4: one slow normalization per product summation, not per MAC."""
    p = get_profile("rns9")
    n = 256
    rng = np.random.default_rng(1)
    xs = jnp.stack([fr.fr_encode(p, rng.uniform(-1, 1, 64).astype(np.float32))
                    for _ in range(n)])

    def deferred(xs):
        return fr.fr_dot_deferred(p, xs, xs)

    def per_mac(xs):
        acc = None
        for i in range(n):
            prod = fr.fr_mul(p, xs[i], xs[i])
            acc = prod if acc is None else fr.fr_add(p, acc, prod)
        return acc

    t_def = _t(jax.jit(deferred), xs, n=3)
    t_mac = _t(jax.jit(per_mac), xs, n=3)
    report("deferred_norm_dot256", t_def,
           f"per_mac_normalize={t_mac:.0f}us speedup={t_mac/t_def:.1f}x "
           f"slow_ops: 1 vs {n}")


def bench_exactness(report):
    """Claim 1: wide product summations are bit-exact in RNS; float accum
    drifts."""
    p = get_profile("rns9")
    rng = np.random.default_rng(2)
    for D in (4096, 65536):
        a = rng.integers(-32767, 32768, (1, D)).astype(np.int64)
        b = rng.integers(-32767, 32768, (D, 1)).astype(np.int64)
        want = int((a.astype(object) @ b.astype(object))[0, 0])
        rc = rns_matmul_res(
            "rns9", rns.encode_int32(p, a.astype(np.int32)),
            rns.encode_int32(p, b.astype(np.int32)))
        got = int(rns.decode_exact(p, np.asarray(rc))[0, 0])
        f32 = int(float((a.astype(np.float32) @ b.astype(np.float32))[0, 0]))
        bf16 = int(float(
            (a.astype(jnp.bfloat16) @ b.astype(jnp.bfloat16)).astype(
                jnp.float32)[0, 0]))
        report(f"exact_dot_n{D}", 0.0,
               f"rns_err={abs(got-want)} f32_err={abs(f32-want)} "
               f"bf16_err={abs(bf16-want)}")


def bench_conversion_overhead(report):
    """Claim 5: conversion pipelines amortize to negligible vs the matmul."""
    p = get_profile("rns9")
    rng = np.random.default_rng(3)
    for MKN in (64, 256, 1024):
        x = jnp.asarray(rng.standard_normal((MKN, MKN)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((MKN, MKN)), jnp.float32)
        cfg = RnsDotConfig(profile="rns9", qx=14, qw=14)
        t_full = _t(jax.jit(lambda x, w: rns_dot(x, w, cfg)), x, w, n=3)
        # matmul-only on pre-converted residues
        rx = rns.encode_int32(p, jnp.zeros((MKN, MKN), jnp.int32))
        t_mm = _t(jax.jit(lambda a, b: rns_matmul_res("rns9", a, b)), rx, rx,
                  n=3)
        report(f"conversion_share_{MKN}", t_full,
               f"matmul_only={t_mm:.0f}us conv+norm_share="
               f"{max(0.0, 1 - t_mm / t_full):.2f}")


def bench_precision_scaling(report):
    """Claim 6: slices grow linearly with operand bits; binary partial
    products quadratically (structural counts, hardware-independent)."""
    rows = []
    for q in (8, 16, 24, 32, 48):
        k = required_digits(4096, q, q)
        pp = max(1, (2 * q) // 8) ** 2 // 4  # 8x8 mults for a qxq multiply
        rows.append(f"{q}b:rns={k},binary={max(1,(q//8))**2}")
    report("precision_scaling", 0.0, " ".join(rows))


def bench_chain_amortization(report):
    """Tentpole claim: residue-domain chaining amortizes the slow MRC —
    normalize-ops-per-matmul is 1.0 per-op but 1/len(chain) deferred
    (RnsTensor, core/tensor.py).  Counts are structural (trace-time);
    wall times are the CPU proxy."""
    from repro.models.layers import rns_linear_chain

    rng = np.random.default_rng(6)
    cfg = RnsDotConfig(profile="rns9", qx=8, qw=8)
    x = jnp.asarray(rng.standard_normal((16, 64)), jnp.float32)
    ws = tuple(jnp.asarray(rng.standard_normal((64, 64)) / 8, jnp.float32)
               for _ in range(3))

    def per_op(x):
        y = x
        for w in ws:
            y = rns_dot(y, w, cfg)
        return y

    def deferred(x):
        return rns_linear_chain(x, ws, cfg)

    c_per = dispatch.trace_op_counts(per_op, x)
    c_def = dispatch.trace_op_counts(deferred, x)
    t_per = _t(jax.jit(per_op), x, n=3)
    t_def = _t(jax.jit(deferred), x, n=3)
    report("chain3_norm_per_matmul_deferred", t_def,
           f"norm_per_matmul={c_def.normalizes_per_matmul:.3f} "
           f"normalizes={c_def.normalizes} matmuls={c_def.matmuls} "
           f"converts={c_def.converts}")
    report("chain3_norm_per_matmul_per_op", t_per,
           f"norm_per_matmul={c_per.normalizes_per_matmul:.3f} "
           f"normalizes={c_per.normalizes} matmuls={c_per.matmuls} "
           f"converts={c_per.converts} speedup_deferred={t_per/t_def:.2f}x")


def bench_mlp_block_normalizes(report):
    """Per-residual-block slow-op budget: the deferred MLP datapath runs
    2 normalizations (gate nonlinearity + main path) vs 3 per-op."""
    import dataclasses

    from repro.models.layers import init_mlp, mlp

    rng = np.random.default_rng(7)
    p, _ = init_mlp(jax.random.PRNGKey(0), 64, 128, gated=True)
    x = jnp.asarray(rng.standard_normal((4, 8, 64)), jnp.float32)
    per_op = RnsDotConfig(profile="rns9", qx=8, qw=8)
    deferred = dataclasses.replace(per_op, defer=True)
    for tag, cfg in (("per_op", per_op), ("deferred", deferred)):
        c = dispatch.trace_op_counts(
            lambda x, cfg=cfg: mlp(p, x, gated=True, act="silu", rns=cfg), x)
        us = _t(jax.jit(
            lambda x, cfg=cfg: mlp(p, x, gated=True, act="silu", rns=cfg)),
            x, n=3)
        report(f"mlp_block_{tag}", us,
               f"norm_per_matmul={c.normalizes_per_matmul:.3f} "
               f"normalizes={c.normalizes} matmuls={c.matmuls} "
               f"converts={c.converts}")


def bench_resident_weights(report):
    """Tentpole claim (PR 6): resident residue-domain weights delete the
    per-matmul weight conversion.  Structural: weight_converts drops to
    zero.  HLO-costed on the 128x512x128 acceptance shape: fewer HBM
    bytes (no re-materialized [K, 512, 128] weight residues) at identical
    dot FLOPs.  Wall time is the CPU proxy."""
    from repro.launch.hlo_cost import analyze_hlo
    from repro.core.rns_matmul import rns_resident_dot
    from repro.models.resident import _encode_one

    rng = np.random.default_rng(8)
    cfg = RnsDotConfig(profile="rns9", qx=8, qw=8)
    x = jnp.asarray(rng.standard_normal((128, 512)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((512, 128)) / 24, jnp.float32)
    w_res = _encode_one(w, "rns9", 8, 7.0)

    re_fn = jax.jit(lambda x, w: rns_dot(x, w, cfg))
    res_fn = jax.jit(lambda x, r: rns_resident_dot(x, r, cfg))
    c_re = analyze_hlo(re_fn.lower(x, w).compile().as_text())
    c_res = analyze_hlo(res_fn.lower(x, w_res).compile().as_text())
    o_re = dispatch.trace_op_counts(lambda x: rns_dot(x, w, cfg), x)
    o_res = dispatch.trace_op_counts(
        lambda x: rns_resident_dot(x, w_res, cfg), x)
    t_re = _t(re_fn, x, w, n=5)
    t_res = _t(res_fn, x, w_res, n=5)
    report("resident_dot_128x512x128_reencode", t_re,
           f"weight_converts={o_re.weight_converts} "
           f"converts={o_re.converts} hbm_bytes={c_re['hbm_bytes']:.0f} "
           f"flops={c_re['flops']:.0f}")
    report("resident_dot_128x512x128_resident", t_res,
           f"weight_converts={o_res.weight_converts} "
           f"converts={o_res.converts} hbm_bytes={c_res['hbm_bytes']:.0f} "
           f"flops={c_res['flops']:.0f} "
           f"hbm_saved={c_re['hbm_bytes'] - c_res['hbm_bytes']:.0f}B "
           f"speedup={t_re / t_res:.2f}x")


def bench_resident_mlp_block(report):
    """Block-level structural budget: a gated MLP forward schedules 5
    conversions (2 activation + 3 weight) on the re-encode path and 2 on
    the resident path; per-layer narrow profiles additionally shrink the
    digit count the narrow layers move."""
    from repro.models.layers import init_mlp, mlp
    from repro.models.resident import encode_resident, resident_profiles

    class _Cfg:
        rns_targets = "mlp"
        rns = RnsDotConfig(profile="rns9", qx=8, qw=8)

    rng = np.random.default_rng(9)
    p, _ = init_mlp(jax.random.PRNGKey(1), 64, 128, gated=True)
    x = jnp.asarray(rng.standard_normal((4, 8, 64)), jnp.float32)
    variants = [("reencode", p)]
    for tag, kw in (("resident", {}),
                    ("resident_narrow", {"per_layer_profiles": True})):
        variants.append(
            (tag, encode_resident({"mlp": p}, _Cfg(), **kw)["mlp"]))
    for tag, pp in variants:
        c = dispatch.trace_op_counts(
            lambda x, pp=pp: mlp(pp, x, gated=True, act="silu",
                                 rns=_Cfg.rns), x)
        us = _t(jax.jit(
            lambda x, pp=pp: mlp(pp, x, gated=True, act="silu",
                                 rns=_Cfg.rns)), x, n=3)
        profs = sorted(set(resident_profiles({"mlp": pp}).values())) or ["-"]
        report(f"resident_mlp_block_{tag}", us,
               f"converts={c.converts} weight_converts={c.weight_converts} "
               f"activation_converts={c.activation_converts} "
               f"matmuls={c.matmuls} normalizes={c.normalizes} "
               f"profiles={','.join(profs)}")


def bench_paged_gather(report):
    """Serving-path overhead: the paged cache's block-table gather vs a
    dense cache read (the price of decoupling cache memory from batch).
    """
    from repro.serve.kv_cache import gather_pages

    rng = np.random.default_rng(5)
    R, nb, bs, Hk, D = 8, 16, 16, 4, 64
    P = 1 + R * nb
    pages = jnp.asarray(rng.standard_normal((1, P, bs * Hk * D)),
                        jnp.float32)
    bt = jnp.asarray(
        rng.permutation(np.arange(1, P))[: R * nb].reshape(R, nb), jnp.int32)
    dense = jnp.asarray(rng.standard_normal((R, nb * bs, Hk, D)), jnp.float32)
    t_gather = _t(jax.jit(lambda p, b: gather_pages(p, 0, b, (Hk, D)) * 1.0),
                  pages, bt, n=5)
    t_dense = _t(jax.jit(lambda d: d * 1.0), dense, n=5)
    report("paged_gather_8x256", t_gather,
           f"dense_read={t_dense:.0f}us pages={P} page_size={bs} "
           f"(gather cost amortizes into the decode attention read)")


def bench_rns_matmul_wall(report):
    """CPU-proxy wall time: digit-sliced matmul (jnp + pallas-interpret)."""
    rng = np.random.default_rng(4)
    p = get_profile("rns9")
    M = K = N = 256
    A = rng.integers(-2000, 2000, (M, K)).astype(np.int32)
    B = rng.integers(-2000, 2000, (K, N)).astype(np.int32)
    ra, rb = rns.encode_int32(p, A), rns.encode_int32(p, B)
    t_jnp = _t(jax.jit(lambda a, b: rns_matmul_res("rns9", a, b)), ra, rb, n=3)
    from repro.kernels.rns_matmul.ops import rns_matmul

    t_pal = _t(lambda a, b: rns_matmul("rns9", a.astype(jnp.int8),
                                       b.astype(jnp.int8)), ra, rb, n=3)
    xf = jnp.asarray(A, jnp.float32)
    wf = jnp.asarray(B, jnp.float32)
    t_f32 = _t(jax.jit(lambda a, b: a @ b), xf, wf, n=3)
    report("rns_matmul_256", t_jnp,
           f"pallas_interpret={t_pal:.0f}us f32_dense={t_f32:.0f}us "
           f"slices={p.n_digits} (TPU target: int8 MXU @2x bf16 rate)")


def run_all(report):
    bench_pac_ops(report)
    bench_deferred_norm(report)
    bench_exactness(report)
    bench_conversion_overhead(report)
    bench_precision_scaling(report)
    bench_chain_amortization(report)
    bench_mlp_block_normalizes(report)
    bench_paged_gather(report)
    bench_rns_matmul_wall(report)
    bench_resident_weights(report)
    bench_resident_mlp_block(report)
