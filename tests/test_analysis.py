"""Static analysis: the exactness auditor and the repo-invariant linter.

The auditor must agree with the runtime magnitude ledger op for op (they
share ``core.tensor.ledger_limit_bits`` / ``dot_out_bits``), prove every
shipped ServeConfig feature combination exact without running the model,
and reject a deliberately overflowing configuration while naming the
failing layer and op.  The linter must hold ``src/`` at zero unsuppressed
violations (the CI ``static-analysis`` gate).
"""

import dataclasses
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis.ledger_audit import (
    audit_fn,
    audit_serve,
    validate_resident,
)
from repro.analysis.lint import lint_source, run_lint
from repro.configs.base import get_config
from repro.core import dispatch
from repro.core.moduli import get_profile
from repro.core.rns_matmul import RnsDotConfig
from repro.core.tensor import (
    dot_out_bits,
    ledger_limit_bits,
    matmul_out_bits,
    needs_renormalize,
    rt_decode,
    rt_encode,
    rt_encode_int,
    rt_matmul,
)
from repro.models import model as M
from repro.serve.engine import ContinuousEngine, ServeConfig


@pytest.fixture(scope="module")
def smoke():
    cfg = dataclasses.replace(get_config("smollm-135m", smoke=True),
                              rns=RnsDotConfig(profile="rns9", qx=8, qw=8),
                              rns_targets="mlp")
    return cfg, M.init_model(jax.random.PRNGKey(0), cfg)[0]


# ----------------------------------------------- shared bound helpers -----
class TestBoundHelpers:
    """The three former per-call-site bound formulas now share one home;
    the runtime ledger and the static auditor must read identical numbers
    at the rns6/rns9 boundaries."""

    @pytest.mark.parametrize("profile", ["rns6", "rns9"])
    def test_limit_is_signed_bits_minus_safety(self, profile):
        p = get_profile(profile)
        assert ledger_limit_bits(profile) == p.signed_bits - 1.0
        assert ledger_limit_bits(p) == ledger_limit_bits(profile)

    @pytest.mark.parametrize("profile", ["rns6", "rns9"])
    def test_headroom_matches_limit(self, profile):
        rt = rt_encode(jnp.ones((2, 4)), profile, bits=8)
        assert rt.headroom_bits() == ledger_limit_bits(profile) - rt.mag_bits

    @pytest.mark.parametrize("profile", ["rns6", "rns9"])
    def test_matmul_out_bits_is_dot_out_bits(self, profile):
        a = rt_encode(jnp.ones((2, 8)), profile, bits=8)
        w = rt_encode(jnp.ones((8, 2)), profile, bits=8)
        assert matmul_out_bits(a, w, 8) == dot_out_bits(
            a.mag_bits, w.mag_bits, 8)
        assert dot_out_bits(7.0, 7.0, 8) == 7.0 + 7.0 + 3.0

    @pytest.mark.parametrize("profile", ["rns6", "rns9"])
    def test_needs_renormalize_boundary_agreement(self, profile):
        """Exactly at the limit is fine; any epsilon over trips — and the
        trip point is THE shared limit, on both profiles."""
        lim = ledger_limit_bits(profile)
        rt = rt_encode(jnp.ones((2, 4)), profile, bits=8)
        at = lim - rt.mag_bits
        assert not needs_renormalize(rt, at)
        assert needs_renormalize(rt, at + 1e-6)


# ------------------------------------------------------- rt_encode_int ----
class TestEncodeIntLedger:
    """The old hardcoded ``mag_bits=31.0`` default lied for small values
    and silently passed unrepresentable ones; the bound is now derived."""

    def test_concrete_value_derives_actual_bound(self):
        rt = rt_encode_int(jnp.asarray([3, -12345], jnp.int32), "rns9")
        assert rt.mag_bits == pytest.approx(math.log2(12345))

    def test_tiny_values_floor_at_zero(self):
        assert rt_encode_int(jnp.asarray([0, 1], jnp.int32),
                             "rns9").mag_bits == 0.0

    def test_explicit_mag_bits_wins(self):
        rt = rt_encode_int(jnp.asarray([3], jnp.int32), "rns9",
                           mag_bits=20.0)
        assert rt.mag_bits == 20.0

    def test_unrepresentable_concrete_value_raises(self):
        with pytest.raises(ValueError, match="wider profile"):
            rt_encode_int(np.asarray([2**40], np.int64), "rns5")

    def test_traced_value_clamps_to_profile(self):
        seen = {}

        def f(v):
            rt = rt_encode_int(v, "rns9")
            seen["mag"] = rt.mag_bits
            return rt.digits

        jax.eval_shape(f, jax.ShapeDtypeStruct((4,), jnp.int32))
        assert seen["mag"] == 31.0  # int32 payload < rns9 signed range


# ------------------------------------------------------------ OpCounts ----
class TestOpCounts:
    def test_add_merges_and_scales(self):
        a = dispatch.OpCounts(converts=1, matmuls=2, normalizes=1,
                              fallbacks=1,
                              fallback_sites={("s1", "r1"): 1})
        b = dispatch.OpCounts(converts=2, matmuls=1, fused=1, fallbacks=2,
                              weight_converts=1,
                              fallback_sites={("s1", "r1"): 1,
                                              ("s2", "r2"): 1})
        out = a.add(b, times=3)
        assert (out.converts, out.matmuls, out.normalizes, out.fused,
                out.fallbacks, out.weight_converts) == (7, 5, 1, 3, 7, 3)
        assert out.fallback_sites == {("s1", "r1"): 4, ("s2", "r2"): 3}
        # inputs untouched
        assert a.fallback_sites == {("s1", "r1"): 1}

    def test_fallbacks_tally_per_site(self):
        with dispatch.count_ops() as c:
            dispatch._tally_fallback("unit-test reason")
            dispatch._tally_fallback("unit-test reason")
        assert c.fallbacks == 2
        ((site, reason), n), = c.fallback_sites.items()
        assert reason == "unit-test reason" and n == 2
        # callers outside the repo get the explicit out-of-tree marker;
        # in-tree sites are named (see the audit fallback tests)
        assert site == "<external>"


# --------------------------------------------------------------- audit ----
class TestAuditFn:
    def test_proves_simple_matmul_chain(self):
        def f(x, w):
            a = rt_encode(x, "rns9", bits=8)
            b = rt_encode(w, "rns9", bits=8, weight=True)
            return rt_decode(rt_matmul(a, b))

        rep = audit_fn(f, jnp.ones((4, 16)), jnp.ones((16, 4)))
        assert rep.ok
        (ph,) = rep.phases
        assert ph.counts["matmuls"] == 1 and ph.counts["normalizes"] == 1
        assert ph.counts_match                   # graph == traced OpCounts
        assert ph.min_headroom == pytest.approx(
            ledger_limit_bits("rns9") - dot_out_bits(7.0, 7.0, 16))
        assert ph.critical_path                  # names the tight chain

    def test_smoke_arch_prefill_proved(self, smoke):
        cfg, params = smoke
        rep = audit_fn(
            lambda p, t: M.prefill(p, cfg, {"tokens": t}, S_max=16),
            params, jnp.zeros((1, 8), jnp.int32), name="prefill")
        assert rep.ok and rep.min_headroom > 0
        (ph,) = rep.phases
        assert ph.counts["matmuls"] > 0 and ph.counts_match


class TestOverflowRejection:
    def test_overflowing_config_names_layer_and_op(self, smoke):
        cfg, params = smoke
        # rns5 holds ~33.8 exact bits: a 16x16-bit dot over the smoke
        # model's MLP contraction provably cannot fit
        bad = dataclasses.replace(
            cfg, rns=RnsDotConfig(profile="rns5", qx=16, qw=16))
        rep = audit_serve(params, bad, ServeConfig(
            max_cache=24, page_size=8, max_seqs=2))
        assert not rep.ok
        failed = [p for p in rep.phases if not p.ok]
        assert failed
        ph = failed[0]
        assert ph.error and "wider profile" in ph.error
        assert ph.error_site["layer"].startswith("models/")
        assert ph.error_site["op"].startswith(("core/", "kernels/"))
        assert "FAILED" in rep.summary()


class TestServeConfigAudit:
    def test_audit_true_builds_and_attaches_report(self, smoke):
        cfg, params = smoke
        eng = ContinuousEngine(params, cfg, ServeConfig(
            max_cache=24, page_size=8, max_seqs=2, audit=True))
        assert eng.audit_report is not None and eng.audit_report.ok
        assert eng.audit_report.min_headroom > 0

    def test_audit_true_refuses_unprovable_config(self, smoke):
        cfg, params = smoke
        bad = dataclasses.replace(
            cfg, rns=RnsDotConfig(profile="rns5", qx=16, qw=16))
        with pytest.raises(ValueError, match="exactness audit"):
            ContinuousEngine(params, bad, ServeConfig(
                max_cache=24, page_size=8, max_seqs=2, audit=True))

    def test_audit_skips_float_configs(self, smoke):
        cfg, params = smoke
        float_cfg = dataclasses.replace(cfg, rns=None)
        eng = ContinuousEngine(params, float_cfg, ServeConfig(
            max_cache=24, page_size=8, max_seqs=2, audit=True))
        assert eng.audit_report is None

    def test_all_feature_combos_proved(self, smoke):
        """resident x defer x chunked x spec x prefix — every shipped
        combination must be provably exact at build time."""
        cfg, params = smoke
        n = 0
        for resident in (False, True):
            for defer in (False, True):
                for chunked in (False, True):
                    for spec in (False, True):
                        for prefix in (False, True):
                            scfg = ServeConfig(
                                max_cache=24, page_size=8, max_seqs=2,
                                rns_defer=defer, resident_weights=resident,
                                per_layer_profiles=resident,
                                chunked_prefill=chunked,
                                spec_decode=spec, spec_k=3,
                                token_budget=16, prefix_cache=prefix,
                                audit=True)
                            eng = ContinuousEngine(params, cfg, scfg)
                            assert eng.audit_report.ok, vars(scfg)
                            n += 1
        assert n == 32


# ---------------------------------------------------- resident re-proof ---
class TestResidentValidation:
    def test_resident_entries_reproved_from_masters(self, smoke):
        from repro.models.resident import encode_resident

        cfg, params = smoke
        res = encode_resident(params, cfg, per_layer_profiles=True)
        entries = validate_resident(res, cfg.rns)
        assert entries and all(e["ok"] for e in entries)

    def test_tampered_ledger_bound_is_caught(self, smoke):
        from repro.models import resident as R

        cfg, params = smoke
        res = jax.tree.map(lambda x: x,            # fresh containers
                           R.encode_resident(params, cfg))

        def tamper(mlp, path):
            for name in R._MLP_WEIGHTS:
                if isinstance(mlp.get(name), dict) and "w_res" in mlp[name]:
                    w = mlp[name]["w_res"]
                    mlp[name]["w_res"] = dataclasses.replace(
                        w, mag_bits=w.mag_bits - 4.0)
                    return mlp
            return mlp

        R._walk_mlps(res, tamper)
        entries = validate_resident(res, cfg.rns)
        assert any(not e["ok"] and "under-approximates" in e["detail"]
                   for e in entries)


# ---------------------------------------------------------------- lint ----
class TestLintRules:
    def test_pallas_call_outside_kernels(self):
        src = "import jax.experimental.pallas as pl\npl.pallas_call(k)\n"
        (v,) = lint_source(src, "models/layers.py")
        assert v.rule == "pallas-call" and v.line == 2
        assert not lint_source(src, "kernels/rns_matmul/kernel.py")

    def test_raw_digits_arithmetic(self):
        src = "y = rt.digits + 1\n"
        (v,) = lint_source(src, "serve/engine.py")
        assert v.rule == "raw-digits"
        assert not lint_source(src, "core/tensor.py")
        # arithmetic-shaped calls count too; layout moves don't
        assert lint_source("jnp.sum(rt.digits)\n", "serve/engine.py")
        assert not lint_source("jnp.moveaxis(rt.digits, 0, -1)\n",
                               "serve/engine.py")

    def test_backend_flag_bypass(self):
        src = "f(x, interpret=True)\n"
        (v,) = lint_source(src, "serve/engine.py")
        assert v.rule == "backend-flag"
        assert not lint_source(src, "kernels/rns_fused/ops.py")
        assert lint_source("g(use_pallas=True)\n", "models/layers.py")
        assert not lint_source("g(use_pallas=True)\n", "core/rns_matmul.py")

    def test_host_in_jit(self):
        src = "import time\nt = time.perf_counter()\n"
        (v,) = lint_source(src, "models/layers.py")
        assert v.rule == "host-in-jit"
        assert not lint_source(src, "serve/engine.py")  # host code is fine
        assert lint_source("x = np.random.uniform(0, 1)\n", "core/rns.py")

    @pytest.mark.parametrize("spec, flagged", [
        ("pl.BlockSpec()", True),
        ("pl.BlockSpec(None, lambda i: (i,))", True),
        ("pl.BlockSpec(memory_space=pltpu.VMEM)", True),
        ("pl.BlockSpec(memory_space=pltpu.SMEM)", False),
        ("pl.BlockSpec((8, 128), lambda i: (i, 0))", False),
    ])
    def test_whole_array_vmem(self, spec, flagged):
        vs = lint_source(spec + "\n", "kernels/rns_matmul/kernel.py")
        assert [v.rule for v in vs] == (["whole-array-vmem"] if flagged
                                        else [])

    def test_line_suppression_covers_line_and_next(self):
        src = ("# lint-ok: raw-digits (unit test)\n"
               "y = rt.digits + 1\n"
               "z = rt.digits + 2\n")
        (v,) = lint_source(src, "serve/engine.py")
        assert v.line == 3                       # line 2 was covered

    def test_file_suppression_and_multi_rule(self):
        src = ("# lint-ok-file: raw-digits\n"
               "y = rt.digits + 1\n"
               "t = time.sleep(1)  # lint-ok: host-in-jit, backend-flag\n")
        assert not lint_source(src, "models/layers.py")

    def test_repo_is_clean(self):
        violations = run_lint()
        assert violations == [], "\n".join(str(v) for v in violations)
