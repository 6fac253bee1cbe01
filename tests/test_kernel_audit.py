"""Kernel legality/VMEM auditor + jit compile-churn prover.

The load-bearing claims, executed:
  * every shipped block config (autotune DEFAULTS, every CANDIDATE,
    persisted cache rows) is statically proven Mosaic-legal and within
    the VMEM budget, for every kernel family x shape bucket;
  * an intentionally-illegal block is caught and NAMED at every layer:
    the closed-form checker, the wrapper guard (ValueError with kernel,
    blocks, computed VMEM bytes), the autotune cache load (self-heal to
    DEFAULTS with a logged reason), and the ``ServeConfig(audit=True)``
    engine build gate;
  * the trace auditor proves the continuous engine's phases keep ONE
    jit signature across a traffic family — and that static proof
    agrees with the runtime ``_cache_size() == 1`` pins;
  * a fabricated traffic-dependent phase is caught with the drifting
    leaf named.
"""

import dataclasses
import json
import logging

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.analysis.kernel_audit import (
    BUDGET_BYTES,
    KernelAuditReport,
    audit_all,
    audit_config,
    capture_launches,
    check_launch,
    check_wrapper_blocks,
    launch_vmem_bytes,
    sublane,
    validate_blocks,
    vmem_bytes,
)
from repro.analysis.trace_audit import (
    arg_signature,
    audit_traces,
    describe_signature,
    traffic_family,
)
from repro.configs.base import get_config
from repro.core.moduli import get_profile
from repro.core.rns import encode_int32
from repro.core.rns_matmul import RnsDotConfig
from repro.kernels import autotune
from repro.models import model as M
from repro.serve.engine import ContinuousEngine, ServeConfig

_MATMUL_KINDS = ("rns_matmul", "rns_fused_encode_matmul",
                 "rns_fused_matmul_normalize", "rns_fused_dot")


@pytest.fixture(scope="module")
def smoke():
    cfg = dataclasses.replace(get_config("smollm-135m", smoke=True),
                              rns=RnsDotConfig(profile="rns9", qx=8, qw=8),
                              rns_targets="mlp")
    return cfg, M.init_model(jax.random.PRNGKey(0), cfg)[0]


@pytest.fixture()
def tmp_cache(tmp_path, monkeypatch):
    path = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    autotune.clear_cache()
    yield path
    autotune.clear_cache()


# ------------------------------------------------ closed-form contract ----
class TestTileContract:
    def test_shipped_defaults_legal_for_every_kind(self):
        for kind, blocks in autotune.DEFAULTS.items():
            assert validate_blocks(kind, blocks, n_digits=9) == [], kind

    def test_lane_violation_named(self):
        v = validate_blocks("rns_matmul",
                            {"bm": 128, "bn": 100, "bk": 512}, n_digits=9)
        assert v and all(s.startswith("rns_matmul") for s in v)
        assert any("lane" in s for s in v)

    def test_whole_dim_exempts_lane_rule(self):
        # bn == N: the block spans the array dim, so 100 lanes is fine
        v = validate_blocks("rns_matmul", {"bm": 8, "bn": 100, "bk": 512},
                            n_digits=9,
                            dims={"M": 8, "D": 512, "N": 100})
        assert v == []

    def test_int8_profiles_tighten_the_sublane_rule(self):
        assert sublane(1) == 32 and sublane(2) == 16 and sublane(4) == 8
        # bm=8 is a legal f32 sublane but NOT a legal int8 one
        ok = validate_blocks("rns_matmul", {"bm": 8, "bn": 128, "bk": 512},
                             n_digits=6, res_bytes=4)
        bad = validate_blocks("rns_matmul", {"bm": 8, "bn": 128, "bk": 512},
                              n_digits=6, res_bytes=1)
        assert ok == []
        assert any("sublane" in s and "32" in s for s in bad)

    def test_vmem_formula_is_double_buffered_streams_plus_scratch(self):
        # rns_normalize, K=9, bt=1024: res (9,1024)x4B + out (1024,)x4B
        # streamed, no scratch -> 2 * (36864 + 4096)
        assert vmem_bytes("rns_normalize", {"bt": 1024},
                          n_digits=9) == 2 * (9 * 1024 * 4 + 1024 * 4)
        # rns_matmul defaults, K=9: a + b + out tiles double-buffered
        # (the moduli sit whole in SMEM), plus the (bm, bn) f32
        # accumulator scratch once
        streamed = (128 * 512 + 512 * 128 + 128 * 128) * 4
        assert vmem_bytes("rns_matmul", {"bm": 128, "bn": 128, "bk": 512},
                          n_digits=9) == 2 * streamed + 128 * 128 * 4

    def test_budget_violation_named(self):
        v = validate_blocks("rns_fused_matmul_normalize",
                            {"bm": 1024, "bn": 1024, "bk": 1024},
                            n_digits=9)
        assert any("budget" in s and str(BUDGET_BYTES) in s for s in v)

    def test_junk_is_named_not_raised(self):
        assert validate_blocks("no_such_kernel", {"bm": 128}) \
            == ["unknown kernel kind 'no_such_kernel'"]
        v = validate_blocks("rns_matmul",
                            {"bm": "big", "bn": 128, "bk": 512})
        assert v and "'bm'" in v[0] and "positive int" in v[0]
        assert "not a dict" in validate_blocks("rns_convert", [1024])[0]
        assert "positive int" in \
            validate_blocks("rns_convert", {"bt": True})[0]

    def test_wrapper_gate_names_kernel_blocks_and_vmem(self):
        blocks = {"bm": 128, "bn": 100, "bk": 512}
        with pytest.raises(ValueError) as e:
            check_wrapper_blocks("rns_matmul", blocks, dims={}, n_digits=9)
        msg = str(e.value)
        assert "rns_matmul" in msg and "'bn': 100" in msg
        assert "VMEM working set" in msg and str(BUDGET_BYTES) in msg


# ------------------------------------------------------ wrapper guards ----
class TestWrapperGuards:
    def test_rns_matmul_refuses_illegal_bn(self):
        from repro.kernels.rns_matmul.ops import rns_matmul

        p = get_profile("rns9")
        rng = np.random.default_rng(0)
        ra = jnp.asarray(encode_int32(
            p, rng.integers(-2**10, 2**10, (8, 256)).astype(np.int32)))
        rb = jnp.asarray(encode_int32(
            p, rng.integers(-2**10, 2**10, (256, 256)).astype(np.int32)))
        with pytest.raises(ValueError,
                           match="rns_matmul: illegal block config"):
            rns_matmul("rns9", ra, rb, bn=100)

    @pytest.mark.parametrize("kind", ["rns_matmul", "rns_fused_dot"])
    def test_refuses_residues_wider_than_int8(self, kind):
        """The digit dots take int8 MXU operands: a profile with moduli
        above 128 must be refused, not silently wrapped."""
        from repro.kernels.rns_fused.ops import rns_fused_dot
        from repro.kernels.rns_matmul.ops import rns_matmul

        p = get_profile("rns8_u8")
        assert not p.int8_safe
        b = jnp.zeros((p.n_digits, 512, 128), jnp.int32)
        with pytest.raises(ValueError, match="moduli above 128"):
            if kind == "rns_matmul":
                rns_matmul(p, jnp.zeros((p.n_digits, 8, 512), jnp.int32), b)
            else:
                rns_fused_dot(p, jnp.zeros((8, 512), jnp.float32), 1.0, b)

    def test_rns_convert_refuses_illegal_bt(self):
        from repro.kernels.rns_convert.ops import rns_convert

        with pytest.raises(ValueError,
                           match="rns_convert: illegal block config"):
            rns_convert("rns9", jnp.ones(512, jnp.float32),
                        jnp.float32(4.0), bt=100)


# ------------------------------------------------------- capture layer ----
class TestCaptureLayer:
    def test_capture_records_the_real_launch(self):
        from repro.kernels.rns_matmul.ops import rns_matmul

        launches = capture_launches(
            lambda a, b: rns_matmul("rns9", a, b),
            jax.ShapeDtypeStruct((9, 8, 512), jnp.int32),
            jax.ShapeDtypeStruct((9, 512, 512), jnp.int32))
        assert len(launches) == 1
        ln = launches[0]
        assert ln.kind == "rns_matmul" and ln.grid[0] == 9
        assert check_launch(ln) == []
        # the closed-form model must be conservative vs the real launch
        assert launch_vmem_bytes(ln) <= vmem_bytes(
            "rns_matmul", autotune.DEFAULTS["rns_matmul"], n_digits=9)

    @pytest.mark.parametrize("kind", ["rns_matmul",
                                      "rns_fused_encode_matmul"])
    def test_capture_places_smem_moduli_whole(self, kind):
        """The moduli ride as a whole-array SMEM operand: no tiles to
        check, and no VMEM charged for them."""
        from repro.kernels.rns_fused.ops import rns_fused_encode_matmul
        from repro.kernels.rns_matmul.ops import rns_matmul

        b = jax.ShapeDtypeStruct((9, 512, 512), jnp.int32)
        if kind == "rns_matmul":
            (ln,) = capture_launches(
                lambda a, b: rns_matmul("rns9", a, b),
                jax.ShapeDtypeStruct((9, 8, 512), jnp.int32), b)
            a_tile = 8 * 512 * 4
        else:
            (ln,) = capture_launches(
                lambda x, b: rns_fused_encode_matmul("rns9", x, 2.0, b),
                jax.ShapeDtypeStruct((8, 512), jnp.float32), b)
            a_tile = 8 * 512 * 4 + 8 * 1 * 4      # x tile + scale rows
        assert ln.kind == kind
        assert ln.in_specs[0] == (None, None)
        assert ln.operands[0][0] == (9,)
        assert check_launch(ln) == []
        streamed = a_tile + 512 * 128 * 4 + 8 * 128 * 4
        assert launch_vmem_bytes(ln) == 2 * streamed + 8 * 128 * 4

    def test_unit_sublane_block_over_a_longer_dim_refused(self):
        """A (1, 1) block over a (K, 1) array is what v5e's compiler
        refused for the old moduli operand; the auditor had passed it."""
        from repro.analysis.kernel_audit import KernelLaunch

        ln = KernelLaunch(
            kind="rns_matmul", kernel_name="_kernel", profile="rns9",
            grid=(9,), in_specs=(((1, 1), lambda s: (s, 0)),),
            out_specs=(((1, 128), lambda s: (s, 0)),),
            operands=(((9, 1), "int32", 4),), outs=(((9, 128), "int32", 4),),
            scratch=())
        violations = check_launch(ln)
        assert any("in0" in v and "sublane" in v for v in violations)

    def test_capture_drops_its_poisoned_traces(self):
        from repro.kernels.rns_matmul.kernel import rns_matmul_tiles
        from repro.kernels.rns_matmul.ops import rns_matmul

        capture_launches(
            lambda a, b: rns_matmul("rns9", a, b),
            jax.ShapeDtypeStruct((9, 8, 512), jnp.int32),
            jax.ShapeDtypeStruct((9, 512, 512), jnp.int32))
        # the zeros-returning shim trace must never serve a real call
        assert rns_matmul_tiles._cache_size() == 0


# ------------------------------------------------------- report layer -----
class TestAuditSweep:
    def test_every_shipped_config_proved(self):
        report = audit_all()
        assert report.ok, report.summary()
        kinds = {e["kind"] for e in report.entries}
        assert kinds == set(autotune.DEFAULTS)
        sources = {e["source"].split("[")[0] for e in report.entries}
        assert {"defaults", "candidate"} <= sources
        # flash has no RNS profile: audited once under its dtype tag
        assert {e["profile"] for e in report.entries
                if e["kind"] == "flash_attention"} == {"float32"}
        assert report.summary().startswith("kernel audit: PROVED")

    def test_injected_illegal_config_failed_and_named(self):
        entry = audit_config("rns_matmul", "rns9", (8, 512, 512),
                             {"bm": 128, "bn": 100, "bk": 512},
                             source="injected")
        assert not entry["ok"]
        joined = " ".join(entry["violations"])
        assert "rns_matmul" in joined and "lane" in joined
        report = KernelAuditReport(ok=False, entries=[entry])
        assert "FAILED" in report.summary()
        assert "injected" in report.summary()
        assert json.loads(report.to_json())["ok"] is False


# --------------------------------------------------- engine build gate ----
class TestEngineGate:
    @pytest.mark.parametrize("backend", ["pallas_interpret",
                                         "pallas_fused_interpret"])
    def test_illegal_tuned_block_refuses_build(self, smoke, tmp_cache,
                                               monkeypatch, backend):
        """A bad tile that reaches the wrappers (here: forced through
        DEFAULTS) must refuse the audited engine build, naming the
        kernel, the block, and the violated constraint."""
        cfg, params = smoke
        for kind in _MATMUL_KINDS:
            monkeypatch.setitem(autotune.DEFAULTS, kind,
                                dict(autotune.DEFAULTS[kind], bn=100))
        with pytest.raises(ValueError, match="kernel audit failed") as e:
            ContinuousEngine(params, cfg, ServeConfig(
                max_cache=24, page_size=8, max_seqs=2, audit=True,
                rns_backend=backend))
        msg = str(e.value)
        assert "'bn': 100" in msg and "illegal block config" in msg

    def test_legal_build_attaches_kernel_and_trace_reports(self, smoke):
        cfg, params = smoke
        eng = ContinuousEngine(params, cfg, ServeConfig(
            max_cache=24, page_size=8, max_seqs=2, audit=True,
            rns_backend="pallas_interpret"))
        assert eng.kernel_audit_report.ok
        assert {e["kind"] for e in eng.kernel_audit_report.entries} \
            == {"engine.decode", "engine.prefill"}
        assert all(e["n_launches"] > 0
                   for e in eng.kernel_audit_report.entries)
        assert eng.trace_audit_report.ok
        assert eng.audit_report.ok          # the exactness proof rides along


# --------------------------------------------- autotune cache self-heal ---
class TestCacheSelfHeal:
    def test_illegal_row_dropped_with_logged_reason(self, tmp_cache,
                                                    caplog):
        bad_key = "rns_matmul|rns9|128x512x128|cpu"
        tmp_cache.write_text(json.dumps({"version": 1, "entries": {
            bad_key: {"blocks": {"bm": 128, "bn": 100, "bk": 512},
                      "us": 1.0},
            "rns_normalize|rns9|512|cpu": {"blocks": {"bt": 512},
                                           "us": 1.0},
        }}))
        autotune.clear_cache()
        with caplog.at_level(logging.WARNING,
                             logger="repro.kernels.autotune"):
            blk = autotune.get_blocks("rns_matmul", "rns9",
                                      (128, 512, 128), "cpu")
        assert blk == autotune.DEFAULTS["rns_matmul"]    # healed
        assert "self-healing to DEFAULTS" in caplog.text
        assert bad_key in caplog.text and "'bn': 100" in caplog.text
        # the legal row in the same file survives the heal
        assert autotune.get_blocks("rns_normalize", "rns9",
                                   (512,), "cpu") == {"bt": 512}

    def test_tune_skips_illegal_candidates(self, tmp_cache, monkeypatch,
                                           caplog):
        monkeypatch.setitem(autotune.CANDIDATES, "rns_normalize",
                            [{"bt": 100}, {"bt": 512}])
        measured = []

        def bench(blocks):
            measured.append(dict(blocks))
            return 0.001

        with caplog.at_level(logging.WARNING,
                             logger="repro.kernels.autotune"):
            got = autotune.tune("rns_normalize", "rns9", (512,), "cpu",
                                bench_fn=bench, repeats=1)
        assert measured == [{"bt": 512}]    # the illegal tile never ran
        assert got == {"bt": 512}
        assert "skipping illegal candidate" in caplog.text

    def test_tune_with_no_legal_candidates_keeps_defaults(
            self, tmp_cache, monkeypatch, caplog):
        monkeypatch.setitem(autotune.CANDIDATES, "rns_normalize",
                            [{"bt": 100}])

        def boom(blocks):
            raise AssertionError("illegal candidate was measured")

        with caplog.at_level(logging.WARNING,
                             logger="repro.kernels.autotune"):
            got = autotune.tune("rns_normalize", "rns9", (512,), "cpu",
                                bench_fn=boom, repeats=1)
        assert got == autotune.DEFAULTS["rns_normalize"]
        assert "no legal candidates" in caplog.text
        assert not tmp_cache.exists()       # nothing bogus persisted


# ------------------------------------------------------- trace auditor ----
class _DriftingEngine:
    """Fake engine whose step signature depends on traffic — the exact
    bug class the auditor exists to catch."""

    prompt_pad = 8

    def _trace_specs(self, traffic=None):
        L = int((traffic or {}).get("length", 1))
        return {"step": (lambda t: t, (jnp.zeros((1, L), jnp.int32),))}


class _FlakyPhaseEngine:
    prompt_pad = 8

    def _trace_specs(self, traffic=None):
        specs = {"decode": (lambda t: t, (jnp.zeros((1, 1), jnp.int32),))}
        if int((traffic or {}).get("length", 1)) == 8:
            specs["prefill"] = (lambda t: t,
                                (jnp.zeros((1, 8), jnp.int32),))
        return specs


class TestTraceAudit:
    def test_arg_signature_sees_weak_types(self):
        sig = arg_signature((1.0, jnp.zeros((2, 8), jnp.int32)))
        (s0, _d0, weak0), (s1, d1, weak1) = sig[1]
        assert s0 == () and weak0          # python scalar: weak, retraces
        assert s1 == (2, 8) and d1 == "int32" and not weak1
        txt = describe_signature(sig)
        assert "~" in txt and "2x8:int32" in txt

    def test_family_spans_the_prompt_pad(self, smoke):
        cfg, params = smoke
        eng = ContinuousEngine(params, cfg, ServeConfig(
            max_cache=32, max_new_tokens=4, page_size=8, max_seqs=2))
        fam = traffic_family(eng)
        assert {t["length"] for t in fam} \
            == {1, 2, eng.prompt_pad // 2, eng.prompt_pad - 1,
                eng.prompt_pad}

    def test_static_proof_agrees_with_runtime_cache_pins(self, smoke):
        cfg, params = smoke
        eng = ContinuousEngine(params, cfg, ServeConfig(
            max_cache=32, max_new_tokens=4, page_size=8, max_seqs=2))
        report = audit_traces(eng)
        assert report.ok, report.summary()
        assert {p.phase for p in report.phases} == {"decode", "prefill"}
        assert report.n_variants == len(traffic_family(eng))
        assert "PROVED" in report.summary()
        # the runtime fact the proof predicts: mixed lengths, one trace
        rng = np.random.default_rng(0)
        prompts = [rng.integers(1, cfg.vocab, (L,)).astype(np.int32)
                   for L in (3, 7)]
        eng.run(prompts)
        assert eng._decode._cache_size() == 1
        assert eng._prefill._cache_size() == 1

    def test_drifting_phase_caught_with_leaf_named(self):
        report = audit_traces(_DriftingEngine())
        assert not report.ok
        bad = report.failed[0]
        assert bad.phase == "step"
        assert any("leaf 0" in d for d in bad.drift)
        assert "FAILED" in report.summary() and "step" in report.summary()

    def test_traffic_dependent_phase_set_caught(self):
        report = audit_traces(_FlakyPhaseEngine())
        assert not report.ok
        drift = [d for p in report.failed for d in p.drift]
        assert any("traffic variants" in d for d in drift)
