"""A configuration names its architecture, and everything a run needs of
that architecture comes from ``chipbench/archs/<arch>.py``: a new one is
added as new files, an unknown one is refused before any weights are
made, and ``dense`` is the code the benchmark ran before the lookup."""

import functools
import hashlib
import json
import pathlib
import shutil
import sys
import types

import jax
import numpy as np
import pytest

from chipbench import flops, measure, model, reference, run, spec
from chipbench.archs import dense
from chipbench.device import PEAKS
from chipbench.loop import Spans, StepRecord, Window

from conftest import ROOT

sys.path.insert(0, str(pathlib.Path(__file__).parent))
from test_bench_check import BENCH, _gate, _tiny_cell  # noqa: E402

# a thin variant of dense whose reference and launch count record calls
PROBE = '''"""Dense, with calls to its reference and launch count recorded."""
from chipbench.archs import dense

CALLS = []
dims, make_weights, program_config, model_flops = (
    dense.dims, dense.make_weights, dense.program_config, dense.model_flops)


def logits(params, dm, tokens, bits=None):
    CALLS.append("logits")
    return dense.logits(params, dm, tokens, bits)


def fused_launches(dm, rows, digits):
    CALLS.append("fused_launches")
    return dense.fused_launches(dm, rows, digits)
'''


def _digests(top: pathlib.Path) -> dict:
    return {str(p.relative_to(top)): hashlib.sha256(p.read_bytes()).digest()
            for p in sorted(top.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def _copy(tmp_path, arch: str):
    """A copy of the benchmark under ``tmp_path`` with, as new files, a
    tiny configuration that names ``arch``, a tiny chat mix and a cell of
    the two; ``BENCHMARK.json`` lists them."""
    here = tmp_path / "chipbench"
    shutil.copytree(ROOT / "chipbench", here,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(here)
    tiny = _tiny_cell()
    config = dict(tiny.config, name="tiny.rns9", arch=arch)
    (here / "configs" / "tiny.rns9.json").write_text(json.dumps(config))
    (here / "traffic" / "tiny-chat.json").write_text(json.dumps(tiny.traffic))
    (here / "cells" / "tiny.rns9.chat.json").write_text(
        json.dumps(tiny.rate))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({
        "name": "tiny.rns9", "source": "test",
        "file": "chipbench/configs/tiny.rns9.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "tiny.rns9.chat", "config": "tiny.rns9",
        "traffic": "tiny-chat", "chips": 1, "why": "test"})
    for m in bench["per_layer"]:
        if m["name"] in ("rns_kernel_roofline.saturated",
                         "step_mfu.saturated"):
            m["workloads"].append("tiny.rns9.chat")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return here, before


def test_an_architecture_added_as_files_runs_end_to_end(tmp_path,
                                                        monkeypatch):
    """A module, a configuration and a cell, added as files to a copy of
    the benchmark, are found and run whole on the CPU (traced, so that
    the readers count its launches); no file that was there changes but
    ``BENCHMARK.json``, which lies outside the copied directory."""
    here, before = _copy(tmp_path, "dense_probe")
    (here / "archs" / "dense_probe.py").write_text(PROBE)
    load = functools.partial(spec.load_cell, root=tmp_path, here=here)
    cells = []
    monkeypatch.setattr(spec, "load_cell",
                        lambda name: cells.append(load(name)) or cells[-1])
    try:
        r = run.run_cell("tiny.rns9.chat", 2**31 + 13, 3.0, True, gate=_gate)
        probe = cells[0].arch
        assert probe.__name__ == "chipbench.archs.dense_probe"
        assert pathlib.Path(probe.__file__) == here / "archs" / \
            "dense_probe.py"
        assert r["correct"], r["checks"]
        assert {"logits", "fused_launches"} <= set(probe.CALLS)
    finally:
        sys.modules.pop("chipbench.archs.dense_probe", None)
    after = _digests(here)
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {
        "archs/dense_probe.py", "configs/tiny.rns9.json",
        "traffic/tiny-chat.json", "cells/tiny.rns9.chat.json"}


def test_an_unknown_architecture_is_refused_before_any_weights(
        tmp_path, monkeypatch):
    here, _ = _copy(tmp_path, "no_such_arch")
    load = functools.partial(spec.load_cell, root=tmp_path, here=here)
    monkeypatch.setattr(spec, "load_cell", load)
    made = []
    monkeypatch.setattr(dense, "make_weights",
                        lambda *a: made.append(a))
    gated = []
    with pytest.raises(KeyError, match=r"no_such_arch\.py.*'dense'"):
        run.run_cell("tiny.rns9.chat", 1, 1.0, False,
                     gate=lambda *a: gated.append(a))
    assert not gated and not made
    with pytest.raises(KeyError, match="looked for"):
        spec.arch({"arch": "../model"})


def test_dense_through_the_lookup_is_the_code_it_replaced():
    """At a small size: the same weights, bit for bit, and the same
    gaps and control gaps as ``model.py`` and ``reference.py`` give."""
    f = _tiny_cell().config
    arch = spec.arch(f)
    assert arch.__file__ == spec.arch(dict(f, arch="dense")).__file__ == \
        dense.__file__
    dm = arch.dims(f)
    assert dm == model.Dims.of(f)
    hash(dm)
    seed = 2**31 + 3
    got, want = arch.make_weights(dm, seed), model.make_weights(dm, seed)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert all(np.array_equal(a, b) for a, b in
               zip(jax.tree.leaves(got), jax.tree.leaves(want)))
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, dm.vocab, (2, 24)).astype(np.int32)
    targets = np.full_like(tokens, -1)
    targets[:, 8:] = tokens[:, 8:]
    new = reference.gaps(want, tokens, targets, dm=dm, bits=4,
                         logits=arch.logits)
    old = reference.gaps(want, tokens, targets, dm=dm, bits=4)
    for a, b in zip(new, old):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert arch.model_flops(dm, 7, 90) == flops.model_flops(dm, 7, 90)
    assert arch.fused_launches(dm, 16, 9) == flops.mlp_launches(dm, 16, 9)


def test_the_readers_count_the_work_through_the_architecture():
    """``step_mfu`` and ``rns_kernel_roofline`` take the FLOPs and the
    launches from the run's architecture module, not from a dense
    formula of their own."""
    from chipbench import xtrace

    peak = PEAKS["TPU v5 lite"]
    ops, nbytes = 2 * 393e12, 819e9         # 2 s of compute, 1 s of bytes
    arch = types.SimpleNamespace(
        model_flops=lambda dm, tokens, ctx: 1e12 * tokens + 1e9 * ctx,
        fused_launches=lambda dm, rows, digits: [(ops, nbytes)] * 3)
    steps = [StepRecord(0.1, 0.2, lanes=64, context=640),
             StepRecord(0.3, 0.4, lanes=32, context=320)]
    launch = [xtrace.Event("rns_fused_dot_kernel", i * 8e9, 8e9,
                           device="/device:TPU:0") for i in range(6)]
    tr = xtrace.Trace(ops=launch, modules=[], devices=["/device:TPU:0"],
                      spans=[xtrace.Event(xtrace.WINDOW, 0, 1e12)])
    data = measure.RunData(
        arch=arch, dims=None, budget=64, digits=9, peak=peak,
        win=Window(0.0, 2.0), states=[], steps=steps, spans=Spans(),
        trace=tr)
    mfu = measure.reader("step_mfu.saturated")(data)
    assert mfu == pytest.approx(100 * (96e12 + 960e9) / (2.0 * 197e12))
    # six launches of 8 s, two whole steps of three launches of 2 s each
    roof = measure.reader("rns_kernel_roofline.saturated")(data)
    assert roof == pytest.approx(100 * 2 * 3 * 2.0 / 48.0)
