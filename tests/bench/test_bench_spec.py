"""The cell's pieces are files found by name; traffic is fixed by its mix
and seed; the device gate and the peak table refuse what they do not
know; ``BENCHMARK.json`` keeps to its shape."""

import json
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

from chipbench import device, measure, spec, traffic

from conftest import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _mix(name):
    return json.loads((ROOT / "chipbench" / "traffic" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("mix", ["chat", "long-prompt", "long-context"])
def test_traffic_is_fixed_by_the_seed_and_within_the_cap(mix):
    m = _mix(mix)
    a = traffic.build(m, 3.0, 20, 2**31 + 7, 49152)
    b = traffic.build(m, 3.0, 20, 2**31 + 7, 49152)
    c = traffic.build(m, 3.0, 20, 2**33 + 1, 49152)
    assert [(r.due, r.max_new, r.prompt.tolist()) for r in a] == \
        [(r.due, r.max_new, r.prompt.tolist()) for r in b]
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in c]
    for reqs in (a, c):
        assert all(len(r.prompt) + r.max_new <= m["max_total"]
                   for r in reqs)
        assert all(m["prompt"]["min"] <= len(r.prompt) <= m["prompt"]["max"]
                   for r in reqs)
        assert all(0 <= r.prompt.min() and r.prompt.max() < 49152
                   for r in reqs)
        assert [r.due for r in reqs] == sorted(r.due for r in reqs)


def test_every_seed_gets_the_same_work():
    m = _mix("chat")
    runs = [traffic.build(m, 4.0, 30, s, 1000) for s in (1, 2, 3)]
    shapes = [[(r.due, len(r.prompt), r.max_new, r.in_window)
               for r in reqs] for reqs in runs]
    assert shapes[0] == shapes[1] == shapes[2]
    window = [r for r in runs[0] if r.in_window]
    assert len(window) == 120
    assert m["preroll_s"] <= window[0].due
    assert window[-1].due < m["preroll_s"] + 30
    assert runs[0][0].prompt.tolist() != runs[1][0].prompt.tolist()


def test_a_mix_added_as_a_file_is_found_by_name(tmp_path):
    """A later change adds a mix, a rate and a cell as files only."""
    here = tmp_path / "chipbench"
    shutil.copytree(ROOT / "chipbench", here,
                    ignore=shutil.ignore_patterns("__pycache__"))
    mix = dict(_mix("chat"), base_seed=7)
    (here / "traffic" / "dummy.json").write_text(json.dumps(mix))
    (here / "cells" / "smollm-135m.rns9.dummy.json").write_text(
        json.dumps({"rate_per_s": 1.5}))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({
        "name": "smollm-135m.rns9.dummy", "config": "smollm-135m.rns9",
        "traffic": "dummy", "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("smollm-135m.rns9.dummy", root=tmp_path,
                          here=here)
    assert cell.traffic["base_seed"] == 7 and cell.rate["rate_per_s"] == 1.5
    assert {m["name"] for m in cell.end_to_end} >= {"tokens_per_s",
                                                     "setup_s"}
    with pytest.raises(KeyError):
        spec.load_cell("no-such-cell", root=tmp_path, here=here)


def test_the_peak_table_refuses_an_unknown_device_kind():
    assert device.peaks("TPU v5 lite")["int8_ops"] == 393e12
    with pytest.raises(KeyError, match="TPU v9"):
        device.peaks("TPU v9")


def test_the_device_gate_refuses_a_cpu():
    import jax

    with pytest.raises(SystemExit, match="no TPU"):
        device.check_device(1, jax.devices())


def test_a_run_without_a_tpu_exits_non_zero_and_prints_no_result(tmp_path):
    """Here, and in a directory that holds only the benchmark's files."""
    only = tmp_path / "only"
    only.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", only)
    for p in BENCH["paths"]:
        shutil.copytree(ROOT / p, only / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    args = ["--workload", "smollm-135m.rns9.chat", "--seed", "1",
            "--seconds", "1", "--trace", "0"]
    for root in (ROOT, only):
        out = subprocess.run([sys.executable, "chipbench/run.py", *args],
                             cwd=root, capture_output=True, text=True,
                             timeout=120)
        assert out.returncode != 0
        assert '"correct"' not in out.stdout


def test_benchmark_json_keeps_to_its_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    names = ([c["name"] for c in BENCH["configs"]]
             + [w["name"] for w in BENCH["workloads"]]
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 0
    for c in BENCH["configs"]:
        f = json.loads((ROOT / c["file"]).read_text())
        assert f["name"] == c["name"] and f["reduced"] == c["reduced"]
        assert any(w["config"] == c["name"] for w in BENCH["workloads"])
    for w in BENCH["workloads"]:
        cell = spec.load_cell(w["name"])
        reports = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in reports and len(reports) >= 2
        assert cell.per_layer
        assert len(w["why"]) <= 200
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        measure.reader(m["name"])           # every metric has a reader
        for w in m.get("workloads", []):
            assert w in {x["name"] for x in BENCH["workloads"]}
            assert spec._reports(e2e[m["moves"]], w)


@pytest.mark.parametrize("name", [c["name"] for c in BENCH["configs"]])
def test_configuration_builds_the_program_config(name):
    """Through the architecture the file names, so that a configuration
    added later is covered as it is."""
    f = json.loads((ROOT / "chipbench" / "configs" / f"{name}.json")
                   .read_text())
    arch = spec.arch(f)
    cfg, scfg = arch.program_config(f)
    dm = arch.dims(f)
    assert (cfg.n_layers, cfg.vocab) == (dm.layers, dm.vocab)
    assert scfg.chunked_prefill and scfg.resident_weights
    assert scfg.rns_backend == "pallas_fused" and not scfg.audit
    # the benchmark's weights have the program's parameter layout
    import jax
    from repro.models import model as M

    want = jax.eval_shape(lambda: M.init_model(jax.random.PRNGKey(0),
                                               cfg)[0])
    got = jax.eval_shape(lambda: arch.make_weights(dm, 2**31 + 1))
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert [w.shape for w in jax.tree.leaves(want)] == [
        w.shape for w in jax.tree.leaves(got)]
    assert all(w.dtype == jax.numpy.float32 for w in jax.tree.leaves(got))
    assert got["embed"]["table"].shape == (dm.vocab, cfg.d_model)
