"""The readers of the program's own records (``chipbench/progtrace.py``),
on records and trace events made by hand; their silence on a run that
has none; the benchmark's own span readers left as they were; the
labels of the executable that ran; and the tool that reads a whole
run's program records (``chipbench/progprobe.py``)."""

import pathlib
import sys
import types

import jax
import numpy as np
import pytest

from chipbench import loop, measure, progprobe, progtrace, spec, xtrace
from chipbench.device import PEAKS
from chipbench.loop import Spans, Window
from chipbench.model import Dims

sys.path.insert(0, str(pathlib.Path(__file__).parent))
from test_bench_check import _gate, _tiny_cell  # noqa: E402

E = xtrace.Event
DEV = "/device:TPU:0"
SMOLLM = Dims(layers=30, d=576, heads=9, kv_heads=3, head_dim=64, ff=1536,
              vocab=49152, rope_theta=1e4, eps=1e-6, tied=True)
NEW = ("queue_wait_p95_ms.chat", "prefill_drain_p95_ms.chat",
       "step_host_ms.chat", "attn_gather_ms.chat", "attn_gather_ms.saturated",
       "rns_mlp_ms.saturated", "layer_scan_ms.saturated")


def _span(name, start, end, parent=None, step=None):
    return types.SimpleNamespace(name=name, start=start, end=end,
                                 parent=parent, step=step)


def _tracer(spans=(), events=None):
    return types.SimpleNamespace(spans=list(spans), events=events or {})


def _trace(ops, spans=(), modules=(), lo=0.0, hi=100.0):
    return xtrace.Trace(ops=list(ops), modules=list(modules),
                        spans=[E(xtrace.WINDOW, lo, hi - lo), *spans],
                        devices=[DEV])


def _run(program=None, trace=None):
    run = measure.RunData(
        dims=SMOLLM, budget=64, digits=9, peak=PEAKS["TPU v5 lite"],
        win=Window(10.0, 20.0), states=[], steps=[], spans=Spans(),
        trace=trace)
    if program is not None:
        run.program = program
    return run


# ------------------------------------------------------- request events ---
def test_queue_wait_and_prefill_drain_by_hand():
    events = {
        0: [("submit", 9.0), ("admit", 10.5), ("first_token", 11.0)],  # early
        1: [("submit", 10.0), ("admit", 10.2), ("first_token", 11.2),
            ("preempt", 11.5), ("admit", 12.0), ("first_token", 13.0)],
        2: [("submit", 12.0), ("admit", 14.0)],          # no first token
        3: [("submit", 19.0)],                           # never admitted
        4: [("submit", 15.0), ("admit", 15.5), ("first_token", 16.0),
            ("finish", 17.0)],
    }
    run = _run(progtrace.Program(_tracer(events=events), end=21.0))
    wait = measure.reader("queue_wait_p95_ms.chat")(run)
    drain = measure.reader("prefill_drain_p95_ms.chat")(run)
    # request 0 was submitted before the window; 3 waits to the end
    assert wait == pytest.approx(1e3 * np.percentile([0.2, 2.0, 2.0, 0.5],
                                                     95))
    # firsts only: 1 drained in 1.0; 2 still draining at the end (7.0)
    assert drain == pytest.approx(1e3 * np.percentile([1.0, 7.0, 0.5], 95))


def test_step_host_is_the_step_less_its_readback():
    spans = [_span("step", 9.0, 9.5, step=1),            # before the window
             _span("step", 10.0, 10.05, step=2),
             _span("schedule", 10.0, 10.001, 1, 2),
             _span("readback", 10.01, 10.045, 1, 2),
             _span("step", 11.0, 11.04, step=3),         # no lanes: no readback
             _span("schedule", 11.0, 11.001, 4, 3),
             _span("gc", 11.01, 11.02, 4, 3)]
    run = _run(progtrace.Program(_tracer(spans), end=21.0))
    got = measure.reader("step_host_ms.chat")(run)
    assert got == pytest.approx(1e3 * ((0.05 - 0.035) + 0.04) / 2)


def test_stall_lines_name_each_child_and_the_collector():
    spans = []
    for n in range(5):
        t = 10.0 + n
        d = 0.5 if n == 3 else 0.05
        i = len(spans)
        spans += [_span("step", t, t + d, step=n + 1),
                  _span("plan", t, t + 0.01, i, n + 1),
                  _span("readback", t + 0.01, t + d - 0.01, i, n + 1)]
        if n == 3:
            spans.append(_span("gc", t + 0.1, t + 0.4, i, n + 1))
    lines = progtrace.stall_lines(_tracer(spans), 10.0, 20.0)
    assert lines == ["[stall] step=4 ms=500.000 median_ms=50.000 "
                     "plan=10.000 readback=480.000 gc=300.000"]
    assert progtrace.stall_lines(_tracer(spans[:3]), 10.0, 20.0) == []


# --------------------------------------------------------- device scopes ---
HLO = """\
ENTRY %main.1 (p: f32[8]) -> s32[64] {
  %fusion.117 = bf16[2048,16,3,64]{3,2,1,0} fusion(%a), kind=kLoop, metadata={op_name="jit(_mixed_fn)/while/body/closed_call/attn_gather/gather"}
  %rns_fused_dot_tiles.30 = f32[64,1536]{1,0} custom-call(%b), metadata={op_name="jit(_mixed_fn)/while/body/closed_call/mlp/jit(rns_fused_dot_tiles)/pallas_call"}
  %dynamic-slice_bitcast_fusion.16 = s8[9,4096,12800]{2,1,0} fusion(%c), metadata={op_name="jit(_mixed_fn)/while/body/dynamic_slice"}
  ROOT %fusion.9 = s32[64]{0} fusion(%d), metadata={op_name="jit(_mixed_fn)/logits/argmax"}
  %iota.3 = s32[64]{0} iota(), metadata={op_name="jit(_mixed_fn)/iota"}
  %copy.105 = f32[30,2049,16,3,64]{1,4,3,2,0} copy(%e)
}
"""


def test_labels_from_hlo_name_every_instruction():
    assert progtrace.labels_from_hlo(HLO) == {
        "fusion.117": "attn_gather", "rns_fused_dot_tiles.30": "mlp",
        "dynamic-slice_bitcast_fusion.16": progtrace.SCAN,
        "fusion.9": "logits", "iota.3": progtrace.BARE,
        "copy.105": progtrace.NO_METADATA}
    nested = ('  %x.1 = f32[] add(%a), metadata={op_name='
              '"jit(f)/logits/norm/add"}')
    assert progtrace.labels_from_hlo(nested) == {"x.1": "norm"}


def _scoped_trace():
    # two runs of the packed step; the while loop encloses its body ops
    mods = [E("jit__mixed_fn(123)", 0, 40, DEV), E("jit__mixed_fn(123)", 50,
                                                   40, DEV),
            E("jit_broadcast_in_dim(9)", 95, 2, DEV)]
    ops = []
    for t in (0, 50):
        ops += [E("%while.12 = (s32[]) while(...)", t + 1, 37, DEV),
                E("%fusion.117 = bf16[2048,16,3,64]{3,2,1,0:T(8,128)} "
                  "fusion(...)", t + 2, 8, DEV),
                E("%rns_fused_dot_tiles.30 = f32[64,1536]{1,0} "
                  "custom-call(...)", t + 10, 12, DEV),
                E("%dynamic-slice_bitcast_fusion.16 = s8[9,4096,12800]{2,1,0}"
                  " fusion(...)", t + 22, 6, DEV),
                E("%copy.105 = f32[30,2049,16,3,64]{1,4,3,2,0} copy(...)",
                  t + 28, 4, DEV),
                E("%fusion.9 = s32[64]{0} fusion(...)", t + 38, 1, DEV)]
    ops.append(E("%fusion.117 = bf16[1]{0} fusion(...)", 95, 2, DEV))
    return _trace(ops, modules=mods)


def test_scoped_device_ms_by_hand():
    prog = progtrace.Program(_tracer(), end=21.0,
                             labels=progtrace.labels_from_hlo(HLO))
    run = _run(prog, _scoped_trace())
    ms = {n: measure.reader(n)(run)
          for n in ("attn_gather_ms.chat", "attn_gather_ms.saturated",
                    "rns_mlp_ms.saturated", "layer_scan_ms.saturated")}
    # per run: gather 8 ns, mlp 12 ns, the scan's slice 6 ns; the copy
    # with no metadata, the enclosing while and the op of another
    # program count in none of them
    assert ms == pytest.approx({
        "attn_gather_ms.chat": 8e-6, "attn_gather_ms.saturated": 8e-6,
        "rns_mlp_ms.saturated": 12e-6, "layer_scan_ms.saturated": 6e-6})
    assert progtrace.scoped_ms(run, {progtrace.NO_METADATA}) == \
        pytest.approx(4e-6)


@pytest.mark.parametrize("labels", [
    {"fusion.999": "mlp"},                       # another executable's
    {k: v for k, v in progtrace.labels_from_hlo(HLO).items()
     if k != "copy.105"},                        # one op of the step lacks one
    {k: progtrace.SCAN                           # no scope anywhere
     for k in progtrace.labels_from_hlo(HLO)},
], ids=["other-executable", "op-missing", "no-scope"])
def test_layer_scan_is_silent_where_no_op_has_a_known_scope(labels):
    prog = progtrace.Program(_tracer(), end=21.0, labels=labels)
    run = _run(prog, _scoped_trace())
    assert measure.reader("layer_scan_ms.saturated")(run) is None
    assert measure.reader("rns_mlp_ms.saturated")(run) is None


@pytest.mark.parametrize("name", NEW)
def test_new_readers_are_silent_without_program_records(name):
    """As on the parent commit, whose engine has no tracer and whose step
    has no scopes: no ``program`` on the run, or one with nothing in it."""
    read = measure.reader(name)
    tr = _scoped_trace()
    assert read(_run()) is None
    assert read(_run(trace=tr)) is None
    assert read(_run(types.SimpleNamespace(), tr)) is None
    assert read(_run(progtrace.Program(_tracer(), end=21.0), tr)) is None


# -------------------------------------------------------- host spans ---
def _profile(host_events, ops):
    ev = lambda n, s, d: types.SimpleNamespace(name=n, start_ns=s,   # noqa
                                               duration_ns=d)
    line = lambda n, evs: types.SimpleNamespace(name=n, events=evs)  # noqa
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name=DEV, lines=[
            line("XLA Ops", [ev(*o) for o in ops]),
            line("XLA Modules", [])]),
        types.SimpleNamespace(name="/host:CPU", lines=[
            line("python3", [ev(*h) for h in host_events])])])


OPS = [("a", 0, 10), ("b", 30, 10), ("c", 70, 30)]
BENCH_SPANS = [("bench.window", 0, 100), ("bench.step", 0, 60),
               ("bench.plan", 12, 20)]
PROGRAM_SPANS = [("repro.step", 0, 60), ("repro.plan", 12, 20),
                 ("repro.readback", 42, 26)]


def test_program_spans_leave_the_benchmark_readers_as_they_were():
    plain = xtrace.from_profile(_profile(BENCH_SPANS, OPS))
    both = xtrace.from_profile(_profile(BENCH_SPANS + PROGRAM_SPANS, OPS))
    assert both.spans == plain.spans
    assert xtrace.attribute_gaps(both) == xtrace.attribute_gaps(plain) == {
        "plan": 20, "step": 30}
    assert xtrace.idle_gaps(both) == xtrace.idle_gaps(plain)
    assert xtrace.top_ops(both) == xtrace.top_ops(plain)


def test_idle_gaps_go_to_the_innermost_program_span():
    tr = xtrace.from_profile(_profile(BENCH_SPANS + PROGRAM_SPANS, OPS))
    spans = [E(*s) for s in PROGRAM_SPANS + BENCH_SPANS[1:]]
    own = progtrace.program_trace(tr, spans)
    # 10..30 (mid 20): plan; 40..70 (mid 55): readback inside step; the
    # benchmark's spans are left out
    assert xtrace.attribute_gaps(own) == {"plan": 20, "readback": 30}
    assert own.window == tr.window
    assert xtrace.attribute_gaps(progtrace.program_trace(tr, [])) == {
        "outside_steps": 50}
    assert xtrace.attribute_gaps(tr) == {"plan": 20, "step": 30}


# --------------------------------------------- the executable that ran ---
@pytest.fixture
def no_compile_cache():
    """Off meanwhile, so that a compile is one and not a read of an entry
    that an earlier run wrote."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def test_step_labels_are_those_of_the_executable_that_ran(no_compile_cache):
    from repro.configs.base import get_config
    from repro.models import model as M
    from repro.serve.engine import ContinuousEngine, ServeConfig

    cfg = get_config("smollm-135m", smoke=True)
    params = M.init_model(jax.random.PRNGKey(0), cfg)[0]
    eng = ContinuousEngine(params, cfg, ServeConfig(
        max_cache=48, max_seqs=4, page_size=8, chunked_prefill=True,
        token_budget=16, chunk_size=8, max_new_tokens=3))
    # before any step, lowering the arguments compiles anew
    assert progtrace.step_labels(eng._mixed, eng.params, eng.cache, 16) == {}
    eng.submit(np.arange(1, 20, dtype=np.int32))
    while eng.sched.has_work:
        eng.step()
    labels = progtrace.step_labels(eng._mixed, eng.params, eng.cache, 16)
    assert set(progtrace.SCOPES) <= set(labels.values())
    assert progtrace.SCAN in labels.values()
    assert eng._mixed._cache_size() == 1


# ------------------------------------------------------- the probe tool ---
def test_the_probe_reads_the_program_records_of_whole_runs(monkeypatch):
    """Whole runs at a small size on the CPU, with the tracer off, on, and
    on under the profiler: the program's readers read, the labels are
    found, idle gaps go to the program's spans, and the benchmark's own
    modules come out as they went in."""
    cell = _tiny_cell()
    monkeypatch.setattr(spec, "load_cell", lambda name: cell)
    before = (loop.Spans, loop.drive, xtrace.load, measure.RunData)
    got = {mode: progprobe.probe(cell.name, 2**31 + 21, 6.0, mode,
                                 gate=_gate)
           for mode in ("off", "on", "traced")}
    assert (loop.Spans, loop.drive, xtrace.load, measure.RunData) == before
    assert all(r["correct"] and r["step_wall_ms"] > 0 for r in got.values())
    assert "readers" not in got["off"] and got["off"]["stalls"] >= 0
    on = got["on"]
    for name in ("queue_wait_p95_ms.chat", "prefill_drain_p95_ms.chat",
                 "step_host_ms.chat"):
        assert on["readers"][name] > 0, name
    split = on["ttft_split_ms"]
    # the first token's event comes inside the step that returns it
    assert split["n"] > 0 and split["sum"] <= split["ttft"]
    assert {"schedule", "plan", "readback", "stats"} <= set(on["child_ms"])
    traced = got["traced"]
    assert traced["labels"] > 0
    assert traced["readers"]["step_host_ms.chat"] > 0
    assert set(traced["idle_gaps_program"]) <= set(traced["child_ms"]) | {
        "step", "gc", "outside_steps"}
    assert set(traced["idle_gaps"]) <= {
        "schedule", "plan", "mixed_dispatch", "set_tables", "step",
        "outside_steps"}
