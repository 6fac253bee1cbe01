"""The reduction from trace events to numbers, on events made by hand."""

import pytest

from chipbench import flops, measure, xtrace
from chipbench.archs import dense
from chipbench.device import PEAKS
from chipbench.loop import Spans, StepRecord, Window
from chipbench.model import Dims


E = xtrace.Event
DEV = "/device:TPU:0"


def _trace(ops, spans=(), modules=(), lo=0.0, hi=100.0):
    return xtrace.Trace(ops=list(ops), modules=list(modules),
                        spans=[E(xtrace.WINDOW, lo, hi - lo), *spans],
                        devices=sorted({e.device for e in ops} or {DEV}))


def test_union_merges_overlaps_and_touching_intervals():
    assert xtrace.union([(5, 7), (0, 2), (1, 3), (3, 4), (10, 11)]) == [
        (0, 4), (5, 7), (10, 11)]


def test_busy_is_a_union_clipped_to_the_window():
    ops = [E("a", -10, 20, device=DEV),     # 0..10 inside
           E("b", 5, 10, device=DEV),       # overlaps a: adds 10..15
           E("c", 50, 10, device=DEV),
           E("d", 95, 30, device=DEV)]      # clipped at 100
    tr = _trace(ops)
    assert xtrace.busy_ns(tr) == pytest.approx(15 + 10 + 5)
    assert xtrace.idle_gaps(tr) == [(15, 50), (60, 95)]


def test_busy_is_averaged_over_devices():
    ops = [E("a", 0, 40, device="/device:TPU:0"),
           E("a", 0, 20, device="/device:TPU:1")]
    assert xtrace.busy_ns(_trace(ops)) == pytest.approx(30)


def test_idle_gaps_go_to_the_innermost_host_span():
    ops = [E("a", 0, 10, device=DEV), E("b", 30, 10, device=DEV),
           E("c", 70, 30, device=DEV)]
    spans = [E("bench.step", 0, 60), E("bench.plan", 12, 20)]
    got = xtrace.attribute_gaps(_trace(ops, spans))
    # 10..30 (mid 20): inside plan and step -> plan; 40..70 (mid 55): step
    assert got == {"plan": 20, "step": 30}


def test_op_and_module_time_sum_matching_events_in_the_window():
    ops = [E("x_fused_dot_kernel", 10, 3, device=DEV),
           E("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x_fused_dot_kernel)",
             14, 5, device=DEV),
           E("x_fused_dot_kernel", 20, 4, device=DEV),
           E("x_fused_dot_kernel", 200, 4, device=DEV)]
    mods = [E("jit__mixed_fn", 9, 20, device=DEV),
            E("jit_other", 30, 5, device=DEV)]
    tr = _trace(ops, modules=mods)
    assert xtrace.op_time(tr, lambda n: "fused_dot" in n) == (7, 2)
    assert xtrace.module_time(tr, lambda n: "_mixed" in n) == (20, 1)
    [[name, secs]] = xtrace.top_ops(tr, 1)
    assert name == "x_fused_dot_kernel" and secs == pytest.approx(7e-9)


def test_top_ops_skip_enclosing_loops_and_shorten_names():
    ops = [E("%while.1 = (s32[]{:T(128)}) while(...)", 0, 50, device=DEV),
           E("%fusion.2 = bf16[8,4]{1,0:T(4,128)} fusion(f32[8,4]{1,0} %a)",
             1, 30, device=DEV),
           E("%rns_fused_dot_tiles.3 = f32[64,8]{1,0} custom-call(...)",
             31, 10, device=DEV)]
    got = xtrace.top_ops(_trace(ops))
    assert [n for n, _ in got] == ["fusion.2 = bf16[8,4]",
                                   "rns_fused_dot_tiles.3 = f32[64,8]"]


def test_fused_dot_cost_by_hand():
    ops, nbytes = flops.fused_dot_cost(rows=2, d_in=3, d_out=5, digits=9)
    assert ops == 2 * 9 * 2 * 3 * 5
    assert nbytes == 4 * 2 * 3 + 4 * 2 + 9 * 3 * 5 + 4 * 2 * 5


SMOLLM = Dims(layers=30, d=576, heads=9, kv_heads=3, head_dim=64, ff=1536,
              vocab=49152, rope_theta=1e4, eps=1e-6, tied=True)
GRANITE = Dims(layers=3, d=4096, heads=32, kv_heads=8, head_dim=128,
               ff=12800, vocab=49155, rope_theta=1e7, eps=1e-6, tied=False)


@pytest.mark.parametrize("dm,per_layer,head", [
    (SMOLLM, 576 * (576 + 2 * 192) + 576 * 576 + 3 * 576 * 1536,
     576 * 49152),
    (GRANITE, 4096 * (4096 + 2 * 1024) + 4096 * 4096 + 3 * 4096 * 12800,
     4096 * 49155),
])
def test_model_flops_per_token(dm, per_layer, head):
    assert flops.matmul_params(dm) == dm.layers * per_layer + head
    # one token at context 10: 2 per weight + 4*H*D per key per layer
    assert flops.model_flops(dm, 1, 10) == pytest.approx(
        2 * (dm.layers * per_layer + head)
        + 4 * dm.layers * dm.heads * dm.head_dim * 10)


@pytest.mark.parametrize("dm,bound", [(SMOLLM, "memory"),
                                      (GRANITE, "memory")])
def test_fused_launches_of_one_packed_step(dm, bound):
    launches = flops.mlp_launches(dm, rows=64, digits=9)
    assert len(launches) == 3 * dm.layers
    ops = sum(o for o, _ in launches)
    assert ops == pytest.approx(2 * 9 * 64 * 3 * dm.d * dm.ff * dm.layers)
    digit_bytes = 9 * 3 * dm.d * dm.ff * dm.layers
    assert sum(b for _, b in launches) > digit_bytes
    peak = PEAKS["TPU v5 lite"]
    assert {flops.least_time(o, b, peak)[1] for o, b in launches} == {bound}


def _run_data(trace=None, steps=(), spans=None, states=()):
    return measure.RunData(
        arch=dense, dims=SMOLLM, budget=64, digits=9, peak=PEAKS["TPU v5 lite"],
        win=Window(0.0, 2.0), states=list(states), steps=list(steps),
        spans=spans or Spans(), trace=trace)


def test_roofline_reader_by_hand():
    """Two whole steps of launches, each taking twice its least time."""
    per_step = flops.mlp_launches(SMOLLM, 64, 9)
    peak = PEAKS["TPU v5 lite"]
    least = sum(flops.least_time(o, b, peak)[0] for o, b in per_step)
    n = 2 * len(per_step)
    each = 2 * least / len(per_step) * 1e9          # ns per launch
    ops = [E("rns_fused_dot_kernel", 1 + i * each, each, device=DEV)
           for i in range(n)]
    tr = _trace(ops, hi=1e12)
    got = measure.reader("rns_kernel_roofline.saturated")(_run_data(tr))
    assert got == pytest.approx(50.0)


def test_mfu_and_lane_fill_readers_by_hand():
    steps = [StepRecord(0.1, 0.2, lanes=64, context=640),
             StepRecord(0.3, 0.4, lanes=32, context=320),
             StepRecord(2.5, 2.6, lanes=64, context=640)]
    run = _run_data(steps=steps)
    assert measure.reader("lane_fill.saturated")(run) == pytest.approx(75.0)
    want = 100 * flops.model_flops(SMOLLM, 96, 960) / (2.0 * 197e12)
    assert measure.reader("step_mfu.saturated")(run) == pytest.approx(want)


def test_idle_share_reader_and_silence_without_a_trace():
    tr = _trace([E("a", 0, 25, device=DEV)])
    assert measure.reader("device_idle_share.chat")(_run_data(tr)) == \
        pytest.approx(75.0)
    for name in ("device_idle_share.chat", "step_device_ms.chat",
                 "rns_kernel_roofline.saturated"):
        assert measure.reader(name)(_run_data()) is None


def test_a_metric_without_a_reader_is_an_error():
    with pytest.raises(KeyError):
        measure.reader("no_such_metric.chat")


def test_end_to_end_numbers_cover_every_request_and_gap_of_the_window():
    from chipbench.loop import ReqState

    win = Window(10.0, 20.0)
    a = ReqState(due=9.0, in_window=False, prompt=None, max_new=3,
                 times=[9.5, 10.5, 11.0])          # two tokens in window
    b = ReqState(due=12.0, in_window=True, prompt=None, max_new=3,
                 times=[13.0, 15.0, 21.0])         # last one after close
    c = ReqState(due=19.0, in_window=True, prompt=None, max_new=3)
    states = [a, b, c]
    assert measure.tokens_per_s(states, win) == pytest.approx(4 / 10)
    # a request with no first token by the deadline counts at its wait
    assert measure.ttfts(states, 30.0) == ([1.0, 11.0], 1)
    # gaps whose later token came out in the window: a's 1.0 and 0.5,
    # b's 2.0 (15.0 - 13.0); b's last gap ends after the close
    assert sorted(measure.itls(states, win)) == [0.5, 1.0, 2.0]
