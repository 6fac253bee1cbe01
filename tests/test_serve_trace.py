"""The serve engine's in-program tracer (serve/trace.py): spans, request
events and the garbage-collector hook, the engine's span tree and event
order, and the named scopes that label every op of the packed step."""

import dataclasses
import gc
import re

import numpy as np
import pytest

import jax

from repro.configs.base import get_config
from repro.models import model as M
from repro.serve.engine import ContinuousEngine, ServeConfig
from repro.serve.trace import Tracer

STEP_CHILDREN = ("schedule", "cow", "plan", "pack", "set_tables", "upload",
                 "dispatch", "readback", "emit", "stats")
SCOPES = {"embed", "norm", "qkv", "kv_write", "attn_gather", "attn",
          "attn_out", "mlp", "logits"}


@pytest.fixture
def annotations(monkeypatch):
    """Names of every TraceAnnotation made while the test runs."""
    made = []

    class Fake:
        def __init__(self, name):
            made.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Fake)
    return made


# ----------------------------------------------------------- the tracer ---
def test_off_tracer_records_nothing_and_annotates_nothing(annotations):
    tr = Tracer()
    tr.annotate = True
    with tr.span("step", 1):
        with tr.span("plan"):
            pass
    tr.event(0, "submit")
    assert tr.span("a") is tr.span("b")        # one shared null context
    assert tr.spans == [] and tr.events == {} and annotations == []
    assert tr._gc not in gc.callbacks


def test_spans_nest_with_parent_and_step():
    tr = Tracer(on=True)
    try:
        with tr.span("step", 7):
            with tr.span("schedule"):
                pass
            with tr.span("emit"):
                with tr.span("inner"):
                    pass
        with tr.span("step", 8):
            pass
    finally:
        tr.on = False
    got = [(s.name, s.parent, s.step) for s in tr.spans]
    assert got == [("step", None, 7), ("schedule", 0, 7), ("emit", 0, 7),
                   ("inner", 2, 7), ("step", None, 8)]
    for s in tr.spans:
        assert s.end is not None and s.start <= s.end
        if s.parent is not None:
            p = tr.spans[s.parent]
            assert p.start <= s.start and s.end <= p.end


def test_events_by_request_id():
    tr = Tracer(on=True)
    tr.event(3, "submit", 1.0)
    tr.event(4, "submit", 2.0)
    tr.event(3, "admit", 2.5)
    tr.event(3, "first_token")
    tr.on = False
    assert tr.events[4] == [("submit", 2.0)]
    assert [k for k, _ in tr.events[3]] == ["submit", "admit", "first_token"]
    assert tr.events[3][2][1] > 2.5            # stamped now, on perf_counter
    tr.event(3, "finish")                      # off again: not recorded
    assert len(tr.events[3]) == 3


def test_annotations_only_while_asked(annotations):
    tr = Tracer(on=True)
    with tr.span("step", 1):
        pass
    tr.annotate = True
    with tr.span("step", 2):
        with tr.span("readback"):
            pass
    tr.on = False
    assert annotations == ["repro.step", "repro.readback"]


def test_gc_passes_are_spans_and_the_hook_goes_when_off():
    tr = Tracer(on=True)
    assert tr._gc in gc.callbacks
    with tr.span("step", 1):
        gc.collect()
    tr.on = False
    assert tr._gc not in gc.callbacks
    gcs = [s for s in tr.spans if s.name == "gc"]
    assert gcs and all(s.parent == 0 and s.step == 1 for s in gcs)
    n = len(tr.spans)
    gc.collect()
    assert len(tr.spans) == n


# ----------------------------------------------------------- the engine ---
@pytest.fixture(scope="module")
def smoke():
    cfg = get_config("smollm-135m", smoke=True)
    return cfg, M.init_model(jax.random.PRNGKey(0), cfg)[0]


def _serve(cfg, params, tracer, chunked):
    kw = dict(chunked_prefill=True, token_budget=16, chunk_size=8) \
        if chunked else {}
    eng = ContinuousEngine(params, cfg, ServeConfig(
        max_cache=48, max_seqs=4, page_size=8, max_new_tokens=5, **kw))
    if tracer is not None:
        eng.tracer = tracer
    rng = np.random.default_rng(5)
    rids = [eng.submit(rng.integers(1, cfg.vocab, (n,)).astype(np.int32))
            for n in (13, 21, 5, 9, 17)]
    while eng.sched.has_work:
        eng.step()
    return eng, {r: eng.results[r].tolist() for r in rids}


@pytest.mark.parametrize("chunked", [True, False], ids=["mixed", "phased"])
def test_engine_spans_and_request_events(smoke, chunked):
    cfg, params = smoke
    _, want = _serve(cfg, params, None, chunked)
    tr = Tracer(on=True)
    eng, got = _serve(cfg, params, tr, chunked)
    tr.on = False
    assert got == want                         # the tracer changes no token
    if chunked:
        assert eng._mixed._cache_size() == 1

    steps = [i for i, s in enumerate(tr.spans) if s.name == "step"]
    assert [tr.spans[i].step for i in steps] == list(
        range(1, eng._step_idx + 1))
    for i in steps:
        st = tr.spans[i]
        kids = [s for s in tr.spans if s.parent == i and s.name != "gc"]
        assert kids and kids[-1].name == "stats"
        order = [STEP_CHILDREN.index(s.name) for s in kids]
        assert order == sorted(order), [s.name for s in kids]
        for s in kids:
            assert s.step == st.step
            assert st.start <= s.start <= s.end <= st.end
    if chunked:
        full = [{s.name for s in tr.spans if s.parent == i} for i in steps]
        assert {"schedule", "plan", "pack", "set_tables", "upload",
                "dispatch", "readback", "emit", "stats"} <= full[0]

    assert sorted(tr.events) == sorted(got)
    for rid, evs in tr.events.items():
        kinds = [k for k, _ in evs]
        assert kinds == ["submit", "admit", "first_token", "finish"], kinds
        times = [t for _, t in evs]
        assert times == sorted(times)
        assert times[2] - times[0] == eng.ttfts[rid]
        assert times[3] - times[0] == eng.latencies[rid]


@pytest.mark.parametrize("arch", ["gqa", "mla"])
def test_every_op_of_the_block_body_carries_a_scope(smoke, arch):
    """In the compiled packed step, every op that the layer's own code
    made names one of the scopes in its ``op_name``.  What is left
    unscoped are the scan's slices and updates, the loop counter, and
    the literals that the compiler hoists (constants and their
    broadcasts)."""
    cfg, params = smoke
    if arch == "mla":
        cfg = dataclasses.replace(get_config("deepseek-v2-236b", smoke=True),
                                  mlp_types=("dense",) * 4, moe=None)
        params = M.init_model(jax.random.PRNGKey(1), cfg)[0]
    eng = ContinuousEngine(params, cfg, ServeConfig(
        max_cache=48, max_seqs=4, page_size=8, chunked_prefill=True,
        token_budget=16, chunk_size=8))
    z = np.zeros((16,), np.int32)
    zb = np.zeros((16,), bool)
    text = eng._mixed.lower(eng.params, z, z, z, zb, zb,
                            eng.cache).compile().as_text()
    inst = re.compile(r'^\s*(?:ROOT )?%\S+ = \S+ ([\w-]+)\((\S*).*'
                      r'op_name="([^"]*)"')
    body, seen = [], set()
    for line in text.splitlines():
        m = inst.match(line)
        if m and "/while/body/closed_call" in m.group(3):
            body.append(m.groups())
            seen.update(SCOPES.intersection(m.group(3).split("/")))
    assert body
    bare = [(op, name) for op, arg, name in body
            if not SCOPES.intersection(name.split("/"))
            and op != "constant"
            and not (op == "broadcast" and arg.startswith("%constant"))]
    assert bare == []
    assert seen == SCOPES - {"embed", "logits"}
    top = [m.group(3) for m in map(inst.match, text.splitlines())
           if m and "/while/" not in m.group(3)]
    assert any("/embed/" in n for n in top)
    assert any("/logits/" in n for n in top)
