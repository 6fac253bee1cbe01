"""Chunked prefill + packed mixed-phase batching, pinned differentially.

The continuous engine's chunked mode replaces the separate [1, Tpad]
prefill and [R, 1]/[R, W] decode programs with ONE jitted packed step
over a fixed token budget.  These tests pin the two contracts that make
that safe:

  * **token identity** — every request's emitted stream equals the solo
    bucketed run (same params, same prompt, no batching), for float and
    RNS datapaths (defer on/off), gqa and MLA attention, prefix cache
    on/off, speculative decoding on/off, across preemption/readmission,
    and for prompts longer than any whole-prompt prefill could admit;
  * **one compile** — the mixed step recompiles zero times across phase
    mixes (``_mixed._cache_size() == 1`` after arbitrarily varied
    traffic), because its shapes depend only on the token budget and
    the page geometry.

Plus the ServeConfig cross-feature validation (named-field errors) and
the per-step TTFT / phase accounting the scheduler's bounded-TTFT
guarantee is observed through.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.core.rns_matmul import RnsDotConfig
from repro.models import model as M
from repro.models import transformer as tf
from repro.serve import kv_cache as kv
from repro.serve.engine import ContinuousEngine, Engine, ServeConfig


@pytest.fixture(scope="module")
def gqa_model():
    cfg = get_config("smollm-135m", smoke=True)
    return cfg, M.init_model(jax.random.PRNGKey(0), cfg)[0]


@pytest.fixture(scope="module")
def mla_model():
    cfg = dataclasses.replace(get_config("deepseek-v2-236b", smoke=True),
                              mlp_types=("dense",) * 4, moe=None)
    return cfg, M.init_model(jax.random.PRNGKey(1), cfg)[0]


def _rns(cfg, defer=False):
    return dataclasses.replace(
        cfg, rns=RnsDotConfig(profile="rns9", qx=8, qw=8, defer=defer),
        rns_targets="mlp")


def _prompts(vocab, lens=(13, 21, 5, 9), seed=11):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, vocab, (t,)).astype(np.int32) for t in lens]


def _solo(params, cfg, prompts, max_new):
    eng = Engine(params, cfg, ServeConfig(max_cache=64,
                                          max_new_tokens=max_new))
    return {i: eng.generate(p[None])[0].tolist()
            for i, p in enumerate(prompts)}


def _chunked(params, cfg, prompts, max_new=6, **kw):
    base = dict(max_cache=48, max_seqs=4, page_size=8,
                max_new_tokens=max_new, chunked_prefill=True,
                token_budget=16, chunk_size=8)
    base.update(kw)
    eng = ContinuousEngine(params, cfg, ServeConfig(**base))
    out, stats = eng.run(prompts)
    return eng, {r: v.tolist() for r, v in out.items()}, stats


# --------------------------------------------------- identity matrix ---
GQA_CASES = {
    "float": (False, None, {}),
    "float_spec_prefix": (False, None, dict(spec_decode=True, spec_k=3,
                                            prefix_cache=True)),
    "rns": (True, False, {}),
    "rns_defer_spec_prefix": (True, True, dict(spec_decode=True, spec_k=2,
                                               prefix_cache=True)),
}


@pytest.mark.parametrize("case", sorted(GQA_CASES))
def test_chunked_token_identical_to_solo_gqa(gqa_model, case):
    cfg, params = gqa_model
    use_rns, defer, kw = GQA_CASES[case]
    if use_rns:
        cfg = _rns(cfg, defer=defer)
    prompts = _prompts(cfg.vocab)
    want = _solo(params, cfg, prompts, 6)
    eng, got, stats = _chunked(params, cfg, prompts, **kw)
    assert got == want, case
    assert eng._mixed._cache_size() == 1
    # at least one packed step really mixed both phases
    assert any(s["prefill_tokens"] > 0 and s["decode_tokens"] > 0
               for s in stats["steps"]), case


MLA_CASES = {
    "float": (False, {}),
    "rns_spec": (True, dict(spec_decode=True, spec_k=2)),
}


@pytest.mark.parametrize("case", sorted(MLA_CASES))
def test_chunked_token_identical_to_solo_mla(mla_model, case):
    cfg, params = mla_model
    use_rns, kw = MLA_CASES[case]
    if use_rns:
        cfg = _rns(cfg)
    prompts = _prompts(cfg.vocab)
    want = _solo(params, cfg, prompts, 6)
    eng, got, _ = _chunked(params, cfg, prompts, **kw)
    assert got == want, case
    assert eng._mixed._cache_size() == 1


def test_chunked_admits_prompts_beyond_prompt_pad(gqa_model):
    """Whole-prompt prefill rejects prompts longer than prompt_pad;
    chunked mode streams them in and still matches the solo run."""
    cfg, params = gqa_model
    prompts = _prompts(cfg.vocab, lens=(21,))
    with pytest.raises(ValueError, match="prompt"):
        ContinuousEngine(params, cfg, ServeConfig(
            max_cache=48, prompt_pad=8)).submit(prompts[0])
    want = _solo(params, cfg, prompts, 6)
    _, got, _ = _chunked(params, cfg, prompts, prompt_pad=8)
    assert got == want


def test_chunked_preempt_readmit_token_identical(gqa_model):
    """A pool too small for the full load preempts rows mid-stream
    (possibly mid-prefill); greedy recompute readmission keeps every
    stream equal to its uninterrupted solo run."""
    cfg, params = gqa_model
    prompts = _prompts(cfg.vocab, lens=(10, 9, 6), seed=17)
    want = _solo(params, cfg, prompts, 6)
    _, got, stats = _chunked(params, cfg, prompts, max_seqs=3, n_pages=8,
                             page_size=4, max_cache=24, token_budget=8,
                             chunk_size=4)
    assert stats["n_preemptions"] > 0        # the scenario really fired
    assert got == want


def test_one_mixed_compile_across_phase_mixes(gqa_model):
    """Zero per-mix recompiles: wildly different traffic shapes reuse
    the one mixed-step executable."""
    cfg, params = gqa_model
    eng = ContinuousEngine(params, cfg, ServeConfig(
        max_cache=48, max_seqs=4, page_size=8, max_new_tokens=4,
        chunked_prefill=True, token_budget=16, chunk_size=8))
    for lens in ((13, 21, 5, 9), (3,), (17, 2), (8, 8, 8, 8)):
        eng.run(_prompts(cfg.vocab, lens=lens))
    assert eng._mixed._cache_size() == 1


def test_ttft_and_phase_stats(gqa_model):
    cfg, params = gqa_model
    prompts = _prompts(cfg.vocab)
    _, _, stats = _chunked(params, cfg, prompts)
    assert 0.0 < stats["ttft_p50_s"] <= stats["ttft_p95_s"]
    assert stats["ttft_p95_s"] <= stats["latency_p99_s"]
    for s in stats["steps"]:
        assert s["prefill_tokens"] + s["decode_tokens"] >= 0
        assert s["ttft_ms"] >= 0.0
    # chunked prefill touches each non-shared prompt token exactly once
    assert (sum(s["prefill_tokens"] for s in stats["steps"])
            == sum(len(p) for p in prompts))
    assert (sum(s["decode_tokens"] for s in stats["steps"])
            == stats["total_new_tokens"])


# ------------------------------------------ carried page pool ---------
def _slab_apply_blocks(blocks, h, cfg, *, mode, cache, packed=None):
    """Reference layer loop over the paged cache: the pools are scanned
    as ``xs``/``ys``, so each period runs on its own slab of the pool
    (``[P, bs*F]``, handed to the layer as a one-layer pool) and the
    slabs are stacked into a fresh pool."""
    def body(h, xs):
        new = {}
        for j in range(cfg.period):
            lj = f"l{j}"
            c = {k: v[None] if k.endswith("_pages") else v
                 for k, v in xs["cache"][lj].items()}
            h, nc, _, _ = tf._apply_layer(
                xs["params"][lj], h, cfg, cfg.layer_types[j],
                cfg.mlp_types[j], mode=mode, positions=None, kv_mask=None,
                enc_out=None, cache=dict(c, layer=0), packed=packed)
            new[lj] = {k: v[0] if k.endswith("_pages") else v
                       for k, v in nc.items() if k != "layer"}
        return h, new

    h, ys = jax.lax.scan(body, h, {"params": blocks, "cache": cache})
    return h, ys, jnp.zeros((), jnp.float32)


def _paged_inputs(cfg, kind, rng):
    """A random pool, block tables over 3 slots (slot 1 empty for the
    decode kinds), and the step's inputs.  Returns (cache, args,
    written) with ``written`` the (page, row) pairs of valid tokens."""
    bs, nb, R = 4, 4, 3
    pcfg = kv.PagedCacheConfig(page_size=bs, n_pages=1 + R * nb + 3,
                               max_seqs=R, max_blocks=nb)
    cache = kv.make_paged_cache(cfg, pcfg, dtype=jnp.float32)
    cache = jax.tree.map(
        lambda x: jnp.asarray(rng.standard_normal(x.shape), x.dtype)
        if x.dtype == jnp.float32 else x, cache)
    bt = rng.permutation(np.arange(1, pcfg.n_pages))[:R * nb]
    bt = bt.reshape(R, nb).astype(np.int32)
    lengths = np.array([9, 5, 2], np.int32)
    if kind == "mixed_step":
        # slot 0 and 2 decode at their lengths, slot 1 prefills a chunk
        # over positions 4..11; pad lanes (seg -1) aim at slot 0's live
        # positions 0..3 and must land on the trash page instead
        seg = np.array([0] + [1] * 8 + [2] + [-1] * 6, np.int32)
        pos = np.array([9] + list(range(4, 12)) + [2] + [0, 1, 2, 3, 0, 1],
                       np.int32)
        dec = np.array([True] + [False] * 8 + [True] + [False] * 6)
        valid = seg >= 0
        tokens = rng.integers(1, cfg.vocab, seg.shape).astype(np.int32)
        args = tuple(map(jnp.asarray, (tokens, seg, pos, dec, valid)))
        written = {(bt[s, p // bs], p % bs)
                   for s, p in zip(seg[valid], pos[valid])}
    else:
        W = 1 if kind == "decode_step" else 3
        bt[1] = kv.TRASH_PAGE               # an empty slot
        active = np.array([True, False, True])
        tokens = rng.integers(1, cfg.vocab, (R, W)).astype(np.int32)
        args = (jnp.asarray(tokens), jnp.asarray(active))
        written = {(bt[r, p // bs], p % bs) for r in (0, 2)
                   for p in range(lengths[r], lengths[r] + W)}
    cache = kv.set_tables(cache, bt, lengths)
    return cache, args, written


def _run_step(params, cfg, kind, cache, args):
    if kind == "mixed_step":
        fn = lambda p, c, *a: M.mixed_step(p, cfg, *a, c)       # noqa: E731
    elif kind == "decode_step":
        fn = lambda p, c, t, act: M.decode_step(p, cfg, t, c,   # noqa: E731
                                                active=act)
    else:
        fn = lambda p, c, t, act: M.decode_window(p, cfg, t, c,  # noqa: E731
                                                  active=act)
    return jax.jit(fn)(params, cache, *args)


@pytest.mark.parametrize("kind", ["mixed_step", "decode_step",
                                  "decode_window"])
@pytest.mark.parametrize("layer", ["attn", "mla"])
def test_carried_pool_step_bitwise(gqa_model, mla_model, monkeypatch,
                                   layer, kind):
    """The layer loop carries the whole page pool and updates it in
    place; every paged step then equals, bitwise, the loop that scans
    each layer's slab of the pool: the same logits and the same pool.
    A step changes only the rows its valid tokens write and the trash
    page: pad lanes and empty slots land on trash, every other page
    keeps its bytes."""
    cfg, params = gqa_model if layer == "attn" else mla_model
    cache, args, written = _paged_inputs(cfg, kind,
                                         np.random.default_rng(23))
    logits, new = _run_step(params, cfg, kind, cache, args)
    monkeypatch.setattr(tf, "apply_blocks", _slab_apply_blocks)
    want_logits, want = _run_step(params, cfg, kind, cache, args)

    assert (np.asarray(logits) == np.asarray(want_logits)).all()
    assert jax.tree.structure(new) == jax.tree.structure(want)
    for lj, c in new.items():
        for name, pool in c.items():
            if not name.endswith("_pages"):
                assert (np.asarray(pool) == np.asarray(want[lj][name])).all()
                continue
            feat = kv.page_feat(cfg, name)
            got = np.asarray(kv.page_view(pool, feat))  # [n, P, bs, *feat]
            assert (got == np.asarray(kv.page_view(want[lj][name],
                                                   feat))).all(), name
            before = np.asarray(kv.page_view(cache[lj][name], feat))
            changed = (got != before).reshape(got.shape[:3] + (-1,))
            changed = changed.any(axis=(0, 3))           # [P, bs]
            rows = {(int(p), int(r)) for p, r in zip(*np.nonzero(changed))}
            assert rows - {(kv.TRASH_PAGE, r) for r in range(got.shape[2])} \
                == written, name
            assert changed[kv.TRASH_PAGE].any(), name    # pad/empty lanes


# ------------------------------------------- cross-feature validation ---
@pytest.mark.parametrize("kw,field", [
    (dict(chunked_prefill=True, token_budget=0), "token_budget"),
    (dict(chunked_prefill=True, spec_decode=True, spec_k=8,
          token_budget=4), "token_budget"),
    (dict(chunked_prefill=True, cache_dtype="bfloat16"), "cache_dtype"),
    (dict(chunk_size=8), "chunk_size"),
    (dict(chunked_prefill=True, chunk_size=0), "chunk_size"),
    (dict(chunked_prefill=True, chunk_size=12, page_size=8),
     "chunk_size"),
    (dict(chunked_prefill=True, chunk_size=32, token_budget=16),
     "chunk_size"),
    (dict(prefill_reserve=4), "prefill_reserve"),
    (dict(chunked_prefill=True, prefill_reserve=16, token_budget=16),
     "prefill_reserve"),
])
def test_serve_config_cross_feature_errors(kw, field):
    """Incoherent chunked configs fail fast, naming the bad field."""
    with pytest.raises(ValueError, match=field):
        ServeConfig(max_cache=48, **kw)


def test_chunked_mla_rns_all_rejected(mla_model):
    """Packed chunk tokens re-expand gathered latents; with
    rns_targets='all' the original quantization grids are gone, so the
    combination is refused up front rather than silently drifting."""
    cfg, params = mla_model
    cfg = dataclasses.replace(
        cfg, rns=RnsDotConfig(profile="rns9", qx=8, qw=8),
        rns_targets="all")
    with pytest.raises(NotImplementedError, match="rns_targets"):
        ContinuousEngine(params, cfg, ServeConfig(
            max_cache=48, chunked_prefill=True, token_budget=16))
