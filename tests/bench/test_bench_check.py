"""The comparison that decides ``correct``: a whole run at a small size
with the chip gate skipped comes out correct, and each fault planted in
the timed path makes it come out not correct; the int4 control fails
the smollm-135m limit at its published widths."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import check, reference, run, spec
from chipbench.model import Dims, make_weights

from conftest import ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
VOCAB = 256


def _tiny_cell():
    f = json.loads((ROOT / "chipbench" / "configs" / "smollm-135m.rns9.json")
                   .read_text())
    f["model"].update(num_hidden_layers=2, hidden_size=64,
                      intermediate_size=128, num_attention_heads=4,
                      num_key_value_heads=2, head_dim=16, vocab_size=VOCAB)
    f["serve"].update(max_cache=128, token_budget=16, max_seqs=4)
    f["numerics"]["rns_backend"] = "pallas_fused_interpret"
    # this size's own limit: sound runs read < 0.01 here, faults > 0.1
    f["check"].update(max_logit_gap=0.05, sample_tokens=64)
    mix = json.loads((ROOT / "chipbench" / "traffic" / "chat.json")
                     .read_text())
    mix.update(prompt={"dist": "lognormal", "median": 24, "sigma": 0.5,
                       "min": 4, "max": 80},
               output={"dist": "lognormal", "median": 8, "sigma": 0.5,
                       "min": 2, "max": 30},
               max_total=128, preroll_s=0.5, postroll_s=5)
    return spec.Cell(
        name="smollm-135m.rns9.chat", chips=1, config=f, traffic=mix,
        rate={"rate_per_s": 3.0},
        end_to_end=[m for m in BENCH["end_to_end"]
                    if spec._reports(m, "smollm-135m.rns9.chat")],
        per_layer=[m for m in BENCH["per_layer"]
                   if spec._reports(m, "smollm-135m.rns9.chat")])


def _token_altered(engine):
    step = engine._mixed

    def altered(*args):
        g, cache = step(*args)
        return (g + 1) % VOCAB, cache
    engine._mixed = altered


def _state_unchanged(engine):
    step = engine._mixed

    def unchanged(*args):
        g, _ = step(*args[:-1], jax.tree.map(jnp.copy, args[-1]))
        return g, args[-1]
    engine._mixed = unchanged


def _half_the_batch(engine):
    step = engine._mixed

    def half(params, tok, seg, pos, dec, valid, cache):
        n = valid.shape[0] // 2
        return step(params, tok, seg, pos, dec, valid.at[n:].set(False),
                    cache)
    engine._mixed = half


def _gate(need, devices):
    return {"platform": devices[0].platform, "kind": "TPU v5 lite",
            "count": len(devices)}


@pytest.mark.parametrize("fault,correct", [
    (None, True),
    (_token_altered, False),
    (_state_unchanged, False),
    (_half_the_batch, False),
], ids=["sound", "token_altered", "state_unchanged", "half_the_batch"])
def test_a_run_is_correct_only_when_the_timed_path_is_sound(
        monkeypatch, fault, correct):
    from repro.serve import kv_cache

    cell = _tiny_cell()
    monkeypatch.setattr(spec, "load_cell", lambda name: cell)
    set_tables = kv_cache.set_tables
    r = run.run_cell(cell.name, 2**31 + 11, 2.0, False, gate=_gate,
                     hook=fault)
    assert kv_cache.set_tables is set_tables     # the spans came off
    gap = r["checks"]["max_logit_gap"]
    assert r["correct"] is correct, gap
    assert list(r)[-1] == "checks"
    assert set(r["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert r["attempted"] > 0


def test_the_int4_control_fails_the_smollm_limit_at_published_widths():
    """The reference with its MLP operands on 4-bit grids, read at each
    position of one 256-token sequence (seeded), against the limit."""
    f = json.loads((ROOT / "chipbench" / "configs" / "smollm-135m.rns9.json")
                   .read_text())
    dm = Dims.of(f)
    params = make_weights(dm, 2**31 + 5)
    rng = np.random.default_rng(5)
    tokens = rng.integers(0, dm.vocab, (1, 256)).astype(np.int32)
    targets = np.full_like(tokens, -1)
    targets[0, 128:] = tokens[0, 128:]
    _, ctrl = reference.gaps(params, tokens, targets, dm=dm, bits=4)
    limit = f["check"]["max_logit_gap"]
    assert float(jnp.max(ctrl)) > limit


def test_the_sample_holds_the_longest_and_is_fixed_by_the_seed():
    finished = {i: (np.arange(5), np.arange(n)) for i, n in
                enumerate([3, 50, 7, 9, 11, 2])}
    a = check.sample(finished, 9, 60)
    assert a[0] == 1 and sum(len(finished[r][1]) for r in a) >= 60
    assert a == check.sample(finished, 9, 60)
    tokens, targets = next(check.batches(finished, [2], 16))
    assert tokens[0, :12].tolist() == list(range(5)) + list(range(7))
    assert targets[0, 4:11].tolist() == list(range(7))
    assert (targets[0, :4] == -1).all() and (targets[0, 11:] == -1).all()
