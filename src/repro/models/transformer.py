"""Transformer stacks: decoder-only, encoder-decoder, hybrid (SSM/MoE).

Layers are grouped into the smallest periodic pattern (cfg.period) and
scanned over periods — params are stacked pytrees with a leading
``n_periods`` dim, which keeps HLO size and compile time bounded for
64-layer archs, and gives FSDP a natural per-iteration all-gather point.

Modes:
  train   -> dense attention, full remat per period (policy: save nothing)
  prefill -> chunked (online-softmax) attention, returns a decode cache
  decode  -> one token through per-layer caches (attn KV / MLA latent /
             mamba state / rwkv state)

``cfg.attn_window`` tightens every causal mode to a sliding window
(query q attends keys [q - window + 1, q], exact-zero masking outside)
without touching this file's control flow — the gqa/mla wrappers in
models/attention.py read it and thread ``window=`` through all three
attention modes and every decode/paged/packed variant, so train,
prefill, decode and the serving engines all see the same receptive
field (docs/serving.md).

RNS execution: ``cfg.rns`` selects the digit-sliced datapath per target
(attn/mlp/all).  Inside a block the projections share forward conversions
(models/attention.py) and, with ``cfg.rns.defer``, the MLP's
wi -> gate -> wo chain runs residues-in/residues-out with one MRC
normalization on the main path (models/layers.py) — blocks exchange
floats only at the residual stream.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (
    embed,
    init_embedding,
    init_linear,
    init_mlp,
    init_norm,
    linear,
    mlp,
    norm,
    sinusoidal_positions,
)


def _rns_for(cfg, target: str):
    if cfg.rns is None:
        return None
    if cfg.rns_targets == "all" or cfg.rns_targets == target:
        return cfg.rns
    return None


# ------------------------------------------------------------ layer init ---
def _init_layer(key, cfg, layer_type: str, mlp_type: str, dtype):
    ks = jax.random.split(key, 4)
    p, s = {}, {}
    p["ln1"], s["ln1"] = init_norm(cfg.d_model, cfg.norm, dtype)
    if layer_type == "attn":
        p["attn"], s["attn"] = attn.init_gqa(ks[0], cfg, dtype)
    elif layer_type == "mla":
        p["attn"], s["attn"] = attn.init_mla(ks[0], cfg, dtype)
    elif layer_type == "mamba":
        p["mamba"], s["mamba"] = ssm_lib.init_mamba(ks[0], cfg.d_model, cfg.ssm, dtype)
    elif layer_type == "rwkv":
        p["rwkv"], s["rwkv"] = ssm_lib.init_rwkv6(
            ks[0], cfg.d_model, cfg.ssm, cfg.d_ff, dtype)
    else:
        raise ValueError(layer_type)
    if cfg.enc_dec and layer_type == "attn" and mlp_type != "__enc__":
        p["lnx"], s["lnx"] = init_norm(cfg.d_model, cfg.norm, dtype)
        p["xattn"], s["xattn"] = attn.init_gqa(ks[2], cfg, dtype)
    if mlp_type in ("dense", "__enc__"):
        p["ln2"], s["ln2"] = init_norm(cfg.d_model, cfg.norm, dtype)
        rns_mlp = _rns_for(cfg, "mlp") is not None
        p["mlp"], s["mlp"] = init_mlp(
            ks[1], cfg.d_model, cfg.d_ff, gated=cfg.gated_mlp, act=cfg.act,
            dtype=dtype,
            down_axes=((None, "mlp") if rns_mlp else ("mlp", "embed")))
    elif mlp_type == "moe":
        p["ln2"], s["ln2"] = init_norm(cfg.d_model, cfg.norm, dtype)
        p["moe"], s["moe"] = moe_lib.init_moe(
            ks[1], cfg.d_model, cfg.moe, act=cfg.act, dtype=dtype)
    # rwkv channel-mix lives inside the rwkv param dict; "none" adds nothing
    return p, s


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def init_blocks(key, cfg, *, enc: bool = False):
    """Init (stacked params, specs-with-'layers'-axis-prepended)."""
    dtype = jnp.dtype(cfg.param_dtype)
    L = cfg.n_enc_layers if enc else cfg.n_layers
    ltypes = ("attn",) * L if enc else cfg.layer_types
    mtypes = ("__enc__",) * L if enc else cfg.mlp_types
    p = cfg.period if not enc else 1
    n_periods = L // p
    periods, specs = [], None
    for per in range(n_periods):
        pp = {}
        for j in range(p):
            li = per * p + j
            lp, ls = _init_layer(
                jax.random.fold_in(key, li), cfg, ltypes[li], mtypes[li], dtype)
            pp[f"l{j}"] = lp
            if per == 0:
                specs = specs or {}
                specs[f"l{j}"] = ls
        periods.append(pp)
    stacked = _stack(periods)
    specs = jax.tree.map(
        lambda ax: ("layers",) + tuple(ax), specs,
        is_leaf=lambda x: isinstance(x, tuple))
    return stacked, specs


# ---------------------------------------------------------- layer apply ----
def _apply_layer(lp, h, cfg, layer_type, mlp_type, *, mode, positions,
                 kv_mask, enc_out, cache, chunk=1024, packed=None):
    """Returns (h, new_cache_entry, prefill_kv, aux).

    ``packed`` (decode mode, paged caches only): a ``(seg, pos, dec)``
    triple of [N] arrays giving every token of the [1, N, d] stream its
    own row, absolute position, and phase (decode vs prefill chunk) —
    the mixed chunked-prefill/decode step's layout.
    """
    rns_a = _rns_for(cfg, "attn")
    rns_m = _rns_for(cfg, "mlp")
    aux = jnp.zeros((), jnp.float32)
    new_cache, prefill_kv = None, None
    use_rope = cfg.pos_emb == "rope"

    if layer_type in ("attn", "mla"):
        with jax.named_scope("norm"):
            hn = norm(lp["ln1"], h, cfg.norm)
        if mode == "decode":
            # paged caches (continuous batching) are recognized by their
            # page-pool keys; the dense layout stays the default.  A
            # multi-token query ([R, W] speculative-verify window) only
            # exists on the paged path.
            window = hn.shape[1] > 1
            if packed is not None:
                if "k_pages" not in cache and "ckv_pages" not in cache:
                    raise NotImplementedError(
                        "packed mixed steps need the paged cache layout")
                seg, pos, dec = packed
                if layer_type == "attn":
                    y, kp, vp = attn.gqa_decode_packed(
                        lp["attn"], hn, cfg, cache, seg, pos, rns=rns_a,
                        use_rope=use_rope)
                    new_cache = dict(cache, k_pages=kp, v_pages=vp)
                else:
                    y, cp, kp = attn.mla_decode_packed(
                        lp["attn"], hn, cfg, cache, seg, pos, dec, rns=rns_a)
                    new_cache = dict(cache, ckv_pages=cp, krope_pages=kp)
            elif window and "k_pages" not in cache and "ckv_pages" not in cache:
                raise NotImplementedError(
                    "multi-token decode windows (speculative verify) need "
                    "the paged cache layout")
            elif layer_type == "attn":
                if "k_pages" in cache:
                    fn = (attn.gqa_decode_paged_window if window
                          else attn.gqa_decode_paged)
                    y, kp, vp = fn(lp["attn"], hn, cfg, cache, rns=rns_a,
                                   use_rope=use_rope)
                    new_cache = dict(cache, k_pages=kp, v_pages=vp)
                else:
                    y, kc, vc = attn.gqa_decode(
                        lp["attn"], hn, cfg, cache, rns=rns_a,
                        use_rope=use_rope)
                    new_cache = dict(cache, k=kc, v=vc)
            else:
                if "ckv_pages" in cache:
                    fn = (attn.mla_decode_paged_window if window
                          else attn.mla_decode_paged)
                    y, cp, kp = fn(lp["attn"], hn, cfg, cache, rns=rns_a)
                    new_cache = dict(cache, ckv_pages=cp, krope_pages=kp)
                else:
                    y, ckv, krope, _lse = attn.mla_decode(
                        lp["attn"], hn, cfg, cache, rns=rns_a)
                    new_cache = dict(cache, c_kv=ckv, k_rope=krope)
        else:
            T = hn.shape[1]
            if mode == "train":
                amode = "dense" if T <= cfg.attn_dense_max else "flash"
            else:
                amode = "chunked" if T <= cfg.attn_dense_max else "flash"
            if layer_type == "attn":
                y, kv = attn.gqa_attend(
                    lp["attn"], hn, cfg, mode=amode, positions=positions,
                    kv_mask=kv_mask, rns=rns_a, use_rope=use_rope, chunk=chunk)
            else:
                y, kv = attn.mla_attend(
                    lp["attn"], hn, cfg, mode=amode, positions=positions,
                    kv_mask=kv_mask, rns=rns_a, chunk=chunk)
            prefill_kv = kv
        with jax.named_scope("attn_out"):
            h = h + y
        if "xattn" in lp:  # enc-dec decoder cross-attention
            hx = norm(lp["lnx"], h, cfg.norm)
            if mode == "decode":
                y = attn.cross_decode(lp["xattn"], hx, cfg, cache["cross"],
                                      rns=rns_a)
            else:
                y, xkv = attn.gqa_attend(
                    lp["xattn"], hx, cfg, mode="dense", xkv=enc_out, rns=rns_a)
                prefill_kv = (prefill_kv, xkv)
            h = h + y
    elif layer_type == "mamba":
        hn = norm(lp["ln1"], h, cfg.norm)
        state = (cache["h"], cache["conv"]) if mode == "decode" else None
        y, new_state = ssm_lib.mamba_seq(
            lp["mamba"], hn, cfg.ssm, rns=rns_m,
            h0=None if state is None else state[0],
            conv0=None if state is None else state[1])
        if mode == "decode":
            new_cache = dict(cache, h=new_state[0], conv=new_state[1])
        else:
            prefill_kv = new_state
        h = h + y
    elif layer_type == "rwkv":
        hn = norm(lp["ln1"], h, cfg.norm)
        state = (cache["S"], cache["x_tm"]) if mode == "decode" else None
        y, new_state = ssm_lib.rwkv6_timemix(
            lp["rwkv"], hn, cfg.ssm, rns=rns_m, state=state)
        if mode == "decode":
            new_cache = dict(cache, S=new_state[0], x_tm=new_state[1])
        else:
            prefill_kv = new_state
        h = h + y

    if mlp_type in ("dense", "__enc__"):
        with jax.named_scope("norm"):
            hn = norm(lp["ln2"], h, cfg.norm)
        with jax.named_scope("mlp"):
            h = h + mlp(lp["mlp"], hn, gated=cfg.gated_mlp, act=cfg.act,
                        rns=rns_m)
    elif mlp_type == "moe":
        hn = norm(lp["ln2"], h, cfg.norm)
        y, aux = moe_lib.moe_ffn(lp["moe"], hn, cfg.moe, act=cfg.act, rns=rns_m)
        h = h + y
    elif layer_type == "rwkv":  # channel-mix (uses rwkv params)
        cm_state = cache["x_cm"] if mode == "decode" else None
        hn = norm(lp["rwkv"]["ln_cm"], h, cfg.norm) if "ln_cm" in lp["rwkv"] else h
        y, x_cm = ssm_lib.rwkv6_channelmix(lp["rwkv"], hn, rns=rns_m,
                                           state=cm_state)
        if mode == "decode":
            new_cache = dict(new_cache, x_cm=x_cm)
        else:
            prefill_kv = (prefill_kv, x_cm)
        h = h + y
    return h, new_cache, prefill_kv, aux


# ------------------------------------------------------------- the stack ---
def _is_pool(name: str) -> bool:
    return name.endswith("_pages")


def apply_blocks(blocks, h, cfg, *, mode, positions=None, kv_mask=None,
                 enc_out=None, cache=None, enc: bool = False, chunk=1024,
                 packed=None):
    """Scan the stacked periods.  Returns (h, new_cache_or_prefill, aux).

    ``packed``: optional ``(seg, pos, dec)`` per-token coordinates for
    the mixed chunked-prefill/decode step (decode mode, paged caches).

    A paged cache's page pools (its ``*_pages`` arrays) ride in the
    scan's carry, whole, and the period index is scanned beside the
    per-layer tables: each layer writes its new tokens and gathers its
    rows' pages at ``(layer, page)`` of the carried pool in place
    (serve/kv_cache.py).  Scanning the pools as ``xs``/``ys`` would
    slice each layer's pool out, relayout it and stack a fresh copy of
    the whole pool every step.  Dense caches, SSM states and prefill's
    new KV keep the plain ``xs``/``ys`` scan.
    """
    L = cfg.n_enc_layers if enc else cfg.n_layers
    ltypes = ("attn",) * L if enc else cfg.layer_types
    mtypes = ("__enc__",) * L if enc else cfg.mlp_types
    p = cfg.period if not enc else 1
    enc_dec_dec = cfg.enc_dec and not enc

    pools = {}
    if mode == "decode" and cache is not None:
        pools = {lj: {k: v for k, v in c.items() if _is_pool(k)}
                 for lj, c in cache.items()}
        pools = {lj: pl for lj, pl in pools.items() if pl}
        cache = {lj: {k: v for k, v in c.items() if not _is_pool(k)}
                 for lj, c in cache.items()}

    def period_body(carry, xs):
        h, aux, pools = carry
        from repro.distributed.sharding import constrain

        h = constrain(h, ("batch", None, None))
        bp = xs["params"]
        cslice = xs.get("cache")
        pools = dict(pools)
        new_cs, pkvs = {}, {}
        for j in range(p):
            lt, mt = ltypes[j], mtypes[j]
            lj = f"l{j}"
            c_j = cslice[lj] if cslice is not None else None
            if lj in pools:
                c_j = dict(c_j, **pools[lj], layer=xs["layer"])
            h, nc, pkv, a = _apply_layer(
                bp[lj], h, cfg, lt, mt, mode=mode, positions=positions,
                kv_mask=kv_mask, enc_out=enc_out, cache=c_j, chunk=chunk,
                packed=packed)
            aux = aux + a
            if nc is not None:
                if lj in pools:
                    pools[lj] = {k: nc[k] for k in pools[lj]}
                    nc = {k: v for k, v in nc.items()
                          if k != "layer" and not _is_pool(k)}
                new_cs[lj] = nc
            if pkv is not None:
                pkvs[lj] = pkv
        out = new_cs if mode == "decode" else pkvs
        return (h, aux, pools), out

    if cfg.remat == "full" and mode == "train":
        period_body = jax.checkpoint(period_body)

    xs = {"params": blocks}
    if cache is not None:
        xs["cache"] = cache
    if pools:
        xs["layer"] = jnp.arange(L // p, dtype=jnp.int32)
    (h, aux, pools), ys = jax.lax.scan(
        period_body, (h, jnp.zeros((), jnp.float32), pools), xs)
    for lj, pl in pools.items():
        ys[lj] = dict(ys[lj], **pl)
    return h, ys, aux
