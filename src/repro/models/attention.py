"""Attention: GQA/MQA/MHA + RoPE, MLA (DeepSeek-V2), KV caches.

Three execution modes:
  * ``dense``   — training; einsum scores with causal/padding mask.
  * ``chunked`` — long prefill (inference); online-softmax scan over KV
                  blocks so [Tq, Tk] scores never materialize.
  * ``decode``  — one query token against a cache; supports a
                  sequence-sharded cache via LSE-combinable partials
                  (flash-decoding across chips, see distributed/sharding).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.rns_matmul import rns_multi_dot
from repro.models.layers import init_linear, linear

NEG_INF = -1e30


def _multi_proj(x, ps, rns):
    """Project ``x`` through several weight dicts with ONE shared forward
    conversion on the RNS path (numerically identical to per-projection
    ``linear`` calls — same absmax grid), or plain matmuls otherwise."""
    if rns is None:
        return tuple(linear(p, x) for p in ps)
    ys = rns_multi_dot(
        x.astype(jnp.float32),
        tuple(p["w"].astype(jnp.float32) for p in ps), rns)
    out = []
    for p, y in zip(ps, ys):
        y = y.astype(x.dtype)
        if "b" in p:
            y = y + p["b"].astype(y.dtype)
        out.append(y)
    return tuple(out)


# ---------------------------------------------------------------- rope ----
def rope(x, positions, theta: float = 10000.0):
    """x [B, T, H, D], positions [B, T] -> rotated x (half-split convention)."""
    d2 = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (np.arange(d2, dtype=np.float32) / d2))
    ang = positions[..., None].astype(jnp.float32) * freqs  # [B, T, d2]
    sin, cos = jnp.sin(ang)[:, :, None, :], jnp.cos(ang)[:, :, None, :]
    x1, x2 = x[..., :d2], x[..., d2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------- core maths ----
def _gqa_scores(q, k):
    """q [B,Tq,H,D], k [B,Tk,Hk,D] -> scores [B,Hk,G,Tq,Tk] (G=H/Hk)."""
    B, Tq, H, D = q.shape
    Hk = k.shape[2]
    qg = q.reshape(B, Tq, Hk, H // Hk, D)
    return jnp.einsum("bqhgd,bkhd->bhgqk", qg, k) / np.sqrt(D).astype(np.float32)


def _gqa_out(probs, v):
    """probs [B,Hk,G,Tq,Tk], v [B,Tk,Hk,D] -> [B,Tq,H,D]."""
    B, Hk, G, Tq, _ = probs.shape
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(B, Tq, Hk * G, v.shape[-1])


def dense_attention(q, k, v, *, causal: bool, kv_mask=None, q_offset=0,
                    window=None):
    """Training-mode attention.  kv_mask [B, Tk] optional padding mask.
    ``window``: sliding-window width — query q attends keys in
    [q - window + 1, q] (causal only; masked with exact zeros)."""
    scores = _gqa_scores(q.astype(jnp.float32), k.astype(jnp.float32))
    Tq, Tk = scores.shape[-2], scores.shape[-1]
    if causal:
        qpos = jnp.arange(Tq) + q_offset
        kpos = jnp.arange(Tk)
        mask = qpos[:, None] >= kpos[None, :]
        if window is not None:
            mask &= qpos[:, None] - kpos[None, :] < window
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    if kv_mask is not None:
        scores = jnp.where(kv_mask[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    return _gqa_out(probs, v.astype(jnp.float32)).astype(q.dtype)


def chunked_attention(q, k, v, *, causal: bool, chunk: int = 1024,
                      kv_mask=None, q_offset=0, window=None):
    """Online-softmax scan over KV chunks (inference prefill; no O(T^2) buf).

    ``window``: sliding-window width (causal only).  A fully-masked chunk
    contributes exactly nothing: its ``p = exp(NEG_INF - NEG_INF) = 1``
    garbage is cancelled by ``corr = exp(NEG_INF - m_finite) = 0`` at the
    first chunk with a valid key, the same exact-zero mechanism the
    all-padded leading chunks already rely on.
    """
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    Hk = k.shape[2]
    Dv = v.shape[-1]
    G = H // Hk
    nchunk = -(-Tk // chunk)
    pad = nchunk * chunk - Tk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kv_mask = jnp.pad(
            kv_mask if kv_mask is not None else jnp.ones((B, Tk), bool),
            ((0, 0), (0, pad)),
        )
    kc = k.reshape(B, nchunk, chunk, Hk, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nchunk, chunk, Hk, Dv).transpose(1, 0, 2, 3, 4)
    maskc = (
        kv_mask.reshape(B, nchunk, chunk).transpose(1, 0, 2)
        if kv_mask is not None
        else jnp.ones((nchunk, B, chunk), bool)
    )
    qf = q.astype(jnp.float32).reshape(B, Tq, Hk, G, D)
    qpos = jnp.arange(Tq) + q_offset

    @jax.checkpoint
    def body(carry, inp):
        m, l, acc = carry  # [B,Hk,G,Tq], [B,Hk,G,Tq], [B,Hk,G,Tq,D]
        kb, vb, mb, c = inp
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, kb.astype(jnp.float32))
        s = s / np.sqrt(D).astype(np.float32)
        kpos = c * chunk + jnp.arange(chunk)
        valid = mb[:, None, None, None, :]
        if causal:
            keep = qpos[:, None] >= kpos[None, :]
            if window is not None:
                keep &= qpos[:, None] - kpos[None, :] < window
            valid = valid & keep[None, None, None]
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p, vb.astype(jnp.float32)
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Hk, G, Tq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Hk, G, Tq), jnp.float32)
    a0 = jnp.zeros((B, Hk, G, Tq, Dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (kc, vc, maskc, jnp.arange(nchunk))
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Tq, H, Dv)
    return out.astype(q.dtype)


def flash_attention(q, k, v, *, causal: bool, kv_mask=None,
                    q_chunk: int = 512, kv_chunk: int = 1024, window=None):
    """2-level tiled attention: scan over q tiles, online-softmax over KV
    tiles with a rematerialized inner body — O(T) live memory forward AND
    backward (the inner scores/probs are recomputed in the bwd pass), at
    the standard flash-attention 2x-recompute cost.
    """
    B, Tq, H, D = q.shape
    nq = -(-Tq // q_chunk)
    pad = nq * q_chunk - Tq
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else q
    qc = qp.reshape(B, nq, q_chunk, H, D).transpose(1, 0, 2, 3, 4)

    def qbody(_, inp):
        qi, i = inp
        out = chunked_attention(
            qi, k, v, causal=causal, kv_mask=kv_mask, chunk=kv_chunk,
            q_offset=i * q_chunk, window=window)
        return None, out

    _, outs = jax.lax.scan(qbody, None, (qc, jnp.arange(nq)))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, nq * q_chunk, H, -1)
    return out[:, :Tq]


def decode_attention(q, k_cache, v_cache, lengths, window=None):
    """q [B,Tq,H,D] against cache [B,S,Hk,D]; ``lengths`` [B] valid prefix
    sizes shared by every query, or [B, Tq] per-query valid counts (the
    speculative-verify window: query ``i`` sees ``lengths[b, i]`` keys —
    its own window predecessors included, later/rejected KV excluded).

    ``window``: sliding-window width — a query with ``n`` valid keys (its
    position is ``n - 1``) additionally masks keys below ``n - window``
    with exact zeros, so evicted cache slots (trash-page garbage included)
    contribute exactly nothing.

    Returns (out [B,Tq,H,D], lse [B,Hk,G,Tq]) — the LSE makes partial
    results combinable across a sequence-sharded cache (flash-decoding).
    """
    B, S = k_cache.shape[:2]
    if lengths.ndim == 2:       # per-query valid counts (verify window)
        kpos = jnp.arange(S)[None, None, :]
        mask = kpos < lengths[:, :, None]
        if window is not None:
            mask &= kpos >= lengths[:, :, None] - window
        mask = mask[:, None, None, :, :]
    else:
        kpos = jnp.arange(S)[None, :]
        mask = kpos < lengths[:, None]
        if window is not None:
            mask &= kpos >= lengths[:, None] - window
        mask = mask[:, None, None, None, :]
    s = _gqa_scores(q.astype(jnp.float32), k_cache.astype(jnp.float32))
    s = jnp.where(mask, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bhgqd", p, v_cache.astype(jnp.float32))
    out = out / jnp.maximum(l, 1e-30)[..., None]
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    B, Hk, G, Tq, D = out.shape
    return (
        out.transpose(0, 3, 1, 2, 4).reshape(B, Tq, Hk * G, D).astype(q.dtype),
        lse,
    )


# --------------------------------------------------------- GQA module -----
def init_gqa(key, cfg, dtype=jnp.float32):
    """cfg needs: d_model, n_heads, n_kv_heads, d_head, qkv_bias."""
    ks = jax.random.split(key, 4)
    H, Hk, D = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    p, s = {}, {}
    p["wq"], s["wq"] = init_linear(
        ks[0], cfg.d_model, H * D, axes=("embed", "heads"),
        bias=cfg.qkv_bias, dtype=dtype)
    p["wk"], s["wk"] = init_linear(
        ks[1], cfg.d_model, Hk * D, axes=("embed", "kv_heads"),
        bias=cfg.qkv_bias, dtype=dtype)
    p["wv"], s["wv"] = init_linear(
        ks[2], cfg.d_model, Hk * D, axes=("embed", "kv_heads"),
        bias=cfg.qkv_bias, dtype=dtype)
    p["wo"], s["wo"] = init_linear(
        ks[3], H * D, cfg.d_model, axes=("heads", "embed"), dtype=dtype)
    return p, s


def gqa_qkv(p, x, cfg, positions, rns=None, *, use_rope=True):
    B, T, _ = x.shape
    H, Hk, D = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    q, k, v = _multi_proj(x, (p["wq"], p["wk"], p["wv"]), rns)
    q = q.reshape(B, T, H, D)
    k = k.reshape(B, T, Hk, D)
    v = v.reshape(B, T, Hk, D)
    if use_rope:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_attend(p, x, cfg, *, mode: str, positions=None, kv_mask=None,
               rns=None, use_rope=True, chunk=1024, xkv=None):
    """Self- (or cross-, via xkv) attention for train/prefill.

    Returns (y, (k, v)) so prefill can populate a KV cache.
    """
    B, T, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    if xkv is None:
        q, k, v = gqa_qkv(p, x, cfg, positions, rns, use_rope=use_rope)
        causal = cfg.causal
        if getattr(cfg, "attn_batch_shard", False):
            from repro.distributed.sharding import constrain

            q = constrain(q, ("batch_all", None, None, None))
            k = constrain(k, ("batch_all", None, None, None))
            v = constrain(v, ("batch_all", None, None, None))
    else:  # cross-attention: keys/values from the encoder stream
        Hk, D = cfg.n_kv_heads, cfg.d_head
        q = linear(p["wq"], x, rns).reshape(B, T, cfg.n_heads, D)
        Tk = xkv.shape[1]
        k, v = _multi_proj(xkv, (p["wk"], p["wv"]), rns)
        k = k.reshape(B, Tk, Hk, D)
        v = v.reshape(B, Tk, Hk, D)
        causal = False
    window = cfg.attn_window if causal else None
    if mode == "dense":
        out = dense_attention(q, k, v, causal=causal, kv_mask=kv_mask,
                              window=window)
    elif mode == "chunked":
        out = chunked_attention(q, k, v, causal=causal, kv_mask=kv_mask,
                                chunk=chunk, window=window)
    elif mode == "flash":
        out = flash_attention(q, k, v, causal=causal, kv_mask=kv_mask,
                              kv_chunk=chunk, window=window)
    else:
        raise ValueError(mode)
    return linear(p["wo"], out.reshape(B, T, -1), rns), (k, v)


def gqa_decode(p, x, cfg, cache, *, rns=None, use_rope=True):
    """One-token decode.  cache: {"k","v" [B,S,Hk,D], "lengths" [B]}.

    Returns (y [B,1,d], k_cache, v_cache) with the new token's K/V planes
    scattered in at per-row ``lengths``.
    """
    B = x.shape[0]
    positions = cache["lengths"][:, None]
    q, k, v = gqa_qkv(p, x, cfg, positions, rns, use_rope=use_rope)
    idx = jnp.arange(B)
    k_cache = cache["k"].at[idx, cache["lengths"]].set(
        k[:, 0].astype(cache["k"].dtype))
    v_cache = cache["v"].at[idx, cache["lengths"]].set(
        v[:, 0].astype(cache["v"].dtype))
    out, _lse = decode_attention(q, k_cache, v_cache, cache["lengths"] + 1,
                                 window=cfg.attn_window)
    y = linear(p["wo"], out.reshape(B, 1, -1), rns)
    return y, k_cache, v_cache


def gqa_decode_paged(p, x, cfg, cache, *, rns=None, use_rope=True):
    """One-token decode against a paged KV cache (continuous batching).

    cache: {"k_pages","v_pages" the whole stacked pool [n_periods, P,
    bs*Hk*D], "layer" this layer's period index, "block_table" [R,nb],
    "lengths" [R]}.  The new token's K/V are scattered into the row's
    current page of ``layer`` in place, then the row's pages are
    gathered back into a dense [R, nb*bs, Hk, D] view — numerically
    identical to the dense-cache path (positions past ``lengths`` are
    masked to exact zeros in the softmax, so the page-pool garbage there
    never contributes).

    Returns (y [B,1,d], k_pages, v_pages).
    """
    from repro.serve.kv_cache import gather_pages, write_token

    B = x.shape[0]
    positions = cache["lengths"][:, None]
    q, k, v = gqa_qkv(p, x, cfg, positions, rns, use_rope=use_rope)
    layer, bt = cache["layer"], cache["block_table"]
    k_pages = write_token(cache["k_pages"], layer, bt, cache["lengths"],
                          k[:, 0])
    v_pages = write_token(cache["v_pages"], layer, bt, cache["lengths"],
                          v[:, 0])
    kd = gather_pages(k_pages, layer, bt, k.shape[2:])
    vd = gather_pages(v_pages, layer, bt, v.shape[2:])
    out, _lse = decode_attention(q, kd, vd, cache["lengths"] + 1,
                                 window=cfg.attn_window)
    y = linear(p["wo"], out.reshape(B, 1, -1), rns)
    return y, k_pages, v_pages


def gqa_decode_paged_window(p, x, cfg, cache, *, rns=None, use_rope=True):
    """W-token speculative-verify decode against a paged KV cache.

    x [R, W, d]: the window [last_token, draft_1, ..., draft_{W-1}].  All
    W tokens' K/V are scattered at positions lengths..lengths+W-1 (writes
    past the row's allocated pages are redirected to the trash page — the
    engine caps acceptance to what landed on real pages), then causal
    window attention runs over the gathered dense view.  ``lengths`` is
    NOT advanced here: the engine sets it to length + accepted + 1 after
    the greedy accept/reject.

    Returns (y [R, W, d], k_pages, v_pages).
    """
    from repro.serve.kv_cache import gather_pages, write_token_window

    B, W = x.shape[:2]
    positions = cache["lengths"][:, None] + jnp.arange(W)[None]
    q, k, v = gqa_qkv(p, x, cfg, positions, rns, use_rope=use_rope)
    layer, bt = cache["layer"], cache["block_table"]
    k_pages = write_token_window(cache["k_pages"], layer, bt,
                                 cache["lengths"], k)
    v_pages = write_token_window(cache["v_pages"], layer, bt,
                                 cache["lengths"], v)
    kd = gather_pages(k_pages, layer, bt, k.shape[2:])
    vd = gather_pages(v_pages, layer, bt, v.shape[2:])
    qlen = cache["lengths"][:, None] + 1 + jnp.arange(W)[None]   # [R, W]
    out, _lse = decode_attention(q, kd, vd, qlen, window=cfg.attn_window)
    y = linear(p["wo"], out.reshape(B, W, -1), rns)
    return y, k_pages, v_pages


def gqa_decode_packed(p, x, cfg, cache, seg, pos, *, rns=None, use_rope=True):
    """Packed mixed-phase step: N tokens, each with explicit (segment,
    position) coordinates, against a paged KV cache.

    ``x`` [1, N, d]: token i belongs to row ``seg[i]`` at absolute
    position ``pos[i]`` — any mix of prefill-chunk tokens and decode
    rows, padding-free (pad lanes carry ``seg = -1`` and write to the
    trash page).  All N tokens' K/V are scattered *before* the gather,
    so a chunk token attends both earlier chunks' KV pages and its own
    chunk predecessors; the per-token causal mask is ``pos + 1`` keys.

    Exactness: every token runs :func:`decode_attention` over its row's
    gathered pages, which is bitwise the solo math for both token kinds
    — for decode rows it IS the solo path (``gqa_decode_paged``
    modulo layout), and for chunk tokens it equals the single-chunk
    online softmax of :func:`chunked_attention` (the ``m0 = -inf``
    correction underflows to an exact 0.0 and masked keys contribute
    exact zeros), valid while a row's gathered context fits one KV chunk
    (``max_blocks * page_size <= 1024`` — smoke/serve scales here).

    Returns (y [1, N, d], k_pages, v_pages).
    """
    from repro.serve.kv_cache import gather_pages, write_packed_tokens

    N = x.shape[1]
    with jax.named_scope("qkv"):
        q, k, v = gqa_qkv(p, x, cfg, pos[None], rns, use_rope=use_rope)
    layer, bt = cache["layer"], cache["block_table"]
    with jax.named_scope("kv_write"):
        k_pages = write_packed_tokens(cache["k_pages"], layer, bt, seg, pos,
                                      k[0])
        v_pages = write_packed_tokens(cache["v_pages"], layer, bt, seg, pos,
                                      v[0])
    with jax.named_scope("attn_gather"):
        segc = jnp.clip(seg, 0, bt.shape[0] - 1)
        kd = gather_pages(k_pages, layer, bt, k.shape[2:])[segc]  # [N,S,Hk,D]
        vd = gather_pages(v_pages, layer, bt, v.shape[2:])[segc]
    with jax.named_scope("attn"):
        out, _lse = decode_attention(q[0][:, None], kd, vd, pos + 1,
                                     window=cfg.attn_window)
    with jax.named_scope("attn_out"):
        y = linear(p["wo"], out.reshape(1, N, -1), rns)
    return y, k_pages, v_pages


def cross_decode(p, x, cfg, xkv, *, rns=None):
    """Decode-time cross-attention over a static encoder KV (enc-dec archs).

    xkv: {"k","v" [B,Te,Hk,D], "lengths" [B]} precomputed at prefill through
    this layer's wk/wv.
    """
    B = x.shape[0]
    H, D = cfg.n_heads, cfg.d_head
    q = linear(p["wq"], x, rns).reshape(B, 1, H, D)
    out, _ = decode_attention(q, xkv["k"], xkv["v"], xkv["lengths"])
    return linear(p["wo"], out.reshape(B, 1, -1), rns)


# ----------------------------------------------------------- MLA (DSv2) ---
def init_mla(key, cfg, dtype=jnp.float32):
    """DeepSeek-V2 multi-head latent attention params."""
    m = cfg.mla
    ks = jax.random.split(key, 8)
    H = cfg.n_heads
    p, s = {}, {}
    p["wdq"], s["wdq"] = init_linear(
        ks[0], cfg.d_model, m.q_lora_rank, axes=("embed", "lora"), dtype=dtype)
    # nope/rope up-projections kept as separate weights: a fused [lora,
    # H*(dn+dr)] projection shards on the flat dim and the per-head split
    # then crosses shard boundaries (XLA re-gathers the whole q; see
    # EXPERIMENTS.md §Perf deepseek iter 3)
    p["wuqn"], s["wuqn"] = init_linear(
        ks[1], m.q_lora_rank, H * m.qk_nope_dim, axes=("lora", "heads"),
        dtype=dtype)
    p["wuqr"], s["wuqr"] = init_linear(
        jax.random.fold_in(ks[1], 1), m.q_lora_rank, H * m.qk_rope_dim,
        axes=("lora", "heads"), dtype=dtype)
    p["wdkv"], s["wdkv"] = init_linear(
        ks[2], cfg.d_model, m.kv_lora_rank, axes=("embed", "lora"), dtype=dtype)
    p["wkr"], s["wkr"] = init_linear(
        ks[3], cfg.d_model, m.qk_rope_dim, axes=("embed", "lora"), dtype=dtype)
    p["wuk"], s["wuk"] = init_linear(
        ks[4], m.kv_lora_rank, H * m.qk_nope_dim, axes=("lora", "heads"), dtype=dtype)
    p["wuv"], s["wuv"] = init_linear(
        ks[5], m.kv_lora_rank, H * m.v_dim, axes=("lora", "heads"), dtype=dtype)
    p["wo"], s["wo"] = init_linear(
        ks[6], H * m.v_dim, cfg.d_model, axes=("heads", "embed"), dtype=dtype)
    from repro.models.layers import init_rmsnorm

    p["q_norm"], s["q_norm"] = init_rmsnorm(m.q_lora_rank, dtype)
    p["kv_norm"], s["kv_norm"] = init_rmsnorm(m.kv_lora_rank, dtype)
    return p, s


def mla_qkv(p, x, cfg, positions, rns=None):
    """Returns q, k, v expanded per head + the compressed (c_kv, k_rope) pair."""
    from repro.distributed.sharding import constrain
    from repro.models.layers import rmsnorm

    m = cfg.mla
    B, T, _ = x.shape
    H = cfg.n_heads
    # the down-projection pair (wdkv, wkr) and the up-projection pair
    # (wuqn, wuqr) each share one forward conversion on the RNS path
    dq, dkv, kr = _multi_proj(x, (p["wdq"], p["wdkv"], p["wkr"]), rns)
    cq = rmsnorm(p["q_norm"], dq)
    q_nope, q_rope = _multi_proj(cq, (p["wuqn"], p["wuqr"]), rns)
    q_nope = q_nope.reshape(B, T, H, m.qk_nope_dim)
    q_rope = q_rope.reshape(B, T, H, m.qk_rope_dim)
    q_nope = constrain(q_nope, ("batch", None, "model", None))
    q_rope = rope(q_rope, positions, cfg.rope_theta)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)

    c_kv = rmsnorm(p["kv_norm"], dkv)                              # [B,T,r]
    k_rope = rope(
        kr[:, :, None, :], positions, cfg.rope_theta
    )                                                              # [B,T,1,dr]
    k_nope, v = _multi_proj(c_kv, (p["wuk"], p["wuv"]), rns)
    k_nope = k_nope.reshape(B, T, H, m.qk_nope_dim)
    k_nope = constrain(k_nope, ("batch", None, "model", None))
    v = v.reshape(B, T, H, m.v_dim)
    v = constrain(v, ("batch", None, "model", None))
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (B, T, H, m.qk_rope_dim))], axis=-1
    )
    return q, k, v, (c_kv, k_rope[:, :, 0, :])


def mla_attend(p, x, cfg, *, mode: str, positions=None, kv_mask=None,
               rns=None, chunk=1024):
    """Train/prefill MLA.  Returns (y, (c_kv, k_rope)) for the latent cache."""
    B, T, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(T)[None], (B, T))
    q, k, v, latent = mla_qkv(p, x, cfg, positions, rns)
    window = cfg.attn_window if cfg.causal else None
    if mode == "dense":
        out = dense_attention(q, k, v, causal=cfg.causal, kv_mask=kv_mask,
                              window=window)
    elif mode == "chunked":
        out = chunked_attention(q, k, v, causal=cfg.causal, kv_mask=kv_mask,
                                chunk=chunk, window=window)
    elif mode == "flash":
        out = flash_attention(q, k, v, causal=cfg.causal, kv_mask=kv_mask,
                              kv_chunk=chunk, window=window)
    else:
        raise ValueError(mode)
    return linear(p["wo"], out.reshape(B, T, -1), rns), latent


def _mla_proj_at(p, x, cfg, positions, rns):
    """Decode-time MLA projections at explicit absolute ``positions`` [B,T].

    Returns (q_nope [B,T,H,dn], q_rope [B,T,H,dr] roped, c_kv_t [B,T,r],
    k_rope_t [B,T,dr] roped) — everything the cache write + absorbed
    attention need, for either cache layout.  Per token this is the same
    math as :func:`mla_qkv` up to (and excluding) the k/v expansion, so
    the latents written to the cache are bitwise those a whole-prompt
    prefill would produce.
    """
    from repro.models.layers import rmsnorm

    m = cfg.mla
    B, T = x.shape[:2]
    H = cfg.n_heads
    dq, dkv, kr = _multi_proj(x, (p["wdq"], p["wdkv"], p["wkr"]), rns)
    cq = rmsnorm(p["q_norm"], dq)
    q_nope, q_rope = _multi_proj(cq, (p["wuqn"], p["wuqr"]), rns)
    q_nope = q_nope.reshape(B, T, H, m.qk_nope_dim)
    q_rope = q_rope.reshape(B, T, H, m.qk_rope_dim)
    q_rope = rope(q_rope, positions, cfg.rope_theta)
    c_kv_t = rmsnorm(p["kv_norm"], dkv)                             # [B,T,r]
    k_rope_t = rope(
        kr[:, :, None, :], positions, cfg.rope_theta
    )[:, :, 0, :]                                                    # [B,T,dr]
    return q_nope, q_rope, c_kv_t, k_rope_t


def _mla_decode_proj(p, x, cfg, lengths, rns):
    """MLA projections for T=1 decode or a T=W verify window: token ``i``
    sits at absolute position ``lengths + i`` (see :func:`_mla_proj_at`)."""
    T = x.shape[1]
    positions = lengths[:, None] + jnp.arange(T)[None]
    return _mla_proj_at(p, x, cfg, positions, rns)


def _mla_absorbed_ctx(p, cfg, q_nope, q_rope, c_kv, k_rope, lengths,
                      window=None):
    """Absorbed-matrix latent attention core (everything before ``wo``).

    W_uk is absorbed into the query and W_uv into the output so attention
    runs directly in the latent space (MQA-shaped, Hk=1).  ``lengths``:
    [B] valid key counts shared by every query (one-token decode), or
    [B, T] per-query counts (speculative-verify window, query ``i`` sees
    ``lengths[b, i]`` keys).  ``window``: sliding-window width — keys
    below ``lengths - window`` are masked with exact zeros (see
    :func:`decode_attention`).  Returns (out [B,T,H,v_dim] float32,
    lse [B,1,H,T]) — the packed mixed step selects between this and the
    expanded (prefill-math) context per token before the shared ``wo``.
    """
    m = cfg.mla
    H = cfg.n_heads
    wuk = p["wuk"]["w"].reshape(m.kv_lora_rank, H, m.qk_nope_dim)
    q_abs = jnp.einsum("bthd,rhd->bthr", q_nope.astype(jnp.float32),
                       wuk.astype(jnp.float32))
    scale = 1.0 / np.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    s = (
        jnp.einsum("bthr,bsr->bhts", q_abs, c_kv.astype(jnp.float32))
        + jnp.einsum("bthd,bsd->bhts", q_rope.astype(jnp.float32),
                     k_rope.astype(jnp.float32))
    ) * scale                                                        # [B,H,T,S]
    S = c_kv.shape[1]
    if lengths.ndim == 2:       # per-query valid counts (verify window)
        kpos = jnp.arange(S)[None, None, :]
        mask = kpos < lengths[:, :, None]
        if window is not None:
            mask &= kpos >= lengths[:, :, None] - window
        mask = mask[:, None, :, :]
    else:
        kpos = jnp.arange(S)[None, :]
        mask = kpos < lengths[:, None]
        if window is not None:
            mask &= kpos >= lengths[:, None] - window
        mask = mask[:, None, None, :]
    s = jnp.where(mask, s, NEG_INF)
    mx = jnp.max(s, axis=-1)
    pr = jnp.exp(s - mx[..., None])
    l = jnp.sum(pr, axis=-1)
    ctx = jnp.einsum("bhts,bsr->bthr", pr / jnp.maximum(l, 1e-30)[..., None],
                     c_kv.astype(jnp.float32))                       # [B,T,H,r]
    wuv = p["wuv"]["w"].reshape(m.kv_lora_rank, H, m.v_dim)
    out = jnp.einsum("bthr,rhd->bthd", ctx, wuv.astype(jnp.float32))
    lse = (mx + jnp.log(jnp.maximum(l, 1e-30)))[:, None, :, :]  # [B,1,H,T]
    return out, lse


def _mla_absorbed_attend(p, x, cfg, q_nope, q_rope, c_kv, k_rope, lengths,
                         rns):
    """:func:`_mla_absorbed_ctx` + the output projection.  Returns
    (y [B,T,d], lse [B,1,H,T])."""
    B = x.shape[0]
    out, lse = _mla_absorbed_ctx(p, cfg, q_nope, q_rope, c_kv, k_rope,
                                 lengths, window=cfg.attn_window)
    T = out.shape[1]
    y = linear(p["wo"], out.reshape(B, T, -1).astype(x.dtype), rns)
    return y, lse


def mla_decode(p, x, cfg, cache, *, rns=None):
    """Absorbed-matrix MLA decode (DeepSeek-V2's deployment form).

    cache: {"c_kv" [B,S,r], "k_rope" [B,S,dr], "lengths" [B]} — the latent
    cache is (r + dr) per token instead of 2*H*D: the paper's compression.

    Returns (y [B,1,d], c_kv_cache, k_rope_cache, lse) — lse has shape
    [B,1(Hk),H(G),1] for sequence-sharded combination.
    """
    B = x.shape[0]
    q_nope, q_rope, c_kv_t, k_rope_t = _mla_decode_proj(
        p, x, cfg, cache["lengths"], rns)
    idx = jnp.arange(B)
    c_kv = cache["c_kv"].at[idx, cache["lengths"]].set(
        c_kv_t[:, 0].astype(cache["c_kv"].dtype))
    k_rope = cache["k_rope"].at[idx, cache["lengths"]].set(
        k_rope_t[:, 0].astype(cache["k_rope"].dtype))
    y, lse = _mla_absorbed_attend(
        p, x, cfg, q_nope, q_rope, c_kv, k_rope, cache["lengths"] + 1, rns)
    return y, c_kv, k_rope, lse


def mla_decode_paged(p, x, cfg, cache, *, rns=None):
    """MLA decode against a paged latent cache (continuous batching).

    cache: {"ckv_pages" [n_periods,P,bs*r], "krope_pages" [n_periods,P,
    bs*dr] (the whole stacked pools), "layer", "block_table" [R,nb],
    "lengths" [R]}, as for :func:`gqa_decode_paged`.  Returns (y,
    ckv_pages, krope_pages).
    """
    from repro.serve.kv_cache import gather_pages, write_token

    q_nope, q_rope, c_kv_t, k_rope_t = _mla_decode_proj(
        p, x, cfg, cache["lengths"], rns)
    layer, bt = cache["layer"], cache["block_table"]
    ckv_pages = write_token(cache["ckv_pages"], layer, bt, cache["lengths"],
                            c_kv_t[:, 0])
    krope_pages = write_token(cache["krope_pages"], layer, bt,
                              cache["lengths"], k_rope_t[:, 0])
    c_kv = gather_pages(ckv_pages, layer, bt, c_kv_t.shape[2:])
    k_rope = gather_pages(krope_pages, layer, bt, k_rope_t.shape[2:])
    y, _lse = _mla_absorbed_attend(
        p, x, cfg, q_nope, q_rope, c_kv, k_rope, cache["lengths"] + 1, rns)
    return y, ckv_pages, krope_pages


def mla_decode_paged_window(p, x, cfg, cache, *, rns=None):
    """W-token speculative-verify MLA decode against a paged latent cache.

    x [R, W, d]; all W window tokens' latents are scattered at positions
    lengths..lengths+W-1, then the absorbed attention runs with per-query
    causal masks (query ``i`` sees ``lengths + i + 1`` latents — its own
    window predecessors included, later/rejected positions excluded).
    ``lengths`` is advanced by the engine after accept/reject, not here.

    Returns (y [R, W, d], ckv_pages, krope_pages).
    """
    from repro.serve.kv_cache import gather_pages, write_token_window

    W = x.shape[1]
    q_nope, q_rope, c_kv_t, k_rope_t = _mla_decode_proj(
        p, x, cfg, cache["lengths"], rns)
    layer, bt = cache["layer"], cache["block_table"]
    ckv_pages = write_token_window(cache["ckv_pages"], layer, bt,
                                   cache["lengths"], c_kv_t)
    krope_pages = write_token_window(cache["krope_pages"], layer, bt,
                                     cache["lengths"], k_rope_t)
    c_kv = gather_pages(ckv_pages, layer, bt, c_kv_t.shape[2:])
    k_rope = gather_pages(krope_pages, layer, bt, k_rope_t.shape[2:])
    qlen = cache["lengths"][:, None] + 1 + jnp.arange(W)[None]   # [R, W]
    y, _lse = _mla_absorbed_attend(
        p, x, cfg, q_nope, q_rope, c_kv, k_rope, qlen, rns)
    return y, ckv_pages, krope_pages


def mla_decode_packed(p, x, cfg, cache, seg, pos, dec, *, rns=None):
    """Packed mixed-phase MLA step against a paged latent cache.

    Same packed layout as :func:`gqa_decode_packed` (``x`` [1, N, d],
    per-token ``seg``/``pos``), plus a per-token kind mask ``dec`` [N]
    bool.  MLA's two deployment forms are NOT bitwise interchangeable —
    solo prefill runs *expanded* attention (latents up-projected through
    ``wuk``/``wuv``, one dot over dn+dr) while solo decode runs
    *absorbed* attention (two latent-space einsums summed) — so the
    packed step computes BOTH contexts over the gathered latents and
    selects per token: absorbed where ``dec`` (decode rows), expanded
    where not (prefill-chunk tokens).  Re-expanding the *gathered*
    latents is exact because the latent cache is float32 and the
    expansion matmul treats every (token, position) row independently.

    The expansion's ``rns`` grid cannot be reproduced for gathered
    latents (the solo per-token grid info is gone), so the engine
    rejects chunked MLA with ``rns_targets="all"``; with attention off
    the RNS path (``rns is None`` here) both kinds are bitwise solo.

    Returns (y [1, N, d], ckv_pages, krope_pages).
    """
    from repro.serve.kv_cache import gather_pages, write_packed_tokens

    m = cfg.mla
    N = x.shape[1]
    H = cfg.n_heads
    with jax.named_scope("qkv"):
        q_nope, q_rope, c_kv_t, k_rope_t = _mla_proj_at(p, x, cfg, pos[None],
                                                        rns)
    layer, bt = cache["layer"], cache["block_table"]
    with jax.named_scope("kv_write"):
        ckv_pages = write_packed_tokens(cache["ckv_pages"], layer, bt,
                                        seg, pos, c_kv_t[0])
        krope_pages = write_packed_tokens(cache["krope_pages"], layer, bt,
                                          seg, pos, k_rope_t[0])
    with jax.named_scope("attn_gather"):
        segc = jnp.clip(seg, 0, bt.shape[0] - 1)
        c_kv = gather_pages(ckv_pages, layer, bt,
                            c_kv_t.shape[2:])[segc]                 # [N,S,r]
        k_rope = gather_pages(krope_pages, layer, bt,
                              k_rope_t.shape[2:])[segc]             # [N,S,dr]
    with jax.named_scope("attn"):
        qn = q_nope[0][:, None]                                 # [N,1,H,dn]
        qr = q_rope[0][:, None]
        # absorbed context: bitwise the solo decode math per row
        abs_out, _ = _mla_absorbed_ctx(p, cfg, qn, qr, c_kv, k_rope, pos + 1,
                                       window=cfg.attn_window)
        # expanded context: bitwise the solo prefill math per chunk token
        S = c_kv.shape[1]
        k_nope, v = _multi_proj(c_kv, (p["wuk"], p["wuv"]), rns)
        k_nope = k_nope.reshape(N, S, H, m.qk_nope_dim)
        v = v.reshape(N, S, H, m.v_dim)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (N, S, H, m.qk_rope_dim))], axis=-1)
        q = jnp.concatenate([qn, qr], axis=-1)
        exp_out, _lse = decode_attention(q, k, v, pos + 1,
                                         window=cfg.attn_window)  # [N,1,H,vd]
        out = jnp.where(dec[:, None, None, None], abs_out,
                        exp_out.astype(jnp.float32))
    with jax.named_scope("attn_out"):
        y = linear(p["wo"], out.reshape(1, N, -1).astype(x.dtype), rns)
    return y, ckv_pages, krope_pages
