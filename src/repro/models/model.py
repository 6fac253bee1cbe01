"""Public model API: init / train forward / loss / prefill / decode.

All ten assigned architectures flow through these five functions; the
config's layer program decides what happens inside.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as tf
from repro.models.layers import (
    embed,
    init_embedding,
    init_linear,
    init_norm,
    linear,
    norm,
    sinusoidal_positions,
    unembed,
)


# ---------------------------------------------------------------- init -----
def init_model(key, cfg):
    """Returns (params, specs) — specs mirror params with logical axes."""
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 6)
    p, s = {}, {}
    p["embed"], s["embed"] = init_embedding(ks[0], cfg.vocab, cfg.d_model, dtype)
    p["blocks"], s["blocks"] = tf.init_blocks(ks[1], cfg)
    p["final_norm"], s["final_norm"] = init_norm(cfg.d_model, cfg.norm, dtype)
    if not cfg.tie_embeddings:
        p["lm_head"], s["lm_head"] = init_linear(
            ks[2], cfg.d_model, cfg.vocab, axes=("embed", "vocab"), dtype=dtype)
    if cfg.enc_dec:
        p["enc_blocks"], s["enc_blocks"] = tf.init_blocks(ks[3], cfg, enc=True)
        p["enc_norm"], s["enc_norm"] = init_norm(cfg.d_model, cfg.norm, dtype)
    return p, s


# ------------------------------------------------------------- helpers -----
def _embed_tokens(params, cfg, tokens):
    from repro.distributed.sharding import constrain

    h = embed(params["embed"], tokens)
    if cfg.emb_scale:
        h = h * jnp.asarray(np.sqrt(cfg.d_model), h.dtype)
    return constrain(h, ("batch", None, None))


def _add_abs_pos(cfg, h, offset=0):
    if cfg.pos_emb == "sinusoidal":
        pos = sinusoidal_positions(h.shape[1] + offset, cfg.d_model, h.dtype)
        h = h + pos[offset : offset + h.shape[1]][None]
    return h


def _logits(params, cfg, h):
    from repro.distributed.sharding import constrain

    h = constrain(h, ("batch", None, None))
    h = norm(params["final_norm"], h, cfg.norm)
    out = (unembed(params["embed"], h) if cfg.tie_embeddings
           else linear(params["lm_head"], h))
    return constrain(out, ("batch", None, "model"))


def _encode(params, cfg, frames):
    """Encoder pass (enc-dec archs); frames [B, Te, d] from the stub."""
    h = _add_abs_pos(cfg, frames)
    h, _, _ = tf.apply_blocks(params["enc_blocks"], h, cfg, mode="train",
                              enc=True)
    return norm(params["enc_norm"], h, cfg.norm)


# ------------------------------------------------------------- forward -----
def forward_train(params, cfg, batch):
    """batch: tokens [B,T] (+ 'frontend' [B,F,d] for audio/vlm stubs).

    Returns (logits [B, T(+F), V], aux_loss scalar).
    """
    tokens = batch["tokens"]
    h = _embed_tokens(params, cfg, tokens)
    enc_out = None
    if cfg.enc_dec:
        enc_out = _encode(params, cfg, batch["frontend"])
    elif cfg.frontend is not None and "frontend" in batch:
        h = jnp.concatenate([batch["frontend"].astype(h.dtype), h], axis=1)
    h = _add_abs_pos(cfg, h)
    h, _, aux = tf.apply_blocks(params["blocks"], h, cfg, mode="train",
                                enc_out=enc_out)
    return _logits(params, cfg, h), aux


def loss_fn(params, cfg, batch):
    """Next-token CE (+ MoE aux).  Frontend positions are excluded."""
    logits, aux = forward_train(params, cfg, batch)
    F = 0
    if cfg.frontend is not None and not cfg.enc_dec and "frontend" in batch:
        F = batch["frontend"].shape[1]
    tokens = batch["tokens"]
    lg = logits[:, F:-1].astype(jnp.float32)
    tg = tokens[:, 1:]
    mask = batch.get("loss_mask")
    mask = mask[:, 1:].astype(jnp.float32) if mask is not None else jnp.ones(
        tg.shape, jnp.float32)
    logz = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, tg[..., None], axis=-1)[..., 0]
    ce = jnp.sum((logz - gold) * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return ce + aux, {"ce": ce, "aux": aux}


# -------------------------------------------------------------- caches -----
def _layer_cache_shape(cfg, lt, B, S, dtype):
    if lt == "attn":
        kv = (B, S, cfg.n_kv_heads, cfg.d_head)
        c = {"k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype)}
    elif lt == "mla":
        m = cfg.mla
        c = {
            "c_kv": jnp.zeros((B, S, m.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((B, S, m.qk_rope_dim), dtype),
        }
    elif lt == "mamba":
        d_in = cfg.ssm.expand * cfg.d_model
        c = {
            "h": jnp.zeros((B, d_in, cfg.ssm.d_state), jnp.float32),
            "conv": jnp.zeros((B, cfg.ssm.d_conv - 1, d_in), dtype),
        }
    elif lt == "rwkv":
        H = cfg.d_model // cfg.ssm.head_dim
        c = {
            "S": jnp.zeros((B, H, cfg.ssm.head_dim, cfg.ssm.head_dim), jnp.float32),
            "x_tm": jnp.zeros((B, 1, cfg.d_model), dtype),
            "x_cm": jnp.zeros((B, 1, cfg.d_model), dtype),
        }
    else:
        raise ValueError(lt)
    return c


def make_cache(cfg, B, S_max, *, lengths=None, dtype=jnp.bfloat16,
               enc_frames: int | None = None):
    """Zero decode cache (dry-run / pre-prefill).  Stacked [n_periods, ...]."""
    p = cfg.period
    n_periods = cfg.n_layers // p
    lengths = lengths if lengths is not None else jnp.zeros((B,), jnp.int32)

    def one_period():
        per = {}
        for j in range(p):
            lt = cfg.layer_types[j]
            c = _layer_cache_shape(cfg, lt, B, S_max, dtype)
            c["lengths"] = lengths
            if cfg.enc_dec and lt == "attn":
                Te = enc_frames or cfg.n_frontend_tokens
                c["cross"] = {
                    "k": jnp.zeros((B, Te, cfg.n_kv_heads, cfg.d_head), dtype),
                    "v": jnp.zeros((B, Te, cfg.n_kv_heads, cfg.d_head), dtype),
                    "lengths": jnp.full((B,), Te, jnp.int32),
                }
            per[f"l{j}"] = c
        return per

    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (n_periods,) + x.shape),
        one_period())


def set_cache_lengths(cache, lengths):
    """Overwrite every layer's lengths ([B] int32) without touching cross."""

    def walk(d):
        out = {}
        for k, v in d.items():
            if k == "lengths":
                out[k] = jnp.broadcast_to(
                    lengths[None], (v.shape[0],) + lengths.shape
                ) if v.ndim == 2 else lengths
            elif k == "cross":
                out[k] = v
            elif isinstance(v, dict):
                out[k] = walk(v)
            else:
                out[k] = v
        return out

    return walk(cache)


# -------------------------------------------------------------- prefill ----
def prefill(params, cfg, batch, S_max, *, cache_dtype=jnp.bfloat16):
    """Run the prompt (equal lengths per batch), build the decode cache.

    batch: tokens [B,T] (+ frontend).  Returns (last_logits [B,V], cache).

    RNS grids: decoder-only text prompts install an all-ones *per-token*
    quantization mask, so every prompt token gets its own absmax grid —
    the same grid :func:`prefill_ragged` and chunked prefill
    (:func:`mixed_step`) compute for that token, which is what keeps all
    three prefill paths token-identical.  Frontend/enc-dec prompts mix
    non-token positions into the stack and keep the legacy whole-tensor
    grid (the continuous engine rejects them anyway).
    """
    from repro.core.quantize import token_mask

    tokens = batch["tokens"]
    B, T = tokens.shape
    enc_out = None
    F = 0
    mixes_frontend = cfg.enc_dec or (cfg.frontend is not None
                                     and "frontend" in batch)
    mask = (jnp.ones((B, T), bool)
            if cfg.rns is not None and not mixes_frontend else None)
    with token_mask(mask, per_token=True):
        h = _embed_tokens(params, cfg, tokens)
        if cfg.enc_dec:
            enc_out = _encode(params, cfg, batch["frontend"])
        elif cfg.frontend is not None and "frontend" in batch:
            F = batch["frontend"].shape[1]
            h = jnp.concatenate([batch["frontend"].astype(h.dtype), h],
                                axis=1)
        h = _add_abs_pos(cfg, h)
        h, ys, _aux = tf.apply_blocks(params["blocks"], h, cfg,
                                      mode="prefill", enc_out=enc_out)
        logits_last = _logits(params, cfg, h[:, -1:])[:, 0]

    Tc = T + F
    lengths = jnp.full((B,), Tc, jnp.int32)
    cache = make_cache(cfg, B, S_max, lengths=lengths, dtype=cache_dtype,
                       enc_frames=None if not cfg.enc_dec
                       else batch["frontend"].shape[1])

    # write prefill KV/state into the zero cache
    p = cfg.period
    new_cache = {}
    for j in range(p):
        lt = cfg.layer_types[j]
        z = dict(cache[f"l{j}"])
        y = ys[f"l{j}"]
        if lt == "attn":
            if cfg.enc_dec:
                (k, v), (xk, xv) = y
                z["cross"] = dict(z["cross"], k=xk.astype(cache_dtype),
                                  v=xv.astype(cache_dtype))
            else:
                k, v = y
            z["k"] = jax.lax.dynamic_update_slice_in_dim(
                z["k"], k.astype(cache_dtype), 0, axis=2)
            z["v"] = jax.lax.dynamic_update_slice_in_dim(
                z["v"], v.astype(cache_dtype), 0, axis=2)
        elif lt == "mla":
            ckv, krope = y
            z["c_kv"] = jax.lax.dynamic_update_slice_in_dim(
                z["c_kv"], ckv.astype(cache_dtype), 0, axis=2)
            z["k_rope"] = jax.lax.dynamic_update_slice_in_dim(
                z["k_rope"], krope.astype(cache_dtype), 0, axis=2)
        elif lt == "mamba":
            h_last, conv_tail = y
            z["h"], z["conv"] = h_last, conv_tail.astype(z["conv"].dtype)
        elif lt == "rwkv":
            (S_last, x_tm), x_cm = y
            z["S"], z["x_tm"] = S_last, x_tm.astype(z["x_tm"].dtype)
            z["x_cm"] = x_cm.astype(z["x_cm"].dtype)
        new_cache[f"l{j}"] = z
    return logits_last, new_cache


# ------------------------------------------------------- ragged prefill ----
def prefill_ragged(params, cfg, batch, lengths):
    """Mixed-length prefill for continuous batching (paged caches).

    ``batch["tokens"]`` [B, Tpad] right-padded (pad id is irrelevant —
    causal masking keeps pad positions out of every valid position's
    receptive field, and positionwise ops never mix rows/positions), with
    per-row prompt ``lengths`` [B].  One compilation serves *every*
    prompt length <= Tpad.

    Returns (logits at each row's last prompt token [B, V], ys) where
    ``ys`` are the raw per-layer prefill outputs ([n_periods, B, Tpad,
    ...] KV planes) for the caller to blit into its paged cache — see
    ``serve/kv_cache.write_prompt_pages``.

    RNS exactness under padding: a per-tensor absmax grid over the padded
    activations would couple each row's quantization to pad garbage, so a
    :class:`~repro.core.quantize.token_mask` context is installed for the
    whole stack.  The mask is ``per_token``: every prompt token quantizes
    on its own (row, token) absmax grid, which is invariant to padding,
    to batch composition, *and* to how the prompt is split into chunks —
    the property chunked prefill (``mixed_step``) needs to stay
    token-identical to a whole-prompt run.  The bucketed :func:`prefill`
    installs the same per-token grid, so both prefill paths agree
    bit-for-bit.  The float path never consults the mask.

    Sliding windows (``cfg.attn_window``) narrow the in-prompt receptive
    field here exactly as they do at decode: the window mask rides the
    causal mask inside models/attention.py, so a windowed prefill +
    windowed paged decode agree with a windowed solo run even after the
    serving scheduler has recycled the evicted positions' pages.

    Decoder-only, causal, no frontend (the continuous engine validates).
    """
    from repro.core.quantize import token_mask

    tokens = batch["tokens"]
    B, Tpad = tokens.shape
    valid = jnp.arange(Tpad)[None, :] < lengths[:, None]
    with token_mask(valid if cfg.rns is not None else None, per_token=True):
        h = _embed_tokens(params, cfg, tokens)
        h = _add_abs_pos(cfg, h)
        h, ys, _aux = tf.apply_blocks(params["blocks"], h, cfg,
                                      mode="prefill")
        h_last = h[jnp.arange(B), lengths - 1][:, None]    # [B, 1, d]
        return _logits(params, cfg, h_last)[:, 0], ys


# --------------------------------------------------------------- decode ----
def decode_step(params, cfg, token, cache, active=None):
    """token [B,1] int32 -> (logits [B,V], updated cache).

    ``active`` [B] bool (continuous batching): inactive rows keep their
    ``lengths`` frozen — their compute is garbage the engine discards,
    and their cache writes land on the paged pool's trash page.  On the
    RNS path ``active`` doubles as the quantization token-mask, so each
    row's fixed-point grid is its own (a batched decode step is then
    bit-identical per row to a solo decode — same guarantee as
    :func:`prefill_ragged`).
    """
    from repro.core.quantize import token_mask

    mask = active[:, None] if (active is not None
                               and cfg.rns is not None) else None
    with token_mask(mask):
        h = _embed_tokens(params, cfg, token)
        # absolute-pos archs gather the position embedding at `lengths`
        if cfg.pos_emb == "sinusoidal":
            lengths = _cache_lengths(cache)
            table = sinusoidal_positions(_cache_smax(cfg, cache), cfg.d_model,
                                         h.dtype)
            h = h + table[lengths][:, None]
        h, ys, _ = tf.apply_blocks(params["blocks"], h, cfg, mode="decode",
                                   cache=cache)
        logits = _logits(params, cfg, h)[:, 0]
    step = 1 if active is None else active.astype(jnp.int32)
    new_cache = set_cache_lengths(ys, _cache_lengths(cache) + step)
    return logits, new_cache


def decode_window(params, cfg, tokens, cache, active=None):
    """Speculative-verify window: tokens [R, W] -> (logits [R, W, V], ys).

    The window is [last_token, draft_1, ..., draft_{W-1}] per row; all W
    tokens' KV is written at positions lengths..lengths+W-1 (paged caches
    only) and ``logits[:, i]`` is the model's next-token distribution
    after consuming window position ``i`` — exactly what W consecutive
    :func:`decode_step` calls would produce, so a greedy accept/reject
    over these logits keeps the emitted stream token-identical to vanilla
    decode.  Cache ``lengths`` are NOT advanced here: the caller sets
    them to ``length + accepted + 1`` once it knows the accept counts
    (``ys`` is the raw per-layer cache with the window KV scattered in).

    On the RNS path the token mask is installed ``per_token``: each
    window position quantizes on its own (row, token) absmax grid — the
    same grid its solo decode step would compute — instead of a grid
    coupled to its window neighbours (see core/quantize.token_mask).
    """
    from repro.core.quantize import token_mask

    R, W = tokens.shape
    mask = None
    if active is not None and cfg.rns is not None:
        mask = jnp.broadcast_to(active[:, None], (R, W))
    with token_mask(mask, per_token=True):
        h = _embed_tokens(params, cfg, tokens)
        if cfg.pos_emb == "sinusoidal":
            lengths = _cache_lengths(cache)
            table = sinusoidal_positions(_cache_smax(cfg, cache), cfg.d_model,
                                         h.dtype)
            h = h + table[lengths[:, None] + jnp.arange(W)[None]]
        h, ys, _ = tf.apply_blocks(params["blocks"], h, cfg, mode="decode",
                                   cache=cache)
        logits = _logits(params, cfg, h)
    return logits, ys


def mixed_step(params, cfg, tokens, seg, pos, dec, valid, cache):
    """ONE packed chunked-prefill + decode step (paged caches only).

    ``tokens``/``seg``/``pos`` [N] int32, ``dec``/``valid`` [N] bool:
    lane i carries the token for row ``seg[i]`` at absolute position
    ``pos[i]`` — a decode row's next token (``dec``) or one token of a
    prefill chunk (``~dec``).  Pad lanes (``~valid``) carry ``seg = -1``:
    their KV lands on the trash page and their logits are garbage the
    engine discards.  N is the engine's fixed ``token_budget``, so ONE
    compilation serves every prefill/decode mix.

    Returns (logits [N, V], updated cache).  ``logits[i]`` is the
    next-token distribution after consuming lane i — meaningful for
    decode lanes and for each chunk's last token (TTFT!).  Cache
    ``lengths`` are not advanced; the engine owns them host-side and
    pushes fresh tables before every step.

    Every op of the step carries one of the ``jax.named_scope`` names
    ``embed``, ``norm``, ``qkv``, ``kv_write``, ``attn_gather``, ``attn``,
    ``attn_out``, ``mlp`` or ``logits`` in its HLO ``op_name``; ops under
    none of them are the layer scan's own (carry copies, weight slices).

    Token identity: per-token quantization grids (see
    :func:`prefill_ragged`), write-then-gather packed attention
    (models/attention.py ``*_decode_packed``), and a float32 page pool
    make each lane's math bitwise its solo bucketed counterpart.
    """
    from repro.core.quantize import token_mask

    mask = valid[None] if cfg.rns is not None else None
    with token_mask(mask, per_token=True):
        with jax.named_scope("embed"):
            h = _embed_tokens(params, cfg, tokens[None])
            if cfg.pos_emb == "sinusoidal":
                table = sinusoidal_positions(_cache_smax(cfg, cache),
                                             cfg.d_model, h.dtype)
                h = h + table[pos][None]
        h, ys, _ = tf.apply_blocks(params["blocks"], h, cfg, mode="decode",
                                   cache=cache, packed=(seg, pos, dec))
        with jax.named_scope("logits"):
            logits = _logits(params, cfg, h)[0]
    return logits, ys


def _cache_lengths(cache):
    first = cache[next(iter(cache))]
    return first["lengths"][0]


def _cache_smax(cfg, cache):
    first = cache[next(iter(cache))]
    if "block_table" in first:      # paged: capacity = max_blocks * page_size
        from repro.serve.kv_cache import page_feat

        for k, v in first.items():
            if k.endswith("_pages"):
                bs = v.shape[-1] // int(np.prod(page_feat(cfg, k)))
                return first["block_table"].shape[-1] * bs
    for k, v in first.items():
        if k in ("k", "c_kv"):
            return v.shape[2]
    return 1 << 20


# ------------------------------------------------------------ counting -----
def count_params(cfg):
    """(total, active) param counts via eval_shape (no allocation)."""
    shapes = jax.eval_shape(lambda k: init_model(k, cfg)[0],
                            jax.random.PRNGKey(0))
    total = 0
    routed = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        n = int(np.prod(leaf.shape))
        total += n
        keys = [getattr(k, "key", getattr(k, "name", "")) for k in path]
        if "moe" in keys and any(k in ("wi", "wg", "wo") for k in keys):
            routed += n
    active = total
    if cfg.moe is not None and routed:
        frac = cfg.moe.top_k / cfg.moe.n_experts
        active = total - routed + int(routed * frac)
    return total, active


def count_params_analytic(cfg, active_only: bool = False):
    total, active = count_params(cfg)
    return active if active_only else total
