"""The plain reference: the model's forward pass in float32 ``jax.numpy``
at the highest matmul precision, with no kernel, cache or batching of
the program's.  It reads the benchmark's own weights (``model.py``).

Llama-style decoder as published for both configurations: RMSNorm,
rotary positions (half split), grouped-query causal attention, a gated
SiLU MLP, a final RMSNorm and the LM head (tied or not).

``bits`` turns it into the control: each MLP matmul then takes operands
quantized to ``bits`` signed bits (one scale per token for activations,
one per layer for each weight, symmetric absmax), multiplied exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.model import Dims


def _rmsnorm(x, scale, eps):

    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, theta):
    """x [B, T, H, D] at positions 0..T-1."""

    T, D = x.shape[1], x.shape[-1]
    d2 = D // 2
    freqs = 1.0 / (theta ** (np.arange(d2, dtype=np.float32) / d2))
    ang = np.arange(T, dtype=np.float32)[:, None] * freqs       # [T, d2]
    sin, cos = jnp.sin(ang)[None, :, None], jnp.cos(ang)[None, :, None]
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _quantized_matmul(x, w, bits):
    """x [..., K] @ w [K, N] on ``bits``-bit symmetric grids."""

    qmax = float(2 ** (bits - 1) - 1)
    ax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    sx = jnp.where(ax > 0, qmax / ax, 1.0)
    aw = jnp.max(jnp.abs(w))
    sw = jnp.where(aw > 0, qmax / aw, 1.0)
    xq = jnp.clip(jnp.round(x * sx), -qmax, qmax)
    wq = jnp.clip(jnp.round(w * sw), -qmax, qmax)
    # integer operands below 2**7 and sums below 2**24: exact in float32
    return (xq @ wq) / (sx * sw)


def logits(params, dm: Dims, tokens, bits: int | None = None):
    """Next-token logits [B, T, V] of ``tokens`` [B, T]."""

    B, T = tokens.shape
    H, Hk, D = dm.heads, dm.kv_heads, dm.head_dim
    causal = np.tril(np.ones((T, T), bool))
    mm = (lambda x, w: x @ w) if bits is None else functools.partial(
        _quantized_matmul, bits=bits)

    def layer(h, lp):
        x = _rmsnorm(h, lp["ln1"]["scale"], dm.eps)
        a = lp["attn"]
        q = _rope((x @ a["wq"]["w"]).reshape(B, T, H, D), dm.rope_theta)
        k = _rope((x @ a["wk"]["w"]).reshape(B, T, Hk, D), dm.rope_theta)
        v = (x @ a["wv"]["w"]).reshape(B, T, Hk, D)
        q = q.reshape(B, T, Hk, H // Hk, D)
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k) / np.sqrt(D)
        s = jnp.where(causal, s, -jnp.inf)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(s, axis=-1), v)
        h = h + o.reshape(B, T, H * D) @ a["wo"]["w"]
        x = _rmsnorm(h, lp["ln2"]["scale"], dm.eps)
        m = lp["mlp"]
        g = jax.nn.silu(mm(x, m["wg"]["w"])) * mm(x, m["wi"]["w"])
        return h + mm(g, m["wo"]["w"]), None

    with jax.default_matmul_precision("highest"):
        h = params["embed"]["table"][tokens]
        h, _ = jax.lax.scan(layer, h, params["blocks"]["l0"])
        h = _rmsnorm(h, params["final_norm"]["scale"], dm.eps)
        if dm.tied:
            return h @ params["embed"]["table"].T
        return h @ params["lm_head"]["w"]


@functools.partial(jax.jit, static_argnames=("dm", "bits", "logits"))
def gaps(params, tokens, targets, dm, bits: int | None = None,
         logits=logits):
    """Per position, how far the reference's logit of a token lies below
    its best.

    ``targets`` [B, T] are the served tokens (-1 where none is served).
    ``logits`` is the architecture's reference (``archs/<arch>.py``; the
    dense one by default) and ``dm`` its sizes.
    Returns (served_gap [B, T], control_gap [B, T] or None): the gap of
    each served token, and with ``bits`` the gap of the token that the
    ``bits``-bit control puts first at the same position."""

    ref = logits(params, dm, tokens)
    best = jnp.max(ref, axis=-1)
    served = jnp.take_along_axis(ref, jnp.maximum(targets, 0)[..., None],
                                 axis=-1)[..., 0]
    served_gap = jnp.where(targets >= 0, best - served, 0.0)
    if bits is None:
        return served_gap, None
    top = jnp.argmax(logits(params, dm, tokens, bits=bits), axis=-1)
    ctrl = jnp.take_along_axis(ref, top[..., None], axis=-1)[..., 0]
    return served_gap, jnp.where(targets >= 0, best - ctrl, 0.0)
