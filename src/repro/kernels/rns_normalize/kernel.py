"""Pallas TPU kernel: fused normalization unit (Fig. 5's purple pipeline).

Takes [K, T] residues, emits [T] float32 values: sign detection + mixed-
radix conversion + float reconstruction, all in VMEM.  Every modular
constant (m_j, MRC inverses, M/2 digits, W_j weights) is compiled into the
kernel — the hardware analogue is the fixed normalization pipeline the
paper sandwiches after the accumulator array.

The MRC is the paper's "slow" O(K^2) op; it runs ONCE per output element
(deferred normalization), so its cost is amortized over the whole product
summation that produced the element.

The tile-level helpers (:func:`mrc_digit_rows`, :func:`lex_ge`,
:func:`mrc_float_tile`) are shape-agnostic — they operate on a python
list of K same-shape residue blocks — so the fused matmul kernels
(kernels/rns_fused) run the SAME reconstruction on their [bm, bn]
accumulator tiles, which is what makes fused and unfused normalization
bit-identical.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.rns import tables


def mrc_digit_rows(rows, t):
    """rows: list of K same-shape int32 blocks -> list of K digit blocks."""
    K = len(rows)
    ms = [int(m) for m in t.moduli]
    r = list(rows)
    digits = []
    for i in range(K):
        d = r[i]
        digits.append(d)
        for j in range(i + 1, K):
            inv = int(t.mrc_inv[i, j])
            r[j] = jnp.remainder((r[j] - d) * inv, ms[j])
    return digits


def lex_ge(digits, ref_digits):
    """Lexicographic (most-significant-last) digits >= ref (elementwise)."""
    K = len(digits)
    ge = jnp.zeros_like(digits[0], dtype=jnp.bool_)
    eq = jnp.ones_like(digits[0], dtype=jnp.bool_)
    for j in range(K - 1, -1, -1):
        ref = jnp.int32(int(ref_digits[j]))
        ge = ge | (eq & (digits[j] > ref))
        eq = eq & (digits[j] == ref)
    return ge | eq


def mrc_float_tile(rows, t):
    """Two-pass MRC + float32 reconstruction of K residue blocks.

    Pass 1 detects the sign (X >= M/2 <=> negative), pass 2 re-runs the
    MRC on the magnitude so the float reconstruction never cancels
    against M.  Accumulation order (digit-ascending, float32) is the
    contract shared with core/mrc.decode_float — keep them in lockstep.
    """
    K = len(rows)
    ms = [int(m) for m in t.moduli]
    digits = mrc_digit_rows(rows, t)
    neg = lex_ge(digits, t.half_digits)
    mag = [
        jnp.where(neg, jnp.remainder(jnp.int32(ms[j]) - rows[j], ms[j]), rows[j])
        for j in range(K)
    ]
    mdig = mrc_digit_rows(mag, t)
    acc = jnp.zeros(rows[0].shape, dtype=jnp.float32)
    for j in range(K):
        acc = acc + mdig[j].astype(jnp.float32) * jnp.float32(float(t.W_f64[j]))
    return jnp.where(neg, -acc, acc)


def _kernel(x_ref, o_ref, *, profile):
    t = tables(profile)
    K = t.profile.n_digits
    rows = [x_ref[j][None, :] for j in range(K)]
    o_ref[...] = mrc_float_tile(rows, t)[0]


@functools.partial(jax.jit, static_argnames=("profile", "bt", "interpret"))
def rns_normalize_tiles(x, *, profile, bt: int = 1024, interpret: bool = False):
    """x [K, T] int32 residues -> [T] float32 signed values (unscaled)."""
    K, T = x.shape
    grid = (T // bt,)
    return pl.pallas_call(
        functools.partial(_kernel, profile=profile),
        grid=grid,
        in_specs=[pl.BlockSpec((K, bt), lambda i: (0, i))],
        out_specs=pl.BlockSpec((bt,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((T,), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(x)
