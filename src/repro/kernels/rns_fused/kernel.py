"""Pallas TPU kernels: the paper's Fig. 5 datapath as ONE pass.

The unfused layer materializes every pipeline stage through HBM: the
conversion kernel writes [K, T] residue planes, the matmul kernel writes
[K, M, N] int32 accumulators, and the normalization kernel reads them
back.  On the paper's hardware those stages are a single wired pipeline —
forward converters sit at the edge of the digit-slice array and the MRC
unit sits after the accumulators — so the software analogue is kernel
fusion:

  * ``rns_fused_encode_matmul_tiles`` — the forward conversion
    (quantize/clip + per-digit reduction) runs in VMEM inside the matmul
    grid's K-loop prologue.  Activation residues NEVER round-trip HBM;
    the quantize is recomputed per digit slice and per K step, which is
    the classic fusion trade (cheap VPU work for HBM bandwidth).  The
    scale rides as a block-indexed [bm, 1] row operand, so per-sequence
    quantization grids (ragged prefill) fuse exactly like scalar grids.
  * ``rns_fused_matmul_normalize_tiles`` — the digit loop moves INSIDE
    the kernel (a [K, bm, bn] accumulator scratch instead of a K-sized
    grid axis) so the ``k == n_k - 1`` step can run the two-pass MRC +
    float reconstruction on the finished tile.  The [K, M, N] int32
    write of a main-path normalize disappears entirely.
  * ``rns_fused_dot_tiles`` — both fusions at once: float activations
    in, float values out, residues only ever exist in VMEM.

Exactness: all residue arithmetic is integer and the reduction schedule
per digit is the unfused kernel's (one lazy ``rem`` per bk step), so the
fused residues are bit-identical to convert->matmul; the epilogue reuses
``rns_normalize.kernel.mrc_float_tile``, so the floats are bit-identical
to the unfused normalize (asserted in tests/test_fused_kernels.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.rns_normalize.kernel import mrc_float_tile

from repro.core.rns import tables


def _quantize_tile(x, s, qmax: int):
    """clip(round(x * s)) — THE fixed-point rule (core/quantize.py)."""
    return jnp.clip(jnp.round(x * s), -qmax, qmax).astype(jnp.int32)


def _dot_s8(a, b):
    """Residue tiles as int8 operands into an int32 accumulator: the MXU's
    int8 mode (v5e has no s32 x s32 matmul).  Exact because every residue
    is < m <= 128."""
    return jax.lax.dot_general(
        a.astype(jnp.int8), b.astype(jnp.int8), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)


# ------------------------------------------------- encode + matmul --------
def _encode_matmul_kernel(m_ref, x_ref, s_ref, b_ref, o_ref, acc_ref, *,
                          n_k: int, qmax: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    m = m_ref[pl.program_id(0)]                           # slice modulus
    v = _quantize_tile(x_ref[...], s_ref[...], qmax)      # [bm, bk] int32
    a = jnp.remainder(v, m)                               # digit residues
    prod = _dot_s8(a, b_ref[0])
    # lazy modular reduction: one rem per K step keeps the carry < m
    acc_ref[...] = jnp.remainder(acc_ref[...] + prod, m)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[0] = acc_ref[...]


@functools.partial(
    jax.jit, static_argnames=("bits", "bm", "bn", "bk", "interpret")
)
def rns_fused_encode_matmul_tiles(
    moduli, x, s_rows, b_res, *, bits: int = 16, bm: int = 128,
    bn: int = 128, bk: int = 512, interpret: bool = False,
):
    """x [M, D] f32, s_rows [M, 1] f32, b_res [K, D, N] -> [K, M, N] int32.

    M, N, D must be multiples of (bm, bn, bk); ops.py pads (zero activation
    rows quantize to zero residues, which contribute nothing mod m).
    """
    K = b_res.shape[0]
    M, D = x.shape
    N = b_res.shape[-1]
    n_k = D // bk
    grid = (K, M // bm, N // bn, n_k)
    return pl.pallas_call(
        functools.partial(_encode_matmul_kernel, n_k=n_k,
                          qmax=2 ** (bits - 1) - 1),
        grid=grid,
        in_specs=[
            # moduli live whole in scalar memory, indexed by digit slice
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((bm, bk), lambda s, i, j, k: (i, k)),
            pl.BlockSpec((bm, 1), lambda s, i, j, k: (i, 0)),
            pl.BlockSpec((1, bk, bn), lambda s, i, j, k: (s, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda s, i, j, k: (s, i, j)),
        out_shape=jax.ShapeDtypeStruct((K, M, N), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(moduli.reshape(-1), x, s_rows, b_res)


# ---------------------------------------------- matmul + normalize --------
def _matmul_normalize_kernel(a_ref, b_ref, o_ref, acc_ref, *, profile,
                             n_k: int):
    t = tables(profile)
    K = t.profile.n_digits
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for j in range(K):
        m = jnp.int32(int(t.moduli[j]))
        prod = _dot_s8(a_ref[j], b_ref[j])
        acc_ref[j] = jnp.remainder(acc_ref[j] + prod, m)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = mrc_float_tile([acc_ref[j] for j in range(K)], t)


@functools.partial(
    jax.jit, static_argnames=("profile", "bm", "bn", "bk", "interpret")
)
def rns_fused_matmul_normalize_tiles(
    a_res, b_res, *, profile, bm: int = 128, bn: int = 128, bk: int = 512,
    interpret: bool = False,
):
    """a_res [K, M, D], b_res [K, D, N] residues -> [M, N] float32
    signed values (unscaled) — no [K, M, N] int32 ever leaves the core."""
    K, M, D = a_res.shape
    N = b_res.shape[-1]
    n_k = D // bk
    grid = (M // bm, N // bn, n_k)
    return pl.pallas_call(
        functools.partial(_matmul_normalize_kernel, profile=profile, n_k=n_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((K, bm, bk), lambda i, j, k: (0, i, k)),
            pl.BlockSpec((K, bk, bn), lambda i, j, k: (0, k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((K, bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(a_res, b_res)


# --------------------------------- encode + matmul + normalize (full) -----
def _fused_dot_kernel(x_ref, s_ref, b_ref, o_ref, acc_ref, *, profile,
                      n_k: int, qmax: int):
    t = tables(profile)
    K = t.profile.n_digits
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    v = _quantize_tile(x_ref[...], s_ref[...], qmax)      # shared by digits
    for j in range(K):
        m = jnp.int32(int(t.moduli[j]))
        a = jnp.remainder(v, m)
        prod = _dot_s8(a, b_ref[j])
        acc_ref[j] = jnp.remainder(acc_ref[j] + prod, m)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = mrc_float_tile([acc_ref[j] for j in range(K)], t)


@functools.partial(
    jax.jit, static_argnames=("profile", "bits", "bm", "bn", "bk", "interpret")
)
def rns_fused_dot_tiles(
    x, s_rows, b_res, *, profile, bits: int = 16, bm: int = 128,
    bn: int = 128, bk: int = 512, interpret: bool = False,
):
    """x [M, D] f32, s_rows [M, 1], b_res [K, D, N] -> [M, N] float32
    signed values (unscaled): the whole Fig. 5 pipeline in one pass."""
    K = b_res.shape[0]
    M, D = x.shape
    N = b_res.shape[-1]
    n_k = D // bk
    grid = (M // bm, N // bn, n_k)
    return pl.pallas_call(
        functools.partial(_fused_dot_kernel, profile=profile, n_k=n_k,
                          qmax=2 ** (bits - 1) - 1),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bm, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((K, bk, bn), lambda i, j, k: (0, k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((K, bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, s_rows, b_res)
