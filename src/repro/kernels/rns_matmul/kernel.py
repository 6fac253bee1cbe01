"""Pallas TPU kernel: digit-sliced modular matmul (the RNS-TPU matrix unit).

One grid slot per (digit slice, M tile, N tile, K step).  Each digit slice is
an independent "layer" of the paper's Fig. 5 — an int8 MXU matmul with a
modular reduction folded into the accumulator ("fixed MOD ... inserted as a
final step just after accumulation", which the paper identifies as the
TPU-compatible option).  Residues < 128 keep every int8 product < 2**14, so
a K-step partial sum of up to bk<=2**17 terms plus the carried accumulator
stays inside int32 — the lazy-reduction guarantee.

BlockSpec tiling: (bm, bk) x (bk, bn) VMEM tiles, MXU-aligned (128x128
output tile, 512-deep K streaming), int32 accumulator scratch in VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(m_ref, a_ref, b_ref, o_ref, acc_ref, *, n_k: int):
    k = pl.program_id(3)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # int8 operands, int32 accumulator: the MXU's int8 mode (v5e has no
    # s32 x s32 matmul).  Exact because every residue is < m <= 128.
    a = a_ref[0].astype(jnp.int8)           # [bm, bk]
    b = b_ref[0].astype(jnp.int8)           # [bk, bn]
    prod = jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )
    m = m_ref[pl.program_id(0)]             # this digit slice's modulus
    # lazy modular reduction: one rem per K step keeps the carry < m
    acc_ref[...] = jnp.remainder(acc_ref[...] + prod, m)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[0] = acc_ref[...]


@functools.partial(
    jax.jit, static_argnames=("bm", "bn", "bk", "interpret")
)
def rns_matmul_tiles(
    moduli, a_res, b_res, *, bm: int = 128, bn: int = 128, bk: int = 512,
    interpret: bool = False,
):
    """a_res [S, M, D] int8/int32, b_res [S, D, N] -> [S, M, N] int32.

    M, N, D must be multiples of (bm, bn, bk); ops.py pads (zero padding is
    exact: zeros contribute nothing to the product-sum mod m).
    """
    S, M, D = a_res.shape
    _, _, N = b_res.shape
    n_k = D // bk
    grid = (S, M // bm, N // bn, n_k)
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k),
        grid=grid,
        in_specs=[
            # moduli live whole in scalar memory, indexed by digit slice
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, bm, bk), lambda s, i, j, k: (s, i, k)),
            pl.BlockSpec((1, bk, bn), lambda s, i, j, k: (s, k, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda s, i, j, k: (s, i, j)),
        out_shape=jax.ShapeDtypeStruct((S, M, N), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(moduli.reshape(-1), a_res, b_res)
