"""Pallas TPU kernel: forward conversion pipeline (binary -> residues).

Fuses the fixed-point quantize (round(x * s), clip) with the per-digit
modular reduction, emitting int8 digit planes ready for the digit-slice
matmul array.  This is the input half of the paper's purple conversion
pipeline; it is O(K) PAC work per element (cheap), unlike the reverse
direction's O(K^2) MRC.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.rns import tables


def _kernel(x_ref, s_ref, o_ref, *, profile, qmax: int, per_elem: bool):
    t = tables(profile)
    x = x_ref[...]
    # per_elem: a [bt] scale tile rides next to the x tile (per-sequence
    # quantization grids broadcast to elements); else one scalar in VMEM
    s = s_ref[...] if per_elem else s_ref[0, 0]
    v = jnp.clip(jnp.round(x * s), -qmax, qmax).astype(jnp.int32)
    for j, m in enumerate(t.moduli):
        o_ref[j] = jnp.remainder(v, jnp.int32(int(m))).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("profile", "bits", "bt", "interpret", "out_dtype")
)
def rns_convert_tiles(
    x, scale, *, profile, bits: int = 16, bt: int = 1024,
    interpret: bool = False, out_dtype=jnp.int8,
):
    """x [T] float32, scale scalar or [T] -> [K, T] residues."""
    t = tables(profile)
    K = t.profile.n_digits
    (T,) = x.shape
    grid = (T // bt,)
    per_elem = scale.ndim > 0
    s_spec = (pl.BlockSpec((bt,), lambda i: (i,)) if per_elem
              else pl.BlockSpec((1, 1), lambda i: (0, 0)))
    return pl.pallas_call(
        functools.partial(_kernel, profile=profile, qmax=2 ** (bits - 1) - 1,
                          per_elem=per_elem),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bt,), lambda i: (i,)),
            s_spec,
        ],
        out_specs=pl.BlockSpec((K, bt), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((K, T), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(x, scale if per_elem else scale.reshape(1, 1))
