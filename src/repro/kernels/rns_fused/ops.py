"""jit-friendly wrappers for the fused kernels (padding + batching).

Shared conventions with the unfused wrappers:

  * leading batch dims flatten into M; every dim zero-pads to its tile
    multiple (exact — zero rows quantize to zero residues);
  * the M tile is bucketed to a power of two >= 8 (Mosaic sublane
    legality + one compile per bucket, not per distinct M);
  * ``None`` block sizes resolve through kernels/autotune.py.

Scale layout: ``scale`` may be a scalar or anything that broadcasts to
``x.shape[:-1] + (1,)`` — i.e. at most one scale per ROW of the flattened
[M, D] activation (the per-sequence grids of ragged prefill).  Per-column
grids cannot fold into a row operand; core/dispatch.py guards that and
decomposes instead.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import dispatch
from repro.core.rns import tables
from repro.kernels.rns_fused.kernel import (
    rns_fused_dot_tiles,
    rns_fused_encode_matmul_tiles,
    rns_fused_matmul_normalize_tiles,
)
from repro.kernels.rns_matmul.ops import (
    _pad_to,
    _pow2_at_least,
    require_int8_residues,
)


def _blocks(kind, t, shape, bm, bn, bk):
    require_int8_residues(t)
    if bm is None or bn is None or bk is None:
        from repro.kernels import autotune

        blk = autotune.get_blocks(kind, t.profile.name, shape)
        bm = bm if bm is not None else blk["bm"]
        bn = bn if bn is not None else blk["bn"]
        bk = bk if bk is not None else blk["bk"]
    return bm, bn, bk


def _check_blocks(kind, bm_eff, bn, bk, dims, n_digits, res_bytes):
    """Fail fast (kernel + blocks + VMEM bytes named) before lowering."""
    from repro.analysis.kernel_audit import check_wrapper_blocks

    check_wrapper_blocks(kind, {"bm": bm_eff, "bn": bn, "bk": bk},
                         dims=dims, n_digits=n_digits, res_bytes=res_bytes)


def _prep_activation(x, scale, bm_eff, bk):
    """Flatten x to padded [Mp, Dp] and scale to padded [Mp, 1] rows."""
    lead = x.shape[:-1]
    D = x.shape[-1]
    x2 = x.reshape(-1, D).astype(jnp.float32)
    M = x2.shape[0]
    s = jnp.asarray(scale, jnp.float32)
    s2 = jnp.broadcast_to(s, lead + (1,)).reshape(M, 1) if s.ndim else (
        jnp.broadcast_to(s, (M, 1)))
    x2 = _pad_to(_pad_to(x2, 0, bm_eff), 1, bk)
    s2 = _pad_to(s2, 0, bm_eff)
    return x2, s2, M, lead


def rns_fused_encode_matmul(
    profile, x, scale, b_res, *, bits: int = 16, bm: int | None = None,
    bn: int | None = None, bk: int | None = None,
    interpret: bool | None = None,
):
    """x [..., D] f32 + scale rows + b_res [K, D, N] -> [K, ..., N] int32.

    Bit-identical to ``convert(x, scale)`` -> ``matmul`` without the
    [K, ..., D] activation-residue round-trip through HBM.
    """
    if interpret is None:
        interpret = dispatch.default_interpret()
    t = tables(profile)
    D = x.shape[-1]
    N = b_res.shape[-1]
    bm, bn, bk = _blocks("rns_fused_encode_matmul", t,
                         (int(np.prod(x.shape[:-1], dtype=np.int64)), D, N),
                         bm, bn, bk)
    moduli = jnp.asarray(np.asarray(t.moduli, np.int32))
    bm_eff = min(bm, _pow2_at_least(x.reshape(-1, D).shape[0]))
    x2, s2, M, lead = _prep_activation(x, scale, bm_eff, bk)
    b2 = _pad_to(_pad_to(b_res, 1, bk), 2, bn)
    _check_blocks("rns_fused_encode_matmul", bm_eff, bn, bk,
                  {"M": x2.shape[0], "D": x2.shape[1], "N": b2.shape[2]},
                  b_res.shape[0], b2.dtype.itemsize)
    out = rns_fused_encode_matmul_tiles(
        moduli, x2, s2, b2, bits=bits, bm=bm_eff, bn=bn, bk=bk,
        interpret=interpret)
    return out[:, :M, :N].reshape((out.shape[0],) + lead + (N,))


def rns_fused_matmul_normalize(
    profile, a_res, b_res, *, bm: int | None = None, bn: int | None = None,
    bk: int | None = None, interpret: bool | None = None,
):
    """a_res [K, ..., D] + b_res [K, D, N] -> [..., N] float32 (unscaled).

    Bit-identical to ``matmul`` -> ``normalize`` without the [K, ..., N]
    int32 accumulator write.
    """
    if interpret is None:
        interpret = dispatch.default_interpret()
    t = tables(profile)
    K = a_res.shape[0]
    D = a_res.shape[-1]
    N = b_res.shape[-1]
    lead = a_res.shape[1:-1]
    a2 = a_res.reshape(K, -1, D)
    M = a2.shape[1]
    bm, bn, bk = _blocks("rns_fused_matmul_normalize", t, (M, D, N),
                         bm, bn, bk)
    bm_eff = min(bm, _pow2_at_least(M))
    a2 = _pad_to(_pad_to(a2, 1, bm_eff), 2, bk)
    b2 = _pad_to(_pad_to(b_res, 1, bk), 2, bn)
    _check_blocks("rns_fused_matmul_normalize", bm_eff, bn, bk,
                  {"M": a2.shape[1], "D": a2.shape[2], "N": b2.shape[2]},
                  K, a2.dtype.itemsize)
    out = rns_fused_matmul_normalize_tiles(
        a2, b2, profile=t.profile.name, bm=bm_eff, bn=bn, bk=bk,
        interpret=interpret)
    return out[:M, :N].reshape(lead + (N,))


def rns_fused_dot(
    profile, x, scale, b_res, *, bits: int = 16, bm: int | None = None,
    bn: int | None = None, bk: int | None = None,
    interpret: bool | None = None,
):
    """x [..., D] f32 + scale rows + b_res [K, D, N] -> [..., N] float32
    signed values (unscaled): encode -> digit matmul -> MRC normalize in
    ONE pass; residues only ever live in VMEM."""
    if interpret is None:
        interpret = dispatch.default_interpret()
    t = tables(profile)
    D = x.shape[-1]
    N = b_res.shape[-1]
    bm, bn, bk = _blocks("rns_fused_dot", t,
                         (int(np.prod(x.shape[:-1], dtype=np.int64)), D, N),
                         bm, bn, bk)
    bm_eff = min(bm, _pow2_at_least(x.reshape(-1, D).shape[0]))
    x2, s2, M, lead = _prep_activation(x, scale, bm_eff, bk)
    b2 = _pad_to(_pad_to(b_res, 1, bk), 2, bn)
    _check_blocks("rns_fused_dot", bm_eff, bn, bk,
                  {"M": x2.shape[0], "D": x2.shape[1], "N": b2.shape[2]},
                  b_res.shape[0], b2.dtype.itemsize)
    out = rns_fused_dot_tiles(
        x2, s2, b2, profile=t.profile.name, bits=bits, bm=bm_eff, bn=bn,
        bk=bk, interpret=interpret)
    return out[:M, :N].reshape(lead + (N,))
