"""jit'd public wrapper for the RNS matmul kernel (padding + batching)."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import dispatch
from repro.core.rns import tables
from repro.kernels.autotune import pow2_at_least as _pow2_at_least
from repro.kernels.rns_matmul.kernel import rns_matmul_tiles


def _pad_to(x, axis: int, mult: int):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def require_int8_residues(t) -> None:
    """The digit matmuls feed residues to the MXU as int8 operands, which
    is exact only while every modulus is <= 128."""
    if not t.profile.int8_safe:
        raise ValueError(
            f"profile {t.profile.name} has moduli above 128: its residues "
            "do not fit the int8 MXU operands of the Pallas kernels; use "
            "the reference backend")


def rns_matmul(
    profile, a_res, b_res, *, bm: int | None = None, bn: int | None = None,
    bk: int | None = None, interpret: bool | None = None,
):
    """a_res [K, ..., M, D], b_res [K, D, N] residues -> [K, ..., M, N] int32.

    Zero-pads every dim to the BlockSpec tile multiples (exact: zero
    residues contribute nothing mod m) and flattens leading batch dims.

    The M tile is always a multiple of 8 (TPU sublanes — ``min(bm, M)``
    alone produced Mosaic-illegal block shapes that only ran in interpret
    mode) and M is bucketed to the next power of two: mixed-batch callers
    whose row counts land in one bucket reuse ONE compiled kernel instead
    of keying a recompile on every distinct M.
    """
    if interpret is None:
        interpret = dispatch.default_interpret()
    t = tables(profile)
    require_int8_residues(t)
    if bm is None or bn is None or bk is None:
        from repro.kernels import autotune

        blk = autotune.get_blocks(
            "rns_matmul", t.profile.name,
            (int(np.prod(a_res.shape[1:-1], dtype=np.int64)),
             a_res.shape[-1], b_res.shape[-1]))
        bm = bm if bm is not None else blk["bm"]
        bn = bn if bn is not None else blk["bn"]
        bk = bk if bk is not None else blk["bk"]
    moduli = jnp.asarray(np.asarray(t.moduli, np.int32))
    S = a_res.shape[0]
    D = a_res.shape[-1]
    N = b_res.shape[-1]
    lead = a_res.shape[1:-1]
    a2 = a_res.reshape(S, -1, D)
    M = a2.shape[1]
    bm_eff = min(bm, _pow2_at_least(M))
    a2 = _pad_to(_pad_to(a2, 1, bm_eff), 2, bk)
    b2 = _pad_to(_pad_to(b_res, 1, bk), 2, bn)
    from repro.analysis.kernel_audit import check_wrapper_blocks

    check_wrapper_blocks(
        "rns_matmul", {"bm": bm_eff, "bn": bn, "bk": bk},
        dims={"M": a2.shape[1], "D": a2.shape[2], "N": b2.shape[2]},
        n_digits=S, res_bytes=a2.dtype.itemsize)
    out = rns_matmul_tiles(
        moduli, a2, b2, bm=bm_eff, bn=bn, bk=bk, interpret=interpret
    )
    out = out[:, :M, :N]
    return out.reshape((S,) + lead + (N,))
