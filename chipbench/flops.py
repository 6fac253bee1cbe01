"""Operations and bytes the algorithm needs, from shapes alone."""

from __future__ import annotations

from chipbench.model import Dims


def matmul_params(dm: Dims) -> int:
    """Weights that take part in a matmul for every token: the layers'
    projections and MLP, and the LM head (the embedding is a lookup)."""
    q, kv = dm.heads * dm.head_dim, dm.kv_heads * dm.head_dim
    per_layer = dm.d * (q + 2 * kv) + q * dm.d + 3 * dm.d * dm.ff
    return dm.layers * per_layer + dm.d * dm.vocab


def model_flops(dm: Dims, tokens: int, context_sum: int) -> float:
    """Model FLOPs of ``tokens`` tokens whose contexts (keys attended,
    the token itself included) sum to ``context_sum``: 2 per weight per
    token, plus 4 * heads * head_dim per key per layer for the scores
    and the weighted sum.  The same work whatever numeric path runs it."""
    attn = 4 * dm.layers * dm.heads * dm.head_dim
    return 2.0 * matmul_params(dm) * tokens + float(attn) * context_sum


def fused_dot_cost(rows: int, d_in: int, d_out: int, digits: int
                   ) -> tuple[float, float]:
    """(int8 ops, HBM bytes) of one fused residue dot: ``rows`` float32
    activation rows and their scales in, ``digits`` int8 digit planes of
    a [d_in, d_out] weight in, float32 rows out.  Every digit plane is a
    full int8 matmul: 2 * rows * d_in * d_out operations each."""
    ops = 2.0 * digits * rows * d_in * d_out
    nbytes = (4.0 * rows * d_in + 4.0 * rows + digits * d_in * d_out
              + 4.0 * rows * d_out)
    return ops, nbytes


def mlp_launches(dm: Dims, rows: int, digits: int) -> list[tuple]:
    """(ops, bytes) of the fused launches of one packed step: wi, wg and
    wo of every layer."""
    one = [fused_dot_cost(rows, dm.d, dm.ff, digits),
           fused_dot_cost(rows, dm.d, dm.ff, digits),
           fused_dot_cost(rows, dm.ff, dm.d, digits)]
    return one * dm.layers


def least_time(ops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The roofline's least time and the bound that sets it."""
    t_ops = ops / peak["int8_ops"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
