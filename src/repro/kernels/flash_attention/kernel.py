"""Pallas TPU kernel: fused online-softmax attention (inference forward).

The jnp flash path (models/attention.py) tiles q x kv at the XLA level;
this kernel fuses the whole online-softmax pipeline into one VMEM-resident
loop per q tile — no scores/probs ever reach HBM.  Used by the serving
path; training keeps the differentiable jnp formulation.

Grid: (batch*heads, Tq/bq, Tk/bk), KV innermost ("arbitrary" semantics);
BlockSpec tiles are MXU-aligned; running max/denominator/accumulator live
in VMEM scratch across KV steps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            n_k: int, bq: int, bk: int, causal: bool, scale: float,
            tk_valid: int):
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)            # [bq, D]
    k = k_ref[0].astype(jnp.float32)            # [bk, D]
    v = v_ref[0].astype(jnp.float32)            # [bk, Dv]
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                    # [bq, bk]

    qi = pl.program_id(1)
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = kpos < tk_valid                      # padded KV tail
    if causal:
        valid = valid & (qpos >= kpos)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kj == n_k - 1)
    def _done():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(
            o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "bq", "bk", "tk_valid", "interpret"))
def flash_attention_bhtd(q, k, v, *, causal: bool, tk_valid: int,
                         bq: int = 128, bk: int = 128,
                         interpret: bool = False):
    """q [BH, Tq, D], k/v [BH, Tk, D(v)] -> out [BH, Tq, Dv].

    Tq % bq == 0 and Tk % bk == 0 (ops.py pads; tk_valid masks the pad).
    """
    BH, Tq, D = q.shape
    _, Tk, Dv = v.shape
    n_k = Tk // bk
    grid = (BH, Tq // bq, n_k)
    scale = float(1.0 / np.sqrt(D))
    return pl.pallas_call(
        functools.partial(_kernel, n_k=n_k, bq=bq, bk=bk, causal=causal,
                          scale=scale, tk_valid=tk_valid),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, Dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, Dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Tq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, Dv), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
