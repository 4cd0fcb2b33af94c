"""Pallas TPU kernel: causal flash attention (forward).

Online-softmax block attention tiled for VMEM/MXU: grid is
(batch*heads, num_q_blocks, num_kv_blocks) with the kv axis innermost —
the TPU grid is sequential, so the running max / denominator / output
accumulator live in VMEM scratch carried across kv steps.  Block shapes
are (BQ, head_dim) / (BK, head_dim) with 128-multiple tiles to keep the
MXU systolic array full.  Supports causal masking and an optional
sliding window (for the SWA serve variant).

This is the substrate kernel the model zoo's attention layers target on
real TPUs; the XLA chunked path in models/attention.py is the lowering
used for the CPU dry-run, and ref.py is the oracle both are tested
against (interpret mode on the CPU backend).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, bq: int, bk: int, seq: int, window):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window

    q = q_ref[0].astype(jnp.float32)            # (bq, d)
    k = k_ref[0].astype(jnp.float32)            # (bk, d)
    v = v_ref[0].astype(jnp.float32)            # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                          # (bq, 1)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur)                       # (bq, bk)
    l_cur = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(p, v)
    m_scr[...] = m_cur
    l_scr[...] = l_cur

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bq", "bk", "window", "interpret"))
def flash_attention(q, k, v, *, bq: int = 128, bk: int = 128, window=None,
                    interpret: Optional[bool] = None):
    """q, k, v: (BH, S, D) (kv heads pre-broadcast to q heads).  Causal.
    Returns (BH, S, D).  ``interpret`` resolves through
    ``repro.kernels.interpret_mode``."""
    BH, S, D = q.shape
    assert S % bq == 0 and S % bk == 0, (S, bq, bk)
    scale = 1.0 / (D ** 0.5)
    grid = (BH, S // bq, S // bk)
    kern = functools.partial(_kernel, scale=scale, bq=bq, bk=bk, seq=S,
                             window=window)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running denominator
            pltpu.VMEM((bq, D), jnp.float32),   # output accumulator
        ],
        interpret=interpret_mode(interpret),
    )(q, k, v)
