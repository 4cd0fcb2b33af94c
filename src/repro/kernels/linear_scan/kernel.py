"""Pallas TPU kernel: chunked gated linear recurrence (Mamba2/RWKV6 core).

Computes, per (batch*head) grid row with the chunk axis innermost:

    S_t = diag(exp(la_t)) S_{t-1} + k_t v_t^T
    y_t = q_t^T S_t                          (include_current=True, Mamba2)
    y_t = q_t^T (S_{t-1} + diag(u) k_t v_t^T)  (RWKV6 bonus form)

The (K, V) state lives in VMEM scratch and is carried across the
sequential chunk grid — the HBM traffic is exactly one read of q/k/v/la
and one write of y (roofline-optimal for this op).  Within a chunk the
quadratic intra-chunk form runs on the MXU ((L,K)x(K,L) and (L,L)x(L,V)
matmuls), mirroring repro.models.recurrence.linear_recurrence's math
(factorised per-dim decay with the same clamp).

Shapes: q,k,la (BH, S, K); v (BH, S, V); u (BH, K) or None.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

LOG_A_MIN = -8.0


def _kernel(q_ref, k_ref, v_ref, la_ref, u_ref, y_ref, s_scr, *,
            chunk: int, include_current: bool, use_u: bool):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    # the two matmuls below that stand in for exact elementwise ops (no
    # cumsum or column broadcast in the TPU kernel compiler) run at full
    # fp32 precision
    exact_dot = functools.partial(jax.lax.dot,
                                  precision=jax.lax.Precision.HIGHEST)
    q = q_ref[0].astype(jnp.float32)               # (L, K)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)               # (L, V)
    la = jnp.clip(la_ref[0].astype(jnp.float32), LOG_A_MIN, 0.0)
    L = chunk
    K = q.shape[1]

    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    # inclusive prefix sum over the chunk as a lower-triangular matmul
    cum = exact_dot(jnp.where(ii >= jj, 1.0, 0.0), la)   # (L, K)
    shift = cum if include_current else cum - la

    # inter-chunk: y += (q * exp(shift)) @ S_in
    s_in = s_scr[...]                              # (K, V)
    qf = q * jnp.exp(shift)
    y = jax.lax.dot(qf, s_in)                      # (L, V)

    # intra-chunk: factorised decay scores, causal mask
    kf = k * jnp.exp(-cum)
    scores = jax.lax.dot_general(qf, kf, (((1,), (1,)), ((), ())))  # (L, L)
    off = 0 if include_current else -1
    scores = jnp.where((ii + off) >= jj, scores, 0.0)
    if use_u:
        u = u_ref[0].astype(jnp.float32)           # (1, K)
        cur = jnp.sum(q * u * k, axis=1, keepdims=True)  # (L, 1)
        scores = scores + jnp.where(ii == jj, cur, 0.0)  # current-token bonus
    y = y + jax.lax.dot(scores, v)

    # state update: S_out = exp(tot) * S_in + sum_s exp(tot - cum_s) k_s v_s
    tot = cum[L - 1:L, :]                          # (1, K)
    kdec = k * jnp.exp(tot - cum)                  # (L, K)
    kk_i = jax.lax.broadcasted_iota(jnp.int32, (K, K), 0)
    kk_j = jax.lax.broadcasted_iota(jnp.int32, (K, K), 1)
    decay = jnp.where(kk_i == kk_j, jnp.exp(tot), 0.0)   # diag(exp(tot))
    s_scr[...] = (exact_dot(decay, s_in)
                  + jax.lax.dot_general(kdec, v, (((0,), (0,)), ((), ()))))

    y_ref[0] = y.astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "include_current",
                                             "interpret"))
def linear_scan(q, k, v, la, u=None, *, chunk: int = 64,
                include_current: bool = True,
                interpret: Optional[bool] = None):
    """Returns y (BH, S, V).  u (BH, K) enables the RWKV6 bonus term
    (pass include_current=False with it).  ``interpret`` resolves through
    ``repro.kernels.interpret_mode``."""
    BH, S, K = q.shape
    V = v.shape[-1]
    assert S % chunk == 0, (S, chunk)
    use_u = u is not None
    if u is None:
        u = jnp.zeros((BH, K), q.dtype)
    # (BH, 1, K) so the (1, 1, K) block spans the array's last two dims,
    # as the TPU tiling requires
    u = u.reshape(BH, 1, K)
    grid = (BH, S // chunk)
    kern = functools.partial(_kernel, chunk=chunk,
                             include_current=include_current, use_u=use_u)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, K), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, K), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, V), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, K), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, K), lambda b, c: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, V), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, V), v.dtype),
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(q, k, v, la, u)
