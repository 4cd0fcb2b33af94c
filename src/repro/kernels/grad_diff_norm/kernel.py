"""Pallas TPU kernel: fused squared-norm of a gradient difference.

VAFL's Eq. 1 needs ||g_prev - g_cur||^2 over the client's full parameter
vector every round.  Naively that is three HBM passes (subtract ->
square -> reduce) over 2x model bytes; at 35 B params that is ~420 GB of
traffic.  This kernel streams both operands HBM->VMEM once in (TILE_M,
128) tiles, computes (a-b)^2 in VREGs and accumulates it across
the sequential TPU grid into one (8, 128) block of partial sums that
stays resident in VMEM; the final 1024-element sum runs outside the
kernel.  A single fused pass at the HBM roofline.

The epilogue V = diff_sq * (1 + N/1e3)^acc runs on the host side of the
jit (ops.py); it is O(1).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

LANE = 128      # TPU lane width
SUBLANE = 8     # fp32 sublanes per vreg
TILE_M = 256    # sublane tile: (256, 128) fp32 = 128 KiB/operand in VMEM


def _kernel(a_ref, b_ref, out_ref):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    d = a_ref[...].astype(jnp.float32) - b_ref[...].astype(jnp.float32)
    # fold the tile onto one (8, 128) vreg of partial sums: elementwise
    # adds only, no cross-lane reduce per grid step
    out_ref[...] += jnp.sum((d * d).reshape(TILE_M // SUBLANE, SUBLANE, LANE),
                            axis=0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def grad_diff_sq_norm_2d(a, b, *, interpret: Optional[bool] = None):
    """a, b: (M, 128)-shaped equal arrays, M % TILE_M == 0. Returns scalar
    fp32 ||a-b||^2.  (ops.py handles pytree flattening/padding.)
    ``interpret`` resolves through ``repro.kernels.interpret_mode``."""
    m = a.shape[0]
    grid = (m // TILE_M,)
    partials = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE_M, LANE), lambda i: (i, 0)),
            pl.BlockSpec((TILE_M, LANE), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((SUBLANE, LANE), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((SUBLANE, LANE), jnp.float32),
        interpret=interpret_mode(interpret),
    )(a, b)
    return jnp.sum(partials)
