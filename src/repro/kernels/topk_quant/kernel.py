"""Pallas TPU kernel: fused abs-threshold top-k selection + stochastic
int8 quantization.

The topk_int8 codec's hot path is: read the flat update once, decide
which entries survive the magnitude threshold, and quantize the
survivors to int8.  Done naively that is three HBM passes (abs-compare
-> divide/round -> mask) over the full fp32 buffer; fused here it is a
single streaming pass over (TILE_M, 128) tiles: compare, hash the flat
element index into stochastic-rounding bits, scale/round/clip, and write
the int8 plane + selection mask — all in VREGs per tile.

Randomness is a counter hash on the global flat index (ref.hash_uniform,
shared with the oracle), not a backend PRNG, so compiled TPU output,
interpret-mode output, and the pure-jnp oracle agree bit-for-bit and a
payload is reproducible from (tree, seed) alone.

Threshold and scale are O(1) scalars computed outside (ops.py); the
kernel receives them as (1, 1) operands pinned to every grid step.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode
from repro.kernels.topk_quant import ref

LANE = 128      # TPU lane width
TILE_M = 256    # sublane tile: (256, 128) fp32 = 128 KiB input per step


def _kernel(x_ref, thr_ref, scale_ref, seed_ref, q_ref, mask_ref):
    i = pl.program_id(0)
    x = x_ref[...].astype(jnp.float32)
    thr = thr_ref[0, 0]
    scale = scale_ref[0, 0]
    seed = seed_ref[0, 0]

    # global flat index of every element in this tile -> rounding bits
    rows = jax.lax.broadcasted_iota(jnp.uint32, (TILE_M, LANE), 0)
    cols = jax.lax.broadcasted_iota(jnp.uint32, (TILE_M, LANE), 1)
    idx = (rows + (i * TILE_M).astype(jnp.uint32)) * jnp.uint32(LANE) + cols
    u = ref.hash_uniform(idx, seed)

    keep = jnp.abs(x) >= thr
    y = jnp.clip(x / scale, -ref.QMAX, ref.QMAX)
    q = jnp.clip(jnp.floor(y + u), -ref.QMAX, ref.QMAX).astype(jnp.int8)
    q_ref[...] = jnp.where(keep, q, jnp.int8(0))
    mask_ref[...] = keep.astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("interpret",))
def topk_quant_2d(x, thr, scale, seed, *, interpret: Optional[bool] = None):
    """x: (M, 128) fp32, M % TILE_M == 0; thr/scale fp32 scalars; seed
    uint32 scalar.  Returns (q int8, mask int8), both (M, 128).
    (ops.py handles pytree flattening/padding and the scalar prologue.)
    ``interpret`` resolves through ``repro.kernels.interpret_mode``."""
    m = x.shape[0]
    grid = (m // TILE_M,)
    scalar = lambda v, dt: jnp.asarray(v, dt).reshape(1, 1)
    pinned = pl.BlockSpec((1, 1), lambda i: (0, 0))
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((TILE_M, LANE), lambda i: (i, 0)),
            pinned, pinned, pinned,
        ],
        out_specs=[
            pl.BlockSpec((TILE_M, LANE), lambda i: (i, 0)),
            pl.BlockSpec((TILE_M, LANE), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, LANE), jnp.int8),
            jax.ShapeDtypeStruct((m, LANE), jnp.int8),
        ],
        interpret=interpret_mode(interpret),
    )(x.astype(jnp.float32), scalar(thr, jnp.float32),
      scalar(scale, jnp.float32), scalar(seed, jnp.uint32))
