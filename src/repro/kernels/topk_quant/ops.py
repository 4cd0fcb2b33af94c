"""Jit'd public wrappers for the topk_quant kernel: pytree in, planes out.

``pad_2d`` packs a flat vector into the padded (M, 128) layout shared
with grad_diff_norm; ``topk_threshold_scale`` is the O(k log n) scalar
prologue (k-th largest magnitude + symmetric int8 scale); ``topk_quant``
runs the fused kernel (or the ref.py oracle with ``use_kernel=False``)
over the packed buffer.  Pytree flattening and the compact index/value
planes that actually go on the wire live with the codec
(repro.compress.composed / repro.compress.sparsify).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.topk_quant import ref
from repro.kernels.topk_quant.kernel import LANE, TILE_M, topk_quant_2d

_CHUNK = TILE_M * LANE


def pad_2d(flat):
    """flat fp32 vector -> padded (M, 128) layout.  Zero padding never
    survives the |x| >= thr gate (thr > 0), so padded tails cost nothing."""
    n = flat.shape[0]
    pad = (-n) % _CHUNK
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, LANE)


@functools.partial(jax.jit, static_argnames=("k",))
def topk_threshold_scale(x2d, n, k: int):
    """k-th largest |x| over the first n real entries, and the symmetric
    int8 scale max|x|/127.  Padding is excluded by masking to -inf."""
    flat = x2d.ravel()
    absx = jnp.where(jnp.arange(flat.shape[0]) < n, jnp.abs(flat), -jnp.inf)
    top = jax.lax.top_k(absx, k)[0]
    thr = jnp.maximum(top[-1], jnp.float32(1e-12))
    scale = jnp.maximum(top[0], jnp.float32(1e-12)) / jnp.float32(ref.QMAX)
    return thr, scale


def topk_quant(x2d, thr, scale, seed, *, use_kernel: bool = True):
    """Fused select+quantize over the packed buffer -> (q int8, mask int8).
    use_kernel=False routes through the pure-jnp oracle (identical bits)."""
    # normalize before the jit boundary: a Python int above 2^31 would
    # otherwise be abstracted as int32 and overflow
    seed = jnp.asarray(seed, jnp.uint32)
    if use_kernel:
        return topk_quant_2d(x2d, thr, scale, seed)
    return ref.topk_quant_2d(x2d, jnp.float32(thr), jnp.float32(scale), seed)
