"""Pure-jnp oracle for the topk_quant kernel.

Semantics (shared spec with kernel.py — the two must match bit-for-bit,
compiled on TPU or in interpret mode):

  keep = |x| >= thr
  q    = clip(floor(clip(x / scale, -127, 127) + u), -127, 127)  where kept
  u    = counter-hash uniform in [0, 1) keyed on (flat index, seed)

The stochastic-rounding randomness is a *deterministic counter hash*
(murmur3-style finalizer on the flat element index) rather than a backend
PRNG, so the kernel and this oracle produce identical bits on any
platform and the codec round-trip is reproducible from (tree, seed).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

QMAX = 127.0  # symmetric int8 range


def hash_uniform(idx, seed):
    """Deterministic uniform [0,1) from uint32 flat index + scalar seed
    (multiply-xorshift finalizer).  kernel.py calls this same function
    inside the Pallas body, so oracle/kernel agreement holds by
    construction; seed may therefore be a traced scalar."""
    x = idx.astype(jnp.uint32) * jnp.uint32(2654435761) \
        + jnp.asarray(seed, jnp.uint32)
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> jnp.uint32(15))
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> jnp.uint32(16))
    # top 24 bits -> [0, 1): exact in fp32, and uint32 -> fp32 is not a
    # cast the TPU kernel compiler supports, int32 -> fp32 is
    return ((x >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32)
            * jnp.float32(2.0 ** -24))


def topk_quant_2d(x, thr, scale, seed):
    """x: (M, 128) fp32; thr/scale: fp32 scalars; seed: uint32 scalar.
    Returns (q int8, mask int8) of x's shape: abs-threshold selection fused
    with stochastic symmetric int8 quantization; dropped entries are 0."""
    x = x.astype(jnp.float32)
    m, lane = x.shape
    idx = jnp.arange(m * lane, dtype=jnp.uint32).reshape(m, lane)
    u = hash_uniform(idx, seed)
    keep = jnp.abs(x) >= thr
    y = jnp.clip(x / scale, -QMAX, QMAX)
    q = jnp.clip(jnp.floor(y + u), -QMAX, QMAX).astype(jnp.int8)
    q = jnp.where(keep, q, jnp.int8(0))
    return q, keep.astype(jnp.int8)


def dequant_2d(q, mask, scale):
    """Inverse map for the kept entries: q * scale where mask else 0."""
    return jnp.where(mask != 0, q.astype(jnp.float32) * scale, 0.0)
