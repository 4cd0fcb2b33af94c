"""Jit'd wrapper: GQA-shaped inputs -> flash attention kernel.

Accepts model-layer shapes (B, S, H, hd) + (B, S, KV, hd), broadcasts KV
groups, flattens (B, H) into the kernel's BH grid axis, and restores the
layer layout.  The kernel runs compiled on TPU and in interpret mode on
the CPU backend (``repro.kernels.interpret_mode``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention


@functools.partial(jax.jit, static_argnames=("window", "bq", "bk"))
def gqa_flash_attention(q, k, v, *, window=None, bq: int = 128, bk: int = 128):
    """q (B,S,H,hd), k/v (B,S,KV,hd) -> (B,S,H,hd), causal."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    kq = jnp.repeat(k, G, axis=2)
    vq = jnp.repeat(v, G, axis=2)
    to_bh = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    o = flash_attention(to_bh(q), to_bh(kq), to_bh(vq), bq=bq, bk=bk,
                        window=window)
    return o.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
