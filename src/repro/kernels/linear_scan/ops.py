"""Jit'd wrappers mapping model-layer shapes onto the linear_scan kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.linear_scan.kernel import linear_scan


@functools.partial(jax.jit, static_argnames=("chunk", "include_current"))
def recurrence(q, k, v, la, u=None, *, chunk: int = 64,
               include_current: bool = True):
    """Layer shapes: q,k,la (B,S,H,K); v (B,S,H,V); u (H,K) optional.
    Returns y (B,S,H,V)."""
    B, S, H, K = q.shape
    V = v.shape[-1]
    to_bh = lambda t: t.transpose(0, 2, 1, 3).reshape(B * H, S, t.shape[-1])
    ub = None
    if u is not None:
        ub = jnp.broadcast_to(u[None], (B, H, K)).reshape(B * H, K)
    y = linear_scan(to_bh(q), to_bh(k), to_bh(v), to_bh(la), ub, chunk=chunk,
                    include_current=include_current)
    return y.reshape(B, H, S, V).transpose(0, 2, 1, 3)
