"""Jit'd public wrapper for the grad_diff_norm kernel: pytree in, scalar out.

``tree_grad_diff_sq_norm``: flattens the gradient pytrees into one padded
(M, 128) buffer pair and calls the fused kernel once per run (instead of
per-leaf), maximising the tile pipeline.  ``communication_value`` adds the
Eq. 1 epilogue.  This is the drop-in for ``FLRunConfig.value_backend``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.grad_diff_norm.kernel import LANE, TILE_M, grad_diff_sq_norm_2d

_CHUNK = TILE_M * LANE


def _flatten_pad(tree):
    flat = [jnp.ravel(x).astype(jnp.float32) for x in jax.tree.leaves(tree)]
    v = jnp.concatenate(flat) if len(flat) > 1 else flat[0]
    n = v.shape[0]
    pad = (-n) % _CHUNK
    if pad:
        v = jnp.pad(v, (0, pad))
    return v.reshape(-1, LANE)


@jax.jit
def tree_grad_diff_sq_norm(tree_a, tree_b):
    a = _flatten_pad(tree_a)
    b = _flatten_pad(tree_b)
    return grad_diff_sq_norm_2d(a, b)


@functools.partial(jax.jit, static_argnames=("n_clients",))
def communication_value(tree_a, tree_b, acc, n_clients: int):
    diff = tree_grad_diff_sq_norm(tree_a, tree_b)
    return diff * (1.0 + n_clients / 1e3) ** jnp.asarray(acc, jnp.float32)


def value_backend(tree_a, tree_b):
    """Signature-compatible with repro.common.pytree.tree_sq_diff_norm —
    plug into FLRunConfig(value_backend=...)."""
    return tree_grad_diff_sq_norm(tree_a, tree_b)
